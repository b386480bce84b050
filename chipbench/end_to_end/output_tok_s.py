"""Generated tokens delivered to the host inside the window, over the
window's seconds."""


def read(run):
    n = sum(1 for s in run.window.served.values() for t in s.token_s
            if t <= run.seconds)
    return n / run.seconds
