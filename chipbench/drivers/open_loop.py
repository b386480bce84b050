"""Open loop: requests arrive on the mix's schedule whether or not earlier
ones have finished, and each is timed from when it was due.

After the window closes the run keeps serving, and keeps offering the
mix's load, until every request due in the window has finished or the
mix's ``drain_s`` has passed.  A request still without a token then is
counted with the time it had waited."""

from __future__ import annotations


def drive(window, offered, seconds: float, mix: dict):
    pending = {o.rid for o in offered if o.counted}
    i, n = 0, len(offered)
    window.open()
    while True:
        now = window.now()
        while i < n and offered[i].due_s <= now:
            window.submit(offered[i])
            i += 1
        if now >= seconds:
            pending = {rid for rid in pending
                       if rid not in window.served
                       or not window.served[rid].done}
            if not pending or now >= seconds + mix["drain_s"]:
                break
        if window.has_work():
            window.step()
        elif i < n:
            window.wait_until(offered[i].due_s)
        else:
            break
    window.close()
