"""Mean device milliseconds per launch of the decode program (the step
program whose tokens are ``(n_slots, 1)``), from the profiler trace."""


def read(run):
    t = run.trace
    if t is None or not t.launches.get("decode"):
        return None
    return 1e3 * t.mean_launch_s("decode")
