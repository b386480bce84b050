"""Mean device milliseconds per launch of the prefill program (the step
program whose tokens are ``(n_slots, prefill_chunk)``), from the
profiler trace."""


def read(run):
    t = run.trace
    if t is None or not t.launches.get("prefill"):
        return None
    return 1e3 * t.mean_launch_s("prefill")
