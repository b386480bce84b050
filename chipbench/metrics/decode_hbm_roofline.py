"""The decode program's share of its memory roofline, in percent: the
least time to move the bytes a decode step needs (``costs.
decode_step_bytes``: the weights, the active slots' valid keys and
values, and the ones written), at the chip's HBM bandwidth, over the
mean device time of a decode launch in the trace."""

from chipbench import costs


def read(run):
    t = run.trace
    if t is None or not t.launches.get("decode"):
        return None
    steps = [s.positions for s in run.window.steps if s.positions]
    if not steps:
        return None
    mean_bytes = sum(costs.decode_step_bytes(run.cfg, p)
                     for p in steps) / len(steps)
    least_s = mean_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t.mean_launch_s("decode")
