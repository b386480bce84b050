"""The decode program's share of its memory roofline for a latent-attention
expert model, in percent: the least time to move the bytes a decode step
needs (``costs_mla_moe.decode_step_bytes``: the weights other than the
held experts, each held expert the launch's tokens hit, each slot's
valid latent entries and the one written) at the chip's HBM bandwidth,
over the mean device time of a decode launch in the trace.  The experts
hit come from the program's ``serve/decode_launch`` spans (arg
``experts_hit``)."""

import numpy as np

from chipbench import costs_mla_moe as C
from chipbench.spans import serve


def read(run):
    t = run.trace
    if t is None or not t.launches.get("decode") or not run.spans:
        return None
    hit = [e["args"]["experts_hit"]
           for e in serve(run.spans, ("decode_launch",))
           if "experts_hit" in e["args"]]
    steps = [s.positions for s in run.window.steps if s.positions]
    if not hit or not steps:
        return None
    experts_hit = float(np.mean(hit))
    mean_bytes = float(np.mean([C.decode_step_bytes(run.cfg, p, experts_hit)
                                for p in steps]))
    least_s = mean_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t.mean_launch_s("decode")
