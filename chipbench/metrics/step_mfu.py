"""The whole serving step's share of the chip's bf16 peak, in percent:
the FLOPs the run's steps needed (``costs.request_flops``: each
delivered token's decode, and the prompt of each request whose first
token they delivered) over the steps' summed wall time times the peak.
Below the knee the load fixes the work per second, so this divides by
the time the steps took, not by the window's."""

from chipbench import costs


def read(run):
    steps_s = sum(s.end_s - s.start_s for s in run.window.steps)
    flops = sum(costs.request_flops(run.cfg, len(s.req.prompt), 0,
                                    len(s.token_s), prefill=True)
                for s in run.window.served.values() if s.token_s)
    if not flops or steps_s <= 0:
        return None
    return 100.0 * flops / (steps_s * run.peaks["bf16_flops_per_s"])
