"""The whole serving step's share of the chip's bf16 peak, in percent: the
FLOPs that the window's work needs (``costs.request_flops``: the prompts
of requests whose first token came in the window, and every token
delivered in it), over the window's seconds times the peak."""

from chipbench import costs


def read(run):
    flops = 0.0
    for s in run.window.served.values():
        n = sum(1 for t in s.token_s if t <= run.seconds)
        if n:
            flops += costs.request_flops(run.cfg, len(s.req.prompt), 0, n,
                                         prefill=True)
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"])
