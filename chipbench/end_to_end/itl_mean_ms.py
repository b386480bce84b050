"""Mean of the gaps between consecutive generated tokens of the same
request, over every request due in the window.

Not the median: about half of the gaps come from steps that also carry
a prefill launch, and those take twice as long, so the median sits on
one of two modes (34 or 69 ms for phi3-mini-3.8b on a v5e) and flips
between them with the smallest change in how many steps carry one."""

from chipbench.stats import token_gaps


def read(run):
    g = token_gaps(run.window)
    return 1e3 * sum(g) / len(g) if g else None
