"""95th percentile of the gaps between consecutive generated tokens of the
same request, over every request due in the window."""

from chipbench.stats import percentile, token_gaps


def read(run):
    return 1e3 * percentile(token_gaps(run.window), 95)
