"""Seconds from process start to the window's opening: backend start,
weights, the batcher and the warm-up (with compilation, where the cache
misses)."""


def read(run):
    return run.setup_s
