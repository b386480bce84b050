"""The program's own obs spans (``repro.obs.trace``) of a traced run, as
the per-layer readers take them: the serving loop's ``cat="serve"``
spans, each a dict with ``ts_ns``, ``dur_ns``, ``name`` and ``args``."""

from __future__ import annotations


def serve(spans, names=None) -> list:
    """The ``serve`` spans (of ``names``, if given), in order of start."""
    return sorted((e for e in spans if e["ph"] == "X" and e["cat"] == "serve"
                   and (names is None or e["name"] in names)),
                  key=lambda e: e["ts_ns"])


def first_per_request_ms(run, name: str) -> list:
    """Milliseconds of the first ``serve/<name>`` span of each request
    due in the window (a retried request has one span per attempt)."""
    first = {}
    for e in serve(run.spans, (name,)):
        first.setdefault(e["args"]["rid"], e)
    served = run.window.served
    return [e["dur_ns"] / 1e6 for rid, e in first.items()
            if rid in served and served[rid].offered.counted]
