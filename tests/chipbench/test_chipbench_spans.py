"""The readers of the program's serving-loop spans, on span lists made by
hand: ``host_gap_ms`` pairs each token fetch with the next launch and
leaves out a step after which nothing was held; ``queue_wait_p95_ms`` and
``prompt_p95_ms`` take each counted request's first span and leave out
requests that were not due in the window.  A run whose program has none
of these spans (an older program) reads nothing and does not raise."""

from types import SimpleNamespace

import numpy as np
import pytest

from chipbench.metrics import host_gap_ms, prompt_p95_ms, queue_wait_p95_ms

MS = 1_000_000


def _span(name, start_ms, end_ms, **args):
    start, end = round(start_ms * MS), round(end_ms * MS)
    return {"ph": "X", "cat": "serve", "name": name, "ts_ns": start,
            "dur_ns": end - start, "n": 1, "args": args or None}


def _step(t, held, prefill=False, fetch=True):
    """One step from ``t`` ms: refill, optionally plan and a prefill
    launch, a decode launch, a 30 ms token fetch and complete."""
    out = [_span("refill", t, t + 0.1)]
    if prefill:
        out += [_span("plan", t + 0.1, t + 0.3),
                _span("prefill_chunk", t + 0.3, t + 0.6, slots=1, tokens=8)]
    out.append(_span("decode_launch", t + 0.6, t + 1.0, active=2))
    if fetch:
        out.append(_span("token_fetch", t + 1.0, t + 31.0))
    out.append(_span("complete", t + 31.0, t + 31.5, held=held))
    return out


def _run(spans, served=None):
    return SimpleNamespace(spans=spans, window=SimpleNamespace(
        served=served or {}))


def test_host_gap_pairs_each_fetch_with_the_next_launch():
    # fetch ends at 31; next step at 33: its decode launch ends at 34.0
    # (3.0 ms); the one after has a prefill launch ending at 65.6 (from
    # a fetch ending at 64.0, 1.6 ms)
    spans = _step(0, 2) + _step(33, 2) + _step(65, 1, prefill=True)
    assert host_gap_ms.gaps_ns(spans) == [3 * MS, round(1.6 * MS)]
    assert host_gap_ms.read(_run(spans)) == pytest.approx(2.3)


def test_host_gap_leaves_out_a_step_that_left_nothing_held():
    # held == 0 after the first step: the batcher waited for an arrival,
    # and the 500 ms until the next launch is not the host's critical path
    spans = _step(0, 0) + _step(530, 3) + _step(562, 3)
    assert host_gap_ms.gaps_ns(spans) == [2 * MS]
    # a step without a fetch (only prefill) does not start a gap
    spans = _step(0, 1, prefill=True, fetch=False) + _step(40, 1)
    assert host_gap_ms.read(_run(spans)) is None


def test_host_gap_reads_the_spans_in_order_of_start():
    # rings hold spans in order of their end; the reader sorts
    spans = _step(0, 2) + _step(33, 2)
    assert host_gap_ms.gaps_ns(spans[::-1]) == [3 * MS]


def _served(counted):
    return {rid: SimpleNamespace(offered=SimpleNamespace(counted=c))
            for rid, c in counted.items()}


def test_queue_and_prompt_take_each_counted_request_once():
    served = _served({1: True, 2: True, 3: True, 4: False})
    spans = [
        _span("queued", 0, 10, rid=1, attempt=0),
        _span("queued", 0, 20, rid=2, attempt=0),
        _span("queued", 5, 35, rid=3, attempt=0),
        _span("queued", 50, 950, rid=3, attempt=1),   # a retry: not first
        _span("queued", 0, 5000, rid=4, attempt=0),   # not due in the window
        _span("queued", 0, 7000, rid=-1, attempt=0),  # warm-up, not served
        _span("prompt", 10, 110, rid=1, tokens=63, launches=4),
        _span("prompt", 20, 420, rid=2, tokens=255, launches=12),
        _span("prompt", 35, 235, rid=3, tokens=127, launches=6),
        _span("prompt", 5000, 9000, rid=4, tokens=800, launches=30),
    ]
    run = _run(spans, served)
    assert queue_wait_p95_ms.read(run) == pytest.approx(
        np.percentile([10, 20, 30], 95))
    assert prompt_p95_ms.read(run) == pytest.approx(
        np.percentile([100, 400, 200], 95))


@pytest.mark.parametrize("spans", [
    None, [],
    # what an older program emits: no lifecycle spans, no fetch, no held
    [_span("refill", 0, 1), _span("decode", 1, 30, active=4),
     _span("complete", 30, 31)],
])
def test_no_spans_to_read_gives_no_reading(spans):
    run = _run(spans, _served({0: True}))
    for reader in (host_gap_ms, queue_wait_p95_ms, prompt_p95_ms):
        assert reader.read(run) is None
