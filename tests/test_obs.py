"""repro.obs: tracer no-op guarantees, ring bounds, Chrome export schema,
and the trace↔telemetry conservation cross-check."""

import json
import time

import pytest

from repro.obs import export as obs_export
from repro.obs import trace as obs
from repro.sched import (
    LogHistogram, MultipleExceptions, SchedTelemetry, ThreadExecutor,
    WorkStealingExecutor,
)
from repro.sched.telemetry import ExchangeCounters


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts and ends with the tracer disabled and empty —
    the default-off contract the rest of the suite relies on."""
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


# -- disabled-by-default is a true no-op ------------------------------------

def test_disabled_emits_nothing():
    obs.instant("sched", "spawn", n=3)
    with obs.trace_span("worker", "task"):
        pass
    obs.complete_span("sched", "steal", obs.perf_counter_ns())
    assert obs.snapshot() == []
    assert obs.ring_stats() == []


def test_disabled_span_is_shared_noop():
    # no allocation when disabled: the same singleton every call
    assert obs.trace_span("a", "b") is obs.trace_span("c", "d")


def test_disabled_executor_run_emits_nothing():
    ex = WorkStealingExecutor(n_workers=2)
    try:
        ex.run_loop(list(range(32)), lambda x: x * x)
    finally:
        ex.shutdown()
    assert obs.snapshot() == []


def test_disabled_emit_cost_is_negligible():
    # generous wall bound: 200k disabled emits must be ~instant (each is
    # one global read + return); catches an accidental allocation or
    # clock read sneaking into the disabled path
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs.instant("sched", "spawn")
        with obs.trace_span("worker", "task"):
            pass
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"{n} disabled emits took {dt:.2f}s"


def test_disabled_batcher_run_emits_nothing_and_reads_no_clock(monkeypatch):
    # the serving loop's step phases and request lifecycle spans cost a
    # flag read each while tracing is off: no event, no obs clock read
    import jax

    from repro.configs.base import ModelConfig
    from repro.models import model as MDL
    from repro.serve.batcher import ContinuousBatcher, Request

    reads = []
    monkeypatch.setattr(obs, "perf_counter_ns",
                        lambda: reads.append(1) or time.perf_counter_ns())
    cfg = ModelConfig(name="obs-off", family="dense", n_layers=1,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    b = ContinuousBatcher(cfg, MDL.init_params(cfg, jax.random.PRNGKey(0)),
                          n_slots=2, cache_len=64, prefill_chunk=8)
    stats = b.run([Request(rid=0, prompt=list(range(30)), max_new=3),
                   Request(rid=1, prompt=[1], max_new=4),
                   Request(rid=2, prompt=[2, 3], max_new=2, arrive_step=1)])
    assert len(stats.latencies) == 3
    assert obs.snapshot() == [] and reads == []


# -- enabled semantics -------------------------------------------------------

def test_span_and_instant_recorded():
    obs.enable()
    with obs.trace_span("worker", "task", {"k": 1}):
        time.sleep(0.001)
    obs.instant("sched", "spawn", n=4)
    evs = obs.snapshot()
    spans = [e for e in evs if e["ph"] == "X"]
    insts = [e for e in evs if e["ph"] == "i"]
    assert len(spans) == 1 and len(insts) == 1
    assert spans[0]["dur_ns"] >= 1_000_000
    assert spans[0]["args"] == {"k": 1}
    assert insts[0]["n"] == 4


def test_disable_mid_span_drops_event():
    obs.enable()
    with obs.trace_span("worker", "task"):
        obs.disable()
    assert obs.snapshot() == []


def test_ring_bounded_and_counts_drops():
    obs.enable(capacity=64)
    for i in range(1000):
        obs.instant("sched", "spawn")
    (stats,) = [s for s in obs.ring_stats() if s["n_events"]]
    assert stats["n_events"] == 64
    assert stats["dropped"] == 1000 - 64
    # oldest events were overwritten: the survivors are the newest 64
    assert len(obs.snapshot()) == 64


def test_ring_bounds_hold_under_executor_stress():
    obs.enable(capacity=128)
    ex = WorkStealingExecutor(n_workers=4)
    try:
        skew = [0.003 if i < 8 else 0.0 for i in range(64)]
        for _ in range(10):
            ex.run_loop(skew, time.sleep)
    finally:
        ex.shutdown()
    stats = obs.ring_stats()
    assert stats, "no rings registered under stress"
    for s in stats:
        assert s["n_events"] <= 128, s
    assert len(obs.snapshot()) <= 128 * len(stats)


def test_clear_resets_between_passes():
    obs.enable()
    obs.instant("sched", "spawn")
    assert obs.snapshot()
    obs.clear()
    assert obs.snapshot() == []
    obs.instant("sched", "join")  # same thread re-registers post-epoch
    assert len(obs.snapshot()) == 1


# -- Chrome trace-event export ----------------------------------------------

def _traced_run():
    obs.enable()
    ex = WorkStealingExecutor(n_workers=4)
    try:
        skew = [0.005 if i < 8 else 0.001 for i in range(64)]
        ex.run_loop(skew, time.sleep)
        return ex.telemetry.summary()
    finally:
        ex.shutdown()


def test_chrome_trace_schema():
    summary = _traced_run()
    doc = obs_export.chrome_trace(extra={"telemetry": summary})
    # the whole doc must survive a JSON roundtrip (CI writes/reads it)
    doc = json.loads(json.dumps(doc))
    evs = doc["traceEvents"]
    assert evs, "trace is empty after a traced run"
    names = set()
    for e in evs:
        assert e["ph"] in ("X", "i", "M"), e
        if e["ph"] == "M":
            assert e["name"] == "thread_name" and e["args"]["name"]
            continue
        names.add(e["name"])
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        else:
            assert e["s"] == "t"
    # scheduling-edge vocabulary present
    assert {"spawn", "join", "complete"} <= names
    # every emitting thread has a named track
    tracks = {e["tid"] for e in evs if e["ph"] == "M"}
    assert {e["tid"] for e in evs if e["ph"] != "M"} <= tracks


def test_crosscheck_matches_telemetry():
    summary = _traced_run()
    doc = obs_export.chrome_trace()
    check = obs_export.crosscheck(doc, summary)
    assert check["ok"], check["mismatches"]
    # the counts are real, not vacuous zeros
    assert check["trace"]["spawns"] > 0
    assert check["trace"]["completions"] == check["trace"]["spawns"]


def test_crosscheck_detects_mismatch():
    summary = _traced_run()
    summary["spawns"] += 1
    check = obs_export.crosscheck(obs_export.chrome_trace(), summary)
    assert not check["ok"]
    assert any("spawns" in m for m in check["mismatches"])


def test_derived_metrics_occupancy():
    _traced_run()
    doc = obs_export.chrome_trace()
    d = obs_export.derived_metrics(doc)
    assert d["wall_ms"] > 0
    assert d["per_worker"], "no worker occupancy derived"
    for w in d["per_worker"].values():
        assert 0.0 <= w["occupancy"] <= 1.0
        assert 0.0 <= w["idle_frac"] <= 1.0
    assert any(k.startswith("worker.") for k in d["span_stats"])


def test_write_chrome_trace_file(tmp_path):
    _traced_run()
    path = tmp_path / "t.trace.json"
    doc = obs_export.write_chrome_trace(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk["traceEvents"]
    assert on_disk["derived"]["counts"] == doc["derived"]["counts"]


def test_errors_traced_and_contained():
    obs.enable()
    tel = SchedTelemetry()
    ex = ThreadExecutor(n_workers=2, telemetry=tel)

    def boom(x):
        if x == 3:
            raise ValueError(x)

    try:
        # spawned-item exceptions are contained (counted, collected) and
        # the per-loop join rethrows them all as ONE MultipleExceptions;
        # a caller-chunk raise would propagate raw like a plain for loop
        with pytest.raises((MultipleExceptions, ValueError)):
            ex.run_loop(list(range(8)), boom)
    finally:
        ex.shutdown()
    check = obs_export.crosscheck(obs_export.chrome_trace(), tel.summary())
    assert check["ok"], check["mismatches"]
    # containment: a raising spawned task still completes, so the task
    # counters close even though the join rethrew
    assert check["trace"]["completions"] == check["trace"]["spawns"]
    if tel.errors:
        # item 3 ran in a spawned chunk: the error instant carries its
        # site, and the per-site breakdown crosschecks (already covered
        # by check["ok"] — assert the count explicitly for clarity)
        assert check["trace"]["errors"] == 1
        assert tel.errors_by_site == {"sched.item": 1}


# -- open spans at export time (truncated, not dropped) ----------------------

def test_open_span_survives_export_as_truncated():
    obs.enable()
    span = obs.trace_span("serve", "decode", {"slot": 1})
    span.__enter__()  # still open when the export happens
    try:
        doc = obs_export.chrome_trace()
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert len(xs) == 1, "open span was silently dropped at export"
        (e,) = xs
        assert e["trunc"] is True
        assert e["args"]["trunc"] is True
        assert e["name"] == "decode" and e["cat"] == "serve"
        assert e["dur"] >= 0
    finally:
        span.__exit__(None, None, None)
    # after a normal exit the span is emitted once, closed, not truncated
    evs = obs.snapshot()
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(spans) == 1 and not spans[0].get("trunc")
    assert obs.open_span_events() == []


def test_closed_spans_not_marked_truncated():
    obs.enable()
    with obs.trace_span("worker", "task"):
        pass
    doc = obs_export.chrome_trace()
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 1 and "trunc" not in xs[0]


def test_truncated_spans_are_conservation_safe():
    # an open span swept into the export must not disturb the counter
    # crosscheck: spans are never counted, only instants are
    summary = _traced_run()
    span = obs.trace_span("serve", "step")
    span.__enter__()
    try:
        doc = obs_export.chrome_trace()
        check = obs_export.crosscheck(doc, summary)
        assert check["ok"], check["mismatches"]
        assert any(e.get("trunc") for e in doc["traceEvents"]
                   if e.get("ph") == "X")
    finally:
        span.__exit__(None, None, None)


def test_export_without_open_spans_flag():
    obs.enable()
    span = obs.trace_span("serve", "decode")
    span.__enter__()
    try:
        doc = obs_export.chrome_trace(include_open=False)
        assert [e for e in doc["traceEvents"] if e.get("ph") == "X"] == []
    finally:
        span.__exit__(None, None, None)


# -- telemetry growth (satellites) ------------------------------------------

def test_summary_has_completions_errors_and_hist():
    tel = SchedTelemetry()
    tel.record_latency(0.002)
    tel.record_latency(0.1)
    s = tel.summary()
    assert s["completions"] == 0 and s["errors"] == 0
    h = s["latency_hist"]
    assert h["n"] == 2 and h["p99_ms"] >= h["p50_ms"]
    assert h["tail_p99_p50"] >= 1.0


def test_log_histogram_buckets_and_merge():
    a, b = LogHistogram(), LogHistogram()
    a.extend([1e-6, 2e-6, 4e-6])
    b.extend([1e-3] * 97)
    a.merge(b)
    s = a.summary()
    assert s["n"] == 100
    # p50 lands in the 1ms bucket; upper-edge convention overestimates
    # by at most one bucket (×2)
    assert 1.0 <= s["p50_ms"] <= 2.1
    assert s["max_ms"] >= 1.0
    assert s["tail_p99_p50"] >= 1.0


def test_log_histogram_diff_windows():
    old = LogHistogram()
    old.extend([1e-3] * 10)
    new = old.copy()
    new.extend([5e-2] * 5)
    d = new.diff(old)
    s = d.summary()
    assert s["n"] == 5
    # the window holds only the 50ms observations: p50 lands in that
    # bucket (upper-edge convention overestimates by at most x2)
    assert 50.0 <= s["p50_ms"] <= 110.0
    # the originals are untouched (diff never resets global state)
    assert old.summary()["n"] == 10 and new.summary()["n"] == 15


def test_log_histogram_diff_rejects_negative_window():
    a, b = LogHistogram(), LogHistogram()
    b.extend([1e-3, 1e-3])
    a.extend([1e-3])
    with pytest.raises(ValueError):
        a.diff(b)  # "newer" has fewer observations than "older"


def test_log_histogram_merge_rejects_bucket_mismatch():
    a, b = LogHistogram(), LogHistogram()
    b.counts = b.counts[:-1]  # simulate a deserialized foreign shape
    with pytest.raises(ValueError):
        a.merge(b)
    with pytest.raises(ValueError):
        a.diff(b)


def test_exchange_posted_completed_split():
    ex = ExchangeCounters()
    ex.posted += 2
    ex.completed += 1
    assert ex.in_flight == 1
    assert ex.rounds == 1  # legacy alias == completed
    s = ex.summary()
    assert s["posted"] == 2 and s["completed"] == 1 and s["rounds"] == 1


def test_record_exchange_legacy_rounds_alias():
    tel = SchedTelemetry()
    tel.record_exchange(sent=4, received=4, rounds=2)
    assert tel.exchange.posted == 2 and tel.exchange.completed == 2
    tel.record_exchange(posted=1)
    tel.record_exchange(completed=1, sent=1, received=1)
    assert tel.exchange.posted == 3 and tel.exchange.completed == 3
    assert tel.exchange.in_flight == 0
    assert tel.summary()["exchange"]["rounds"] == 3
