"""The reduction from a profiler trace to device time, on a small trace
recorded on the CPU: the union of busy intervals, the idle share over
the time the batcher held work, and device time per step program, keyed
by the entry shape the calibration launched and not by launch order."""

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace as TR


def test_interval_arithmetic():
    merged = TR.union([(5, 7), (0, 2), (1, 3), (7, 9), (12, 13)])
    assert merged == [(0, 3), (5, 9), (12, 13)]
    assert TR.overlap(merged, 2, 12) == 1 + 4
    assert TR.subtract(0, 15, merged) == [(3, 5), (9, 12), (13, 15)]
    assert TR.subtract(4, 8, merged) == [(4, 5)]


def _fake_batcher(n_slots=4, chunk=32):
    def decode(p, c, b):
        x = jnp.tanh(p["w"] @ p["w"]) + b["tokens"].sum()
        return x, c

    def prefill(p, c, b):
        x = jax.lax.fori_loop(0, 12, lambda i, x: jnp.tanh(x @ p["w"]),
                              p["w"]) + b["tokens"].sum()
        return x, c

    return SimpleNamespace(
        n_slots=n_slots, prefill_chunk=chunk,
        params={"w": jnp.ones((192, 192)) / 192},
        cache={"k": jnp.zeros((n_slots, 8))},
        decode_fn=jax.jit(decode), prefill_fn=jax.jit(prefill))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    b = _fake_batcher()
    calib = TR.Calibration(b)
    dec = calib.batches["decode"]
    pre = calib.batches["prefill"]
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    calib.launch()
    with jax.profiler.TraceAnnotation("chipbench/window"):
        # prefill launches first and decode after: the roles must come
        # from the calibration's shapes, not from this order
        for _ in range(3):
            jax.block_until_ready(b.prefill_fn(b.params, b.cache, pre))
        with jax.profiler.TraceAnnotation("chipbench/wait"):
            time.sleep(0.05)
        for _ in range(5):
            with jax.profiler.TraceAnnotation("chipbench/step"):
                jax.block_until_ready(b.decode_fn(b.params, b.cache, dec))
    jax.profiler.stop_trace()
    return TR.find_xplane(d)


def test_programs_are_keyed_by_entry_shape(recorded):
    s = TR.reduce(recorded, top=100)
    assert len(s.launches["prefill"]) == 3
    assert len(s.launches["decode"]) == 5
    # twelve chained products against one; the shortest launch of each,
    # since a loaded host can only lengthen a launch
    assert min(s.launches["prefill"]) > 3 * min(s.launches["decode"])
    assert {op.split(":")[0] for op, _ in s.top_ops} == {"decode", "prefill"}


def test_busy_union_and_idle_share_over_pending_time(recorded):
    s = TR.reduce(recorded)
    device, host = TR.read_events(recorded)
    (events,) = device.values()
    window = next(h for h in host if h.name == "chipbench/window")
    wait = next(h for h in host if h.name == "chipbench/wait")
    merged = TR.union((e.start, e.end) for e in events)
    busy = TR.overlap(merged, window.start, window.end)
    assert s.busy_s == pytest.approx(busy / 1e9)
    assert s.window_s == pytest.approx((window.end - window.start) / 1e9)
    # the 50 ms wait is not time in which the batcher held work
    assert s.pending_s == pytest.approx(s.window_s - (wait.end - wait.start)
                                        / 1e9)
    busy_pending = (TR.overlap(merged, window.start, wait.start)
                    + TR.overlap(merged, wait.end, window.end))
    assert s.idle_share == pytest.approx(1 - busy_pending / 1e9 / s.pending_s)
    assert 0 < s.busy_s < s.window_s and 0 <= s.idle_share < 1
    # the longest device gap is the wait, and it is named for it
    assert s.gaps[0][0] == "wait" and s.gaps[0][1] >= 0.05


def _ev(start, end, name, stats=()):
    return SimpleNamespace(start_ns=float(start), duration_ns=float(end - start),
                           name=name, stats=list(stats))


def test_tpu_shaped_trace():
    """Modules and ops as a TPU plane lays them out, host annotations
    half a millisecond off the device's clock, prefill launched first."""
    ms = 1_000_000
    dec, pre = "jit__lambda(111)", "jit__lambda(222)"
    modules = [
        _ev(1 * ms, 2 * ms, "jit_copy(9)", [("run_id", 1)]),
        _ev(3 * ms, 13 * ms, dec, [("run_id", 2)]),       # calibration
        _ev(31 * ms, 51 * ms, pre, [("run_id", 3)]),      # calibration
        _ev(101 * ms, 121 * ms, pre, [("run_id", 4)]),    # window
        _ev(130 * ms, 140 * ms, dec, [("run_id", 5)]),
        _ev(200 * ms, 210 * ms, dec, [("run_id", 6)])]
    ops = [_ev(102 * ms, 110 * ms, "%fusion.1 = bf16[4,32,3072] fusion(x)"),
           _ev(130 * ms, 140 * ms, "%while.1 = (s32[], bf16[4,1,3072]) while(z)"),
           _ev(131 * ms, 139 * ms, "%fusion.1 = bf16[4,1,3072] fusion(y)"),
           _ev(201 * ms, 209 * ms, "%fusion.1 = bf16[4,1,3072] fusion(y)")]
    plane = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=modules),
        SimpleNamespace(name="XLA Ops", events=ops),
        SimpleNamespace(name="Async XLA Ops", events=[_ev(0, 300 * ms, "x")])])
    events = TR._tpu_events(plane)
    # the while loop is left out: its body's operations are counted
    assert [(e.name, e.program) for e in events if not e.launch] == [
        ("fusion.1", pre), ("fusion.1", dec), ("fusion.1", dec)]
    off = ms // 2
    host = [TR.Event(0 + off, 20 * ms + off, "chipbench/calibrate/decode"),
            TR.Event(0 + off, 2 * ms, "chipbench/x"),
            TR.Event(25 * ms + off, 60 * ms + off,
                     "chipbench/calibrate/prefill"),
            TR.Event(100 * ms, 220 * ms, "chipbench/window"),
            TR.Event(150 * ms, 190 * ms, "chipbench/wait"),
            TR.Event(122 * ms, 129 * ms, "chipbench/tokens")]
    s = TR.reduce_events({"/device:TPU:0": events}, host)
    assert s.launches == {"prefill": [0.02], "decode": [0.01, 0.01]}
    assert s.window_s == pytest.approx(0.12)
    assert s.busy_s == pytest.approx(0.04)
    assert s.pending_s == pytest.approx(0.08)
    assert s.idle_share == pytest.approx(1 - 0.04 / 0.08)
    assert s.top_ops[0] == ["decode:fusion.1", pytest.approx(0.016)]
    assert s.gaps[0] == ["wait", pytest.approx(0.06)]
    assert s.gaps[1] == ["outside any annotation", pytest.approx(0.01)]
    assert s.gaps[2] == ["tokens", pytest.approx(0.009)]
