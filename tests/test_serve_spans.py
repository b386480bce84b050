"""The serving loop's obs spans (``cat="serve"``).

* one step emits its phase spans in order: refill, plan, prefill_chunk,
  decode_launch, token_fetch, complete, with join nested in complete;
* every request gets one ``queued`` and one ``prompt`` span, whose
  ``launches`` is the DLBC chunk count of its prompt, and a retried
  request is queued again;
* a launch gets a copy of the slot positions, never the live array the
  step goes on to advance;
* under ``jax.profiler`` the live spans are mirrored into the trace,
  and the decode program's operations lie between the ``decode_launch``
  that dispatched them and the ``token_fetch`` that waited for them: one
  clock for the program's spans and the device's work.
"""

import glob
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import model as MDL
from repro.obs import trace as obs
from repro.sched.faults import (
    FaultPlan, FaultSpec, RetryPolicy, injected_faults,
)
from repro.serve import batcher as B
from repro.serve.batcher import ContinuousBatcher, Request

PHASES = ("refill", "plan", "prefill_chunk", "decode_launch", "token_fetch",
          "complete", "join")
CHUNK = 32


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="spans-test", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab=128)
    return cfg, MDL.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _tracer_off():
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def _batcher(model, **kw):
    cfg, params = model
    return ContinuousBatcher(cfg, params, n_slots=4, cache_len=160,
                             prefill_chunk=CHUNK, **kw)


def _serve_spans():
    return sorted((e for e in obs.snapshot()
                   if e["ph"] == "X" and e["cat"] == "serve"),
                  key=lambda e: e["ts_ns"])


def test_step_emits_its_phases_in_order(model):
    b = _batcher(model)
    b.submit(Request(rid=0, prompt=[1, 2], max_new=1))
    b.submit(Request(rid=1, prompt=list(range(3, 43)), max_new=4))
    obs.enable()
    b.step(0)
    spans = [e for e in _serve_spans() if e["name"] in PHASES]
    assert [e["name"] for e in spans] == list(PHASES)
    complete, join = spans[-2], spans[-1]
    assert complete["ts_ns"] <= join["ts_ns"]
    assert (join["ts_ns"] + join["dur_ns"]
            <= complete["ts_ns"] + complete["dur_ns"])
    # rid 0 finished and left; rid 1 still holds its slot
    assert complete["args"] == {"held": 1}
    assert spans[2]["args"] == {"slots": 2, "tokens": 1 + CHUNK}
    assert spans[3]["args"] == {"active": 1}


def test_queued_and_prompt_spans_per_request(model):
    """A 100-token prompt placed beside 3 decoding slots: its 99-token
    prefix goes in as many launches as the DLBC chunk rule gives."""
    b = _batcher(model)
    obs.enable()
    for rid in range(3):
        b.submit(Request(rid=rid, prompt=[1, 2], max_new=80))
    b.step(0)
    b.submit(Request(rid=7, prompt=list(range(100)), max_new=2))
    for now in range(1, 20):
        b.step(now)
        if not b._prefilling:
            break
    rem, expect = 99, 0
    while rem:
        c = b.sched.policy.prefill_chunk_len(rem, 3, CHUNK)
        rem -= max(1, min(int(c), rem, CHUNK))
        expect += 1
    assert expect > 3   # chunked, not one launch

    spans = _serve_spans()
    queued = {e["args"]["rid"]: e for e in spans if e["name"] == "queued"}
    prompt = {e["args"]["rid"]: e for e in spans if e["name"] == "prompt"}
    assert sorted(queued) == sorted(prompt) == [0, 1, 2, 7]
    assert all(e["args"]["attempt"] == 0 for e in queued.values())
    for rid in range(3):
        assert prompt[rid]["args"] == {"rid": rid, "tokens": 1,
                                       "launches": 1}
    assert prompt[7]["args"] == {"rid": 7, "tokens": 99, "launches": expect}
    q, p = queued[7], prompt[7]
    assert q["ts_ns"] + q["dur_ns"] <= p["ts_ns"]
    chunks = [e for e in spans if e["name"] == "prefill_chunk"
              and e["ts_ns"] >= p["ts_ns"]]
    assert len(chunks) == expect
    assert sum(e["args"]["tokens"] for e in chunks) == 99


def test_a_retried_request_is_queued_again(model):
    plan = FaultPlan([FaultSpec(site="serve.request", kind="raise",
                                every=1)])
    b = _batcher(model, retry=RetryPolicy(attempts=2))
    obs.enable()
    with injected_faults(plan):
        b.run([Request(rid=5, prompt=[1, 2, 3], max_new=4)])
    assert b.stats.failed == 1
    assert [e["args"] for e in _serve_spans() if e["name"] == "queued"] == [
        {"rid": 5, "attempt": 0}, {"rid": 5, "attempt": 1}]


class _RecordingJnp:
    """``jax.numpy`` that keeps every host array handed to ``asarray``."""

    def __init__(self):
        self.host_arrays = []

    def __getattr__(self, name):
        return getattr(jnp, name)

    def asarray(self, a, *args, **kwargs):
        if isinstance(a, np.ndarray):
            self.host_arrays.append(a)
        return jnp.asarray(a, *args, **kwargs)


def test_launches_get_a_copy_of_the_slot_positions(model, monkeypatch):
    """``slot_pos`` advances right after a launch is dispatched; on the
    CPU ``jnp.asarray`` may alias the host array, and an aliased launch
    could read the positions after its own chunk."""
    rec = _RecordingJnp()
    monkeypatch.setattr(B, "jnp", rec)
    b = _batcher(model)
    b.submit(Request(rid=0, prompt=list(range(70)), max_new=3))
    b.submit(Request(rid=1, prompt=[4, 5], max_new=3))
    for now in range(6):
        b.step(now)
    assert rec.host_arrays
    assert not any(np.shares_memory(a, b.slot_pos) for a in rec.host_arrays)


def _xplane_events(path):
    """``(mirrored serve/* annotations, decode-program operations)`` on
    the host plane, each as ``(name, start_ns, end_ns)``."""
    spans, ops = [], []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    start = int(e.start_ns)
                    ev = (e.name, start, start + int(e.duration_ns))
                    if e.name.startswith("serve/"):
                        spans.append(ev)
                    elif dict(e.stats).get("hlo_module") == "jit__lambda":
                        ops.append(ev)
    return spans, ops


def test_spans_share_the_profiler_clock_with_the_step_programs(
        model, tmp_path):
    b = _batcher(model)
    for rid in range(2):
        b.submit(Request(rid=rid, prompt=[1, 2, 3], max_new=10))
    for now in range(2):          # compiles; every prefix is in the cache
        b.step(now)
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    b.step(2)                     # decode only: one jit__lambda program
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans, ops = _xplane_events(path)
    names = [n for n, _, _ in sorted(spans, key=lambda s: s[1])]
    assert names == ["serve/refill", "serve/decode_launch",
                     "serve/token_fetch", "serve/complete"]
    (launch,) = [s for s in spans if s[0] == "serve/decode_launch"]
    (fetch,) = [s for s in spans if s[0] == "serve/token_fetch"]
    assert launch[1] < fetch[1]
    assert ops
    assert all(launch[1] <= s and e <= fetch[2] for _, s, e in ops)
    # the program's own ring saw the same spans
    assert [e["name"] for e in _serve_spans()] == [n[6:] for n in names]
