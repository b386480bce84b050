"""CPU rehearsal of whole benchmark runs at smoke size, through the same
harness, drivers, reference and metric readers as on the chip; only the
harness's look for a chip is skipped (the result names the CPU).

* both drivers end to end, traced and untraced, with the reference
  comparison passing on the real serving path;
* the float8 control and faults planted in the program's decode step
  fail that comparison;
* a cell made of nothing but new files and new entries is found and
  run, and no file that was there changes.

The batcher's host arrays are copied before they reach a launch here.
On the CPU backend ``jnp.asarray`` of its 1-D ``slot_pos`` array aliases
that array's memory, and ``_prefill_phase`` increments ``slot_pos``
right after launching the prefill step without waiting for it, so an
asynchronously dispatched launch can read positions from after its own
chunk and write the prompt at the wrong place: every served token then
disagrees with the reference.  That is a fault of the program, listed
first among the open questions in ``PERF.md``; on the TPU the host
array is copied during the call.
"""

import filecmp
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench.run as R
from chipbench import control
from chipbench.spec import Cell
from chipbench_smoke import REPO, SMOKE_LIMIT, SMOKE_PHI3, add_cell, make_root

SEED = 2**31 + 17
SECONDS = 1.5
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("chipbench"))


class _CopyingJnp:
    """``jax.numpy`` whose ``asarray`` copies a host array first."""

    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def asarray(a, *args, **kwargs):
        if isinstance(a, np.ndarray):
            a = a.copy()
        return jnp.asarray(a, *args, **kwargs)


@pytest.fixture(autouse=True)
def cpu_rehearsal(monkeypatch):
    from repro.serve import batcher

    # the CPU has no entry in the peaks table; lend it the v5e's so the
    # readers' arithmetic runs
    monkeypatch.setattr(R, "peaks_for", lambda kind: V5E)
    monkeypatch.setattr(batcher, "jnp", _CopyingJnp())


def _run(root, cell, trace=False, seed=SEED):
    return R.run_cell(Cell(cell, root=root), seed, SECONDS, trace,
                      jax.devices()[:1], t_process=time.perf_counter())


@pytest.mark.parametrize("cell,e2e", [
    ("phi3-smoke-poisson", {"ttft_p95_ms", "itl_p95_ms", "itl_mean_ms",
                            "setup_s"}),
    ("minitron-smoke-backlog", {"output_tok_s", "setup_s"})])
def test_untraced_run_is_correct(root, cell, e2e):
    out = _run(root, cell)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["window_compiles"]["value"] == 0
    assert 0 <= out["checks"]["max_logit_gap"]["value"] <= SMOKE_LIMIT
    assert list(out)[-1] == "checks"
    json.dumps(out)


@pytest.mark.parametrize("cell,per_layer", [
    ("phi3-smoke-poisson", {"batcher_host_ms.rate", "prefill_fill.rate",
                            "decode_ms.rate", "prefill_ms.rate",
                            "decode_hbm_roofline.rate", "step_mfu.rate",
                            "idle_share.rate"}),
    ("minitron-smoke-backlog", {"batcher_host_ms.backlog", "decode_ms.backlog",
                                "decode_hbm_roofline.backlog",
                                "serve_mfu.backlog", "idle_share.backlog"})])
def test_traced_run_reads_every_layer(root, cell, per_layer):
    out = _run(root, cell, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == per_layer
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    bd = out["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert {op.split(":")[0] for op, _ in bd["device_ops"]} <= {"decode",
                                                                 "prefill"}


@pytest.mark.parametrize("cell", ["phi3-smoke-poisson",
                                  "minitron-smoke-backlog"])
def test_float8_control_fails_the_limit(root, cell):
    r = control.readings(Cell(cell, root=root), SEED, SECONDS)
    assert r["tokens"] >= R.SAMPLE_TOKENS
    assert r["program"] <= SMOKE_LIMIT < r["control"]


def _token_altered(decode_step):
    def broken(params, cfg, cache, batch):
        logits, cache = decode_step(params, cfg, cache, batch)
        return jnp.roll(logits, 1, axis=-1), cache
    return broken


def _state_unchanged(decode_step):
    def broken(params, cfg, cache, batch):
        logits, _ = decode_step(params, cfg, cache, batch)
        return logits, cache
    return broken


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged])
def test_fault_in_the_timed_path_is_not_correct(root, monkeypatch, fault):
    from repro.models import model as MDL

    monkeypatch.setattr(MDL, "decode_step", fault(MDL.decode_step))
    out = _run(root, "phi3-smoke-poisson")
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > SMOKE_LIMIT


def test_new_cell_from_new_files_only(tmp_path):
    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    cb = root / "chipbench"
    add_cell(root, "tiny-burst", "phi3-tiny", dict(SMOKE_PHI3, n_slots=2),
             "tiny-burst", {"driver": "backlog", "schedule_seed": 3,
                            "requests": 24,
                            "prompt_len": {"dist": "lognormal", "median": 12,
                                           "sigma": 0.5, "min": 4, "max": 40},
                            "output_len": {"dist": "lognormal", "median": 40,
                                           "sigma": 0.3, "min": 20,
                                           "max": 60}})
    (cb / "metrics" / "steps_per_s.py").write_text(
        "def read(run):\n"
        "    return len(run.window.steps) / run.window.closed_s\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"]:
        if m["name"] == "output_tok_s":
            m["workloads"].append("tiny-burst")
    bench["per_layer"].append({
        "name": "steps_per_s.tiny", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "step programs",
        "moves": "output_tok_s", "workloads": ["tiny-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    plain = _run(root, "tiny-burst")
    traced = _run(root, "tiny-burst", trace=True)
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"output_tok_s", "setup_s"}
    assert set(traced["metrics"]) == {"steps_per_s.tiny"}
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} changed"
    # the harness's own files are the repo's, unedited
    cmp = filecmp.dircmp(REPO / "chipbench", cb,
                         ignore=["__pycache__", ".jax_cache"])
    assert not cmp.diff_files and not cmp.left_only


def test_sweep_offers_the_mix_in_another_order(root):
    from chipbench import sweep

    cell = Cell("phi3-smoke-poisson", root=root)
    setup = R.Setup(cell, SEED, SECONDS)
    rows = [sweep.sweep_rate(setup, cell, 40.0, SEED, SECONDS, k)
            for k in (None, 1)]
    assert [r["schedule_seed"] for r in rows] == [0, 1]
    assert [r["due"] for r in rows] == [60, 60]
    assert all(r["ttft_p95_ms"] > 0 for r in rows)
