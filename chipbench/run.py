"""Runs one cell of ``BENCHMARK.json`` on the chip and prints its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights from the seed, builds the program's
``ContinuousBatcher`` and serves a few requests to compile every shape
the window uses.  The window then drives ``submit`` and ``step`` on the
wall clock for ``--seconds`` under the cell's traffic.  Afterwards the
program's state is freed and the plain reference judges a sample of the
served requests.  With ``--trace 0`` the last line of standard output
holds the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the profiler and the line holds its per-layer metrics.

A machine without the chips the cell asks for exits non-zero before any
model work, and so does a directory without the program (``src/repro``).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import traffic as T  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402
from chipbench.spec import Cell  # noqa: E402
from chipbench.weights import make_weights  # noqa: E402
from chipbench.window import Window  # noqa: E402

#: configuration-file keys that must equal the program's own config
_SIZES = {"family": "family", "n_layers": "n_layers", "d_model": "d_model",
          "n_heads": "n_heads", "n_kv_heads": "n_kv_heads",
          "head_dim": "head_dim", "d_ff": "d_ff", "vocab": "vocab",
          "act": "act", "norm": "norm", "rope_theta": "rope_theta",
          "tie_embeddings": "tie_embeddings", "dtype": "dtype"}
#: the sample the reference judges: at least this many served tokens and
#: this many requests, the longest request always among them
SAMPLE_TOKENS, SAMPLE_MIN, SAMPLE_MAX = 256, 4, 16


class GcClock:
    """Python's garbage collections and the longest pause they made,
    from ``gc.callbacks``: a pause inside the window shows up as a long
    step, and this tells it apart from a stall of the host or device."""

    def __init__(self):
        self.count, self.longest_s, self._t0 = 0, 0.0, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.longest_s = max(self.longest_s, time.perf_counter() - self._t0)

    def close(self):
        gc.callbacks.remove(self._on_gc)


class CompileClock:
    """Compilations (traces, lowerings, backend compiles) and their
    seconds, from JAX's own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.count += 1


def accelerator_devices(chips: int) -> list:
    """The first ``chips`` accelerators, or exit: the benchmark never
    falls back to the CPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"chipbench needs {chips} TPU chip(s): JAX found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}, "
            f"{len(devices)} devices)")
    return devices[:chips]


def setup_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory of the checkout.  Every program is
    kept, however quick to compile, so a second run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def program_config(cell: Cell):
    """The program's config for the cell, checked against the file."""
    from repro.configs import get_config

    c = cell.config
    cfg = get_config(c["registry"], smoke=bool(c.get("smoke", False)))
    differ = {k: (c[k], getattr(cfg, attr)) for k, attr in _SIZES.items()
              if c[k] != getattr(cfg, attr)}
    if differ:
        raise ValueError(f"{cell.config_entry['file']} differs from the "
                         f"program's {c['registry']!r}: {differ}")
    return cfg


class Setup:
    """Weights, the batcher and the cell's requests, warmed up."""

    def __init__(self, cell: Cell, seed: int, seconds: float):
        from repro.models import model as MDL
        from repro.serve.batcher import ContinuousBatcher, Request

        c = cell.config
        self.cell, self.seed = cell, seed
        self.request_cls = Request
        self.cfg = program_config(cell)
        self.params = make_weights(MDL.param_shapes(self.cfg), seed)
        self.batcher = ContinuousBatcher(
            self.cfg, self.params, n_slots=c["n_slots"],
            cache_len=c["cache_len"], policy=c["policy"])
        self.offered = T.generate(cell.traffic, seed, seconds, c["vocab"],
                                  c["cache_len"])
        self.warm_up()

    def warm_up(self):
        """Serve one request per slot whose prompt spans two prefill
        launches, so the prefill and decode programs and the host-side
        operations after them are compiled before the window."""
        b = self.batcher
        rng = np.random.default_rng([self.seed, 1])
        n = min(2 * b.prefill_chunk + 2, b.cache_len - 4)
        for i in range(b.n_slots):
            b.submit(self.request_cls(
                rid=-1 - i, prompt=rng.integers(0, self.cfg.vocab, n).tolist(),
                max_new=2))
        step = 0
        while b.queued() or any(r is not None for r in b.slot_req):
            b.step(step)
            step += 1
        jax.block_until_ready(b.cache)


def sample(window, seed: int, cache_len: int) -> list:
    """Finished requests due in the window, drawn from the seed, the one
    that spans the most positions always among them: ``(tokens, first,
    served)`` as the reference takes them."""
    done = sorted((s for s in window.served.values()
                   if s.offered.counted and s.done), key=lambda s: s.req.rid)
    if not done:
        return []
    rng = np.random.default_rng([seed, 2])
    longest = max(done, key=lambda s: len(s.req.prompt) + len(s.token_s))
    rest = [s for s in done if s is not longest]
    picked = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    out, tokens = [], 0
    for s in picked:
        if len(out) >= SAMPLE_MAX or (tokens >= SAMPLE_TOKENS
                                      and len(out) >= SAMPLE_MIN):
            break
        served = s.generated()
        seq = list(s.req.prompt) + served[:-1]
        assert len(seq) < cache_len, (len(seq), cache_len)
        out.append((np.asarray(seq, np.int64), len(s.req.prompt) - 1,
                    np.asarray(served, np.int64)))
        tokens += len(served)
    return out


class RunData:
    """What a metric's reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def read_metrics(cell: Cell, names_units, run: RunData) -> dict:
    out = {}
    for name, unit in names_units:
        value = cell.reader_of(name).read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list, t_process: float = T_PROCESS) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    from chipbench import trace as TR

    clock = CompileClock()
    setup = Setup(cell, seed, seconds)
    b = setup.batcher
    print(f"cell {cell.name}: {cell.config['registry']} n_slots={b.n_slots} "
          f"cache_len={b.cache_len} prefill_chunk={b.prefill_chunk} "
          f"policy={b.policy} requests_offered={len(setup.offered)}",
          file=sys.stderr, flush=True)
    window = Window(b, setup.request_cls, annotate=trace)
    profile_dir = None
    if trace:
        from repro.obs import trace as obs

        calib = TR.Calibration(b)
        obs.clear()
        obs.enable()
        profile_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(profile_dir)
        calib.launch()
    compiles_before = clock.count
    gc_clock = GcClock()
    setup_s = time.perf_counter() - t_process
    with (jax.profiler.TraceAnnotation("chipbench/window") if trace
          else contextlib.nullcontext()):
        cell.driver().drive(window, setup.offered, seconds, cell.traffic)
    window_compiles = clock.count - compiles_before
    gc_clock.close()
    spans = None
    if trace:
        jax.profiler.stop_trace()
        spans = obs.snapshot()
        obs.disable()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    counted = [s for s in window.served.values() if s.offered.counted]
    late = [s.submitted_s - s.offered.due_s for s in counted]
    unfinished = sum(1 for s in counted if not s.done)
    stats = b.stats
    failed = stats.failed + stats.expired + stats.truncated
    if cell.traffic["driver"] == "open_loop":
        failed += unfinished
    longest = max(window.steps, key=lambda st: st.end_s - st.start_s)
    print(f"window: {len(window.steps)} steps (longest "
          f"{(longest.end_s - longest.start_s) * 1e3:.1f} ms at "
          f"{longest.start_s:.3f} s), {len(counted)} requests due, "
          f"{unfinished} unfinished at the close ({window.closed_s:.3f} s), "
          f"program failed={stats.failed} expired={stats.expired} "
          f"truncated={stats.truncated}; generator late max "
          f"{max(late) * 1e3:.3f} ms, p95 "
          f"{np.percentile(late, 95) * 1e3:.3f} ms; compilations in the "
          f"window {window_compiles}; garbage collections {gc_clock.count}, "
          f"longest {gc_clock.longest_s * 1e3:.1f} ms", file=sys.stderr,
          flush=True)
    for index, held, stack in window.stalls.stacks[:3]:
        print(f"step {index} still running after {held:.3f} s, in:\n{stack}",
              file=sys.stderr, flush=True)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    run = RunData(cell=cell, cfg=cell.config, seconds=seconds,
                  setup_s=setup_s, window=window, n_slots=b.n_slots,
                  prefill_chunk=b.prefill_chunk,
                  peaks=peaks_for(dev.device_kind), spans=spans, trace=None)
    result = {}
    if trace:
        t0 = time.perf_counter()
        run.trace = TR.reduce(TR.find_xplane(profile_dir), len(devices))
        print(f"trace reduced in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
        shutil.rmtree(profile_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        metrics = read_metrics(cell, [(m["name"], m["unit"])
                                      for m in cell.per_layer()], run)
        result["breakdown"] = run.trace.breakdown()
    else:
        metrics = read_metrics(cell, [(m["name"], m["unit"])
                                      for m in cell.end_to_end()], run)
    seqs = sample(window, seed, cell.config["cache_len"])
    # free the program's state before the reference runs
    del b, setup.batcher, window, run
    gc.collect()
    t0 = time.perf_counter()
    gaps = cell.reference().logit_gaps(cell.config, setup.params, seqs)
    print(f"reference over {len(seqs)} requests in "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    max_gap = max((float(np.max(g)) for g in gaps), default=float("inf"))
    limit = float(cell.limits["max_logit_gap"])
    checks = {
        "max_logit_gap": {"value": max_gap, "limit": limit},
        "served_tokens_checked": {"value": int(sum(len(g) for g in gaps)),
                                  "limit": SAMPLE_TOKENS},
        "window_compiles": {"value": window_compiles, "limit": 0},
    }
    correct = (max_gap <= limit and window_compiles == 0
               and checks["served_tokens_checked"]["value"] >= SAMPLE_TOKENS)
    for name, c in checks.items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    out = {"correct": bool(correct), "attempted": len(counted),
           "failed": int(failed), "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chipbench: no program under {ROOT / 'src'}")
    cell = Cell(args.workload)
    devices = accelerator_devices(cell.chips)
    setup_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
