"""Benchmark entrypoint: `PYTHONPATH=src python -m benchmarks.run [names]`.

One benchmark per paper table/figure plus the TPU-side analogues:

  fig10      — dynamic #finish/#async per kernel × scheme   (paper Fig. 10)
  fig11      — DCAFE vs LC speedup across worker counts     (paper Fig. 11)
  fig12      — full scheme ladder normalised to UnOpt       (paper Fig. 12)
  fig13      — simulated energy                             (paper Fig. 13)
  sync       — HLO collectives per AFE sync policy          (Fig. 10 on TPU)
  moe        — DLBC vs LC MoE dispatch drop rates           (§3.2 on TPU)
  ep         — expert-parallel all-to-all dispatch vs data-parallel:
               exchange telemetry + the one-join-per-round AFE gate
  batcher    — DLBC continuous batching vs LC fixed batches (§3.2 serving)
  tenants    — multi-tenant serving: weighted-DLBC isolation under bursts
  sched      — repro.sched policy ladder on the host pool (uniform/skewed)
  grain      — adaptive-grain work stealing: steal-driven splitting vs
               fixed grains (uniform overhead collapse + skew rebalance)
  faults     — chaos lane: seeded fault injection (raises, fail-fast
               cancellation, worker death) with exact exception/item
               conservation gates and a p99-under-faults CI bound
  slo        — SLO burn-rate lane: adversary bursts burn a tenant's
               error budget and fire a flight-recorder incident; DLBC
               chunking keeps the budget intact at the same load
  adoption   — sched adoption surfaces: train-step / checkpoint / MoE
               spawn-join telemetry + the DCAFE≤LC join regression gate
  design     — paper §6 DLBC design-choice study
  roofline   — per-cell roofline table from dry-run artifacts (§Roofline)

``--seed N`` / ``--repeats N`` thread a deterministic seed and repeat
count into every bench that takes them (signature-inspected), and are
recorded in each saved artifact's envelope so trajectory diffs compare
like with like.
"""

import argparse
import inspect
import time

from . import (
    bench_adoption, bench_batcher, bench_design_choices, bench_ep,
    bench_faults, bench_fig10_counts, bench_fig11_speedup,
    bench_fig12_schemes, bench_fig13_energy, bench_grain,
    bench_moe_dispatch, bench_roofline, bench_sched, bench_slo,
    bench_sync_policy, bench_tenants,
)
from .common import set_run_context

ALL = {
    "ep": bench_ep.run,
    "sync": bench_sync_policy.run,
    "adoption": bench_adoption.run,
    "faults": bench_faults.run,
    "grain": bench_grain.run,
    "slo": bench_slo.run,
    "fig10": bench_fig10_counts.run,
    "fig11": bench_fig11_speedup.run,
    "fig12": bench_fig12_schemes.run,
    "fig13": bench_fig13_energy.run,
    "design": bench_design_choices.run,
    "moe": bench_moe_dispatch.run,
    "batcher": bench_batcher.run,
    "tenants": bench_tenants.run,
    "sched": bench_sched.run,
    "roofline": bench_roofline.run,
}

#: benches that run in a Python child process (it sets a virtual-device
#: XLA_FLAGS).  On a machine with a chip, the child cannot take the chip
#: once this process has touched JAX, so these run only before any
#: in-process bench.
CHILD_PROCESS = ("ep", "sync")


def _call(fn, seed, repeats):
    """Pass seed/repeats through to benches that accept them — several
    used to hardcode their own repeat counts and seed nothing."""
    params = inspect.signature(fn).parameters
    kwargs = {}
    if seed is not None and "seed" in params:
        kwargs["seed"] = seed
    if repeats is not None and "repeats" in params:
        kwargs["repeats"] = repeats
    return fn(**kwargs)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="run registered benchmarks",
        epilog="names: " + " ".join(ALL))
    ap.add_argument("names", nargs="*", help="benchmarks to run (all)")
    ap.add_argument("--seed", type=int, default=None,
                    help="deterministic seed threaded into every bench")
    ap.add_argument("--repeats", type=int, default=None,
                    help="repeat count for distribution-gated benches")
    args = ap.parse_args(argv)
    names = args.names or list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        ap.error(f"unknown benchmarks: {unknown} (have: {' '.join(ALL)})")
    n_first = next((i for i, n in enumerate(names) if n not in CHILD_PROCESS),
                   len(names))
    late = [n for n in names[n_first:] if n in CHILD_PROCESS]
    if late:
        ap.error(f"{late} run in a child process, which cannot use the chip "
                 "after an earlier bench in this process has touched JAX: "
                 "list them first or run them alone")
    set_run_context(seed=args.seed, repeats=args.repeats)
    t0 = time.perf_counter()
    for name in names:
        print(f"\n{'=' * 72}\nBENCH {name}\n{'=' * 72}")
        t = time.perf_counter()
        _call(ALL[name], args.seed, args.repeats)
        print(f"[{name} done in {time.perf_counter() - t:.1f}s]")
    print(f"\nall benchmarks done in {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    main()
