"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST set the host-device override before ANY other import (jax locks the
device count on first initialisation).
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, SHAPES, get_config, input_specs, shape_applicable
from ..distributed.sharding import (
    fsdp_axes, mesh_context, named_shardings, param_specs_tree,
)
from ..models import model as MDL
from ..roofline.analysis import (
    model_flops_estimate, roofline_fraction, roofline_from_artifacts,
    roofline_from_opcost,
)
from ..roofline.hlo_analyzer import analyze_hlo
from ..train.optimizer import AdamWConfig, opt_state_shapes
from ..train.train_step import (
    StepConfig, build_decode_step, build_prefill_step, build_train_step,
)
from .mesh import make_production_mesh


def _batch_shardings(specs: dict, mesh, cfg) -> dict:
    fa = fsdp_axes(mesh)
    out = {}
    for k, v in specs.items():
        if k == "cache_index":
            out[k] = NamedSharding(mesh, P())
        elif v.ndim == 2:
            B = v.shape[0]
            dp = fa if B % _axis_size(mesh, fa) == 0 else None
            out[k] = NamedSharding(mesh, P(dp, None))
        else:  # (B, T, D) stub embeddings
            B = v.shape[0]
            dp = fa if B % _axis_size(mesh, fa) == 0 else None
            out[k] = NamedSharding(mesh, P(dp, None, None))
    return out


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _cache_shardings(cache_tree: dict, mesh, cfg):
    """Sharding rules for decode caches (SP for long-context cells):

    * KV caches (L, B, KV, T, h): B → data axes when divisible, else the
      time axis T → data (context/sequence parallelism for B=1 long_500k);
      T additionally → model when still divisible (KV heads are usually
      too few to split 16-way).
    * SSM conv (L, B, cw-1, Di) / state (L, B, Di, N): Di → model
      (matches the in/out projection sharding); B → data when divisible.
    """
    fa = fsdp_axes(mesh)
    dsize = _axis_size(mesh, fa)
    msize = mesh.shape["model"]

    def leaf_spec(path, s):
        nd = s.ndim
        if nd == 5:  # (L, B, KV, T, h)
            _, B, KV, T, h = s.shape
            if B % dsize == 0:
                b_ax, t_ax = fa, ("model" if T % msize == 0 else None)
            elif T % (dsize * msize) == 0:
                b_ax, t_ax = None, (fa + ("model",))
            elif T % dsize == 0:
                b_ax, t_ax = None, fa
            else:
                b_ax, t_ax = None, None
            return NamedSharding(mesh, P(None, b_ax, None, t_ax, None))
        if nd == 4:  # ssm: (L, B, cw-1, Di) or (L, B, Di, N)
            if "conv" in path:
                _, B, _, Di = s.shape
                b_ax = fa if B % dsize == 0 else None
                d_ax = "model" if Di % msize == 0 else None
                return NamedSharding(mesh, P(None, b_ax, None, d_ax))
            _, B, Di, N = s.shape
            b_ax = fa if B % dsize == 0 else None
            d_ax = "model" if Di % msize == 0 else None
            return NamedSharding(mesh, P(None, b_ax, d_ax, None))
        if nd == 6:  # vlm nested self stack (g, k-1, B, KV, T, h)
            _, _, B, KV, T, h = s.shape
            b_ax = fa if B % dsize == 0 else None
            t_ax = "model" if T % msize == 0 else None
            return NamedSharding(mesh, P(None, None, b_ax, None, t_ax, None))
        return NamedSharding(mesh, P(*([None] * nd)))

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        return leaf_spec(path, node)

    return walk(cache_tree, "")


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             policy: str = "afe", schedule: str = "masked",
             mesh=None, verbose: bool = True, hlo_dump=None) -> dict:
    """Lower + compile one cell; return the roofline/memory record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    rec = dict(arch=arch, shape=shape_name,
               mesh="2x16x16" if multi_pod else "16x16",
               policy=policy, schedule=schedule, status="skipped",
               reason=reason)
    if not ok:
        return rec
    t0 = time.perf_counter()  # monotonic: lower/compile are timed deltas
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    dp_shard = policy in ("afe", "afe_bucket")
    scfg = StepConfig(policy=policy, schedule=schedule)
    ocfg = AdamWConfig()
    with mesh_context(mesh):
        pshapes = MDL.param_shapes(cfg)
        pshard = named_shardings(pshapes, cfg, dp_shard=dp_shard)
        bspecs = input_specs(cfg, shape)
        bshard = _batch_shardings(bspecs, mesh, cfg)

        if shape.kind == "train":
            oshapes = opt_state_shapes(pshapes, ocfg)
            oshard = {
                "m": named_shardings(pshapes, cfg, dp_shard=dp_shard),
                "v": named_shardings(pshapes, cfg, dp_shard=dp_shard),
                "step": NamedSharding(mesh, P()),
                "master": named_shardings(pshapes, cfg, dp_shard=dp_shard),
            }
            oshapes = {k: oshapes[k] for k in oshard}
            step, _ = build_train_step(cfg, shape, scfg, ocfg)
            fn = jax.jit(
                step,
                in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1),
            )
            args = (pshapes, oshapes, bspecs)
        elif shape.kind == "prefill":
            prefill = build_prefill_step(cfg, scfg)
            fn = jax.jit(prefill, in_shardings=(pshard, bshard))
            args = (pshapes, bspecs)
        else:  # decode
            cshapes = MDL.cache_shapes(cfg, shape.global_batch, shape.seq_len,
                                       MDL.lane_width(mesh.devices.flat[0]))
            cshard = _cache_shardings(cshapes, mesh, cfg)
            serve = build_decode_step(cfg)
            fn = jax.jit(
                serve,
                in_shardings=(pshard, cshard, bshard),
                out_shardings=(None, cshard),
                donate_argnums=(1,),
            )
            args = (pshapes, cshapes, bspecs)

        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    model_flops = model_flops_estimate(cfg, shape)
    # Trip-count-scaled roofline (cost_analysis counts scan bodies once —
    # raw numbers kept under "cost" for reference).
    opcost = analyze_hlo(hlo)
    terms = roofline_from_opcost(opcost, chips=chips,
                                 model_flops=model_flops)
    if hlo_dump is not None:
        import zstandard

        Path(hlo_dump).write_bytes(
            zstandard.ZstdCompressor(level=6).compress(hlo.encode()))
    mem_rec = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes"):
        mem_rec[k] = getattr(mem, k, None)
    per_device_bytes = (mem_rec.get("argument_size_in_bytes") or 0) + \
        (mem_rec.get("temp_size_in_bytes") or 0)
    rec.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory=mem_rec,
        hbm_per_device_gb=round(per_device_bytes / 2 ** 30, 3),
        fits_hbm=bool(per_device_bytes < 16 * 2 ** 30),
        cost={k: cost.get(k) for k in ("flops", "bytes accessed")
              if k in cost},
        roofline=terms.as_dict(),
        roofline_fraction=round(roofline_fraction(terms), 4),
        n_params=cfg.n_params(),
        n_active_params=cfg.n_active_params(),
    )
    if verbose:
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "status",
                           "hbm_per_device_gb", "fits_hbm",
                           "roofline_fraction", "compile_s")}),
              flush=True)
        print(f"  dominant={terms.dominant} compute={terms.compute_s:.4f}s "
              f"memory={terms.memory_s:.4f}s "
              f"collective={terms.collective_s:.4f}s "
              f"coll_ops={terms.collective_ops}", flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--policy", default="afe")
    ap.add_argument("--schedule", default="masked")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mname = "2x16x16" if multi_pod else "16x16"
        for arch in archs:
            for shape in shapes:
                tag = f"{mname}_{arch}_{shape}_{args.policy}_{args.schedule}"
                path = outdir / f"{tag}.json"
                if path.exists():
                    print(f"skip (exists): {tag}", flush=True)
                    continue
                print(f"=== {tag}", flush=True)
                try:
                    rec = run_cell(arch, shape, multi_pod=multi_pod,
                                   policy=args.policy,
                                   schedule=args.schedule, mesh=mesh,
                                   hlo_dump=outdir / f"{tag}.hlo.zst")
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = dict(arch=arch, shape=shape, mesh=mname,
                               policy=args.policy, status="error",
                               error=f"{type(e).__name__}: {e}")
                    failures += 1
                path.write_text(json.dumps(rec, indent=1))
    print(f"done; failures={failures}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
