"""AFE sync-policy ladder on TPU (DESIGN.md §2.2): HLO collective count /
bytes per policy — the Fig. 10 "#finish" analogue for the training step.

Runs in a subprocess with an 8-device host mesh so the device-count
override stays contained."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import report

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.distributed.sharding import mesh_context, named_shardings
    from repro.launch.mesh import make_test_mesh
    from repro.models import model as MDL
    from repro.roofline.hlo_analyzer import analyze_hlo
    from repro.train.optimizer import AdamWConfig, opt_state_shapes
    from repro.train.train_step import StepConfig, build_train_step

    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    shape = ShapeConfig("t", 64, 8, "train", microbatches=4)
    ocfg = AdamWConfig()
    mesh = make_test_mesh(data=4, model=2)
    pshapes = MDL.param_shapes(cfg)
    out = {}
    for policy in ("unopt", "lc", "afe", "afe_bucket"):
        with mesh_context(mesh):
            scfg = StepConfig(policy=policy, q_chunk=32, k_chunk=32,
                              ssm_chunk=16)
            step, dp = build_train_step(cfg, shape, scfg, ocfg)
            pshard = named_shardings(pshapes, cfg, dp_shard=dp)
            oshard = {
                "m": named_shardings(pshapes, cfg, dp_shard=dp),
                "v": named_shardings(pshapes, cfg, dp_shard=dp),
                "step": NamedSharding(mesh, P()),
                "master": named_shardings(pshapes, cfg, dp_shard=dp),
            }
            oshapes = opt_state_shapes(pshapes, ocfg)
            oshapes = {k: oshapes[k] for k in oshard}
            bspec = {
                "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
            }
            bshard = {k: NamedSharding(mesh, P("data", None))
                      for k in bspec}
            compiled = jax.jit(
                step, in_shardings=(pshard, oshard, bshard),
            ).lower(pshapes, oshapes, bspec).compile()
            cost = analyze_hlo(compiled.as_text())
            out[policy] = {
                "coll_count": {k: v for k, v in cost.coll_count.items()},
                "coll_bytes": {k: v for k, v in cost.coll_bytes.items()},
                "total_count": cost.total_coll_count,
                "total_bytes": cost.total_coll_bytes,
            }
    print("RESULT " + json.dumps(out))
""")


def run():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1800)
    result = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            result = json.loads(line[len("RESULT "):])
    if result is None:
        print("bench_sync_policy FAILED:\n", proc.stdout[-2000:],
              proc.stderr[-2000:])
        return {}
    rows = []
    for policy, r in result.items():
        rows.append([
            policy, int(r["total_count"]),
            f"{r['total_bytes'] / 2**20:.1f}",
            int(r["coll_count"].get("all-reduce", 0)),
            int(r["coll_count"].get("reduce-scatter", 0)),
            int(r["coll_count"].get("all-gather", 0)),
        ])
    report("Sync-policy ladder (granite smoke, 4x2 mesh, 4 microbatches):"
           " collectives per step",
           rows, ["policy", "#coll", "MB", "all-reduce", "reduce-scatter",
                  "all-gather"],
           "sync_policy", result)
    print("(the paper's dynamic-#finish table, as compiled collectives)\n")
    return result


if __name__ == "__main__":
    run()
