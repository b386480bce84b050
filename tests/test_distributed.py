"""Distribution tests on a small in-process host mesh (subprocess so the
device-count override never leaks into other tests).

Verifies:
* the train step lowers+compiles for every sync policy on a (2,2) mesh
  and the HLO collective mix matches the policy ladder
  (unopt ≥ lc all-reduces; afe introduces reduce-scatter/all-gather);
* sharded and single-device execution agree numerically;
* a tiny multi-pod (2,2,2) mesh compiles (the "pod" axis shards).
"""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.distributed.sharding import mesh_context, named_shardings
    from repro.launch.mesh import make_test_mesh
    from repro.models import model as MDL
    from repro.roofline.analysis import collective_stats
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.train_step import StepConfig, build_train_step

    cfg = get_config("phi3-mini-3.8b", smoke=True)
    shape = ShapeConfig("t", 32, 8, "train", microbatches=2)
    ocfg = AdamWConfig()

    def batch():
        k = jax.random.PRNGKey(0)
        t = jax.random.randint(k, (8, 32), 0, cfg.vocab)
        return {"tokens": t, "labels": jnp.roll(t, -1, axis=1)}

    # --- single-device reference ------------------------------------------
    params = MDL.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params, ocfg)
    scfg = StepConfig(policy="afe", q_chunk=32, k_chunk=32, ssm_chunk=16)
    step, _ = build_train_step(cfg, shape, scfg, ocfg)
    p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch())
    ref_gnorm = float(m_ref["grad_norm"])

    results = {}
    mesh = make_test_mesh(data=2, model=2)
    for policy in ("unopt", "lc", "afe", "afe_bucket"):
        with mesh_context(mesh):
            scfg = StepConfig(policy=policy, q_chunk=32, k_chunk=32,
                              ssm_chunk=16)
            step, dp_shard = build_train_step(cfg, shape, scfg, ocfg)
            pshapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            pshard = named_shardings(pshapes, cfg, dp_shard=dp_shard)
            oshard = {
                "m": named_shardings(pshapes, cfg, dp_shard=dp_shard),
                "v": named_shardings(pshapes, cfg, dp_shard=dp_shard),
                "step": NamedSharding(mesh, P()),
                "master": named_shardings(pshapes, cfg, dp_shard=dp_shard),
            }
            bshard = {k: NamedSharding(mesh, P("data", None))
                      for k in ("tokens", "labels")}
            jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard))
            lowered = jitted.lower(params, opt, batch())
            compiled = lowered.compile()
            stats = collective_stats(compiled.as_text())
            p2, o2, m2 = jitted(params, opt, batch())
            results[policy] = {
                "gnorm": float(m2["grad_norm"]),
                "colls": {k: v["count"] for k, v in stats.items()},
            }
    # --- multi-pod tiny mesh compiles ---------------------------------------
    mesh3 = make_test_mesh(data=2, model=2, pod=2)
    with mesh_context(mesh3):
        scfg = StepConfig(policy="afe", q_chunk=32, k_chunk=32, ssm_chunk=16)
        step, dp_shard = build_train_step(cfg, shape, scfg, ocfg)
        pshapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        pshard = named_shardings(pshapes, cfg, dp_shard=True)
        jax.jit(step, in_shardings=(pshard, None, None)).lower(
            params, opt, batch()).compile()
    results["ref_gnorm"] = ref_gnorm
    print("RESULT " + json.dumps(results))
""")


@pytest.fixture(scope="module")
def dist_results():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200,
                         cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    import json

    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError("no RESULT line:\n" + out.stdout)


def test_policies_numerically_agree(dist_results):
    r = dist_results
    for policy in ("unopt", "lc", "afe", "afe_bucket"):
        assert r[policy]["gnorm"] == pytest.approx(r["ref_gnorm"], rel=2e-2), \
            policy


def test_policy_ladder_collective_mix(dist_results):
    r = dist_results
    ar = lambda p: r[p]["colls"]["all-reduce"]
    rs = lambda p: r[p]["colls"]["reduce-scatter"]
    ag = lambda p: r[p]["colls"]["all-gather"]
    # unopt syncs per microbatch → at least as many all-reduces as lc
    assert ar("unopt") >= ar("lc")
    # afe shards params: all-gathers appear (and usually reduce-scatters)
    assert ag("afe") + rs("afe") > 0
    # NOTE (refuted hypothesis, EXPERIMENTS.md §Perf): afe_bucket was
    # expected to cut the static collective count via fused flat buckets;
    # on GSPMD the concat/slice resharding around the buckets EMITS MORE
    # collectives than it fuses.  We assert only that it compiles and
    # stays numerically correct; the count is reported, not gated.
    assert sum(r["afe_bucket"]["colls"].values()) > 0
