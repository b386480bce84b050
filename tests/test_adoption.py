"""Adoption of repro.sched on the train-step, checkpoint, and MoE
surfaces: async checkpoint overlap + single-join semantics, chunk-plan
gradient bucketing vs the fixed-bucket oracle, expert-capacity admission,
and the kernel/einsum dispatch equivalence."""

import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.sched import DLBC, ExpertCapacityProvider, FixedCapacity
from repro.train.train_step import StepConfig, _bucketize, build_train_step


@pytest.fixture()
def tmpdir():
    d = tempfile.mkdtemp()
    yield d
    shutil.rmtree(d, ignore_errors=True)


def _tree():
    return {f"layer_{i}": {"w": jnp.full((32, 32), float(i)),
                           "b": jnp.zeros((32,))}
            for i in range(12)}


# ---------------------------------------------------------------------------
# Checkpoint surface: DCAFE shard writes, one join per save
# ---------------------------------------------------------------------------


def test_ckpt_async_save_overlaps_and_joins_once(tmpdir):
    """``save(blocking=False)`` returns with the publish still pending
    (the escaped finish), the trainer overlaps its next step, and
    ``wait()`` performs exactly ONE join before the atomic publish."""
    mgr = CheckpointManager(tmpdir, sched_policy="dcafe")
    try:
        mgr.save(3, _tree(), blocking=False)
        # not yet published: the join (and the COMMIT) belong to wait()
        assert mgr.telemetry.joins == 0
        assert mgr.latest_step() is None
        # ... a concurrently running "train step" on the main thread ...
        x = jnp.ones((64, 64))
        jax.block_until_ready(x @ x)
        mgr.wait()
        assert mgr.telemetry.joins == 1      # the single escaped finish
        assert mgr.telemetry.spawns >= 1     # shard writes were spawned
        assert mgr.latest_step() == 3
        step, out = mgr.restore()
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(out["layer_5"]["w"]), np.full((32, 32), 5.0))
        # wait() is idempotent: no second join for the same save
        mgr.wait()
        assert mgr.telemetry.joins == 1
    finally:
        mgr.close()


def test_ckpt_stealing_executor_same_contract(tmpdir):
    """The adaptive work-stealing substrate is a drop-in for the shard
    writes: one escaped join per save, atomic publish, identical restore
    — with the grain decided by the policy's controller (spawns stay
    O(ranges), bounded by the shard count)."""
    from repro.sched import WorkStealingExecutor

    mgr = CheckpointManager(tmpdir, sched_policy="dcafe", stealing=True)
    try:
        mgr.save(7, _tree(), blocking=True)
        assert isinstance(mgr.executor, WorkStealingExecutor)
        t = mgr.telemetry
        assert t.joins == 1
        assert 1 <= t.spawns <= 24  # ranges (+ any splits), not per-shard
        assert t.completions == t.spawns
        assert mgr.latest_step() == 7
        step, out = mgr.restore()
        assert step == 7
        np.testing.assert_array_equal(
            np.asarray(out["layer_5"]["w"]), np.full((32, 32), 5.0))
    finally:
        mgr.close()


def test_global_pool_stealing_opt_in(monkeypatch):
    """``global_pool(stealing=True)`` serves the work-stealing substrate
    through the same wrapper surface (first creation wins)."""
    import repro.data.pool as pool_mod

    monkeypatch.setattr(pool_mod, "_GLOBAL", None)
    pool = pool_mod.global_pool(n_workers=2, stealing=True)
    try:
        assert isinstance(pool, pool_mod.StealingPool)
        done = []
        import threading
        lock = threading.Lock()

        def fn(i):
            with lock:
                done.append(i)

        pool.run_loop(list(range(20)), fn)
        assert sorted(done) == list(range(20))
        assert pool.stats.completions == pool.stats.spawns
    finally:
        pool.shutdown()
        monkeypatch.setattr(pool_mod, "_GLOBAL", None)


def test_ckpt_restore_only_manager_spawns_no_pool(tmpdir):
    """The I/O pool is lazy: a manager used only for restore/inspection
    never starts worker threads (and close() is a no-op)."""
    mgr = CheckpointManager(tmpdir)
    assert mgr._ex is None
    assert mgr.latest_step() is None
    mgr.close()
    assert mgr._ex is None


def test_ckpt_failed_shard_write_never_commits(tmpdir, monkeypatch):
    """A shard write failing PERSISTENTLY on a worker must abort the
    publish: the bounded RetryPolicy exhausts its attempts, wait()
    raises and no COMMIT (hence no 'latest' checkpoint) appears.
    (Transient single-shot faults are retried and recover — see
    test_faults.py.)"""
    import repro.ckpt.checkpoint as CKPT

    real_save = np.save
    calls = {"n": 0}

    def flaky_save(fname, arr, *a, **k):
        calls["n"] += 1
        if calls["n"] >= 3:  # persistent from the 3rd write on
            raise OSError("disk full")
        return real_save(fname, arr, *a, **k)

    monkeypatch.setattr(CKPT.np, "save", flaky_save)
    mgr = CheckpointManager(tmpdir, sched_policy="dcafe")
    try:
        mgr.save(1, _tree(), blocking=False)
        with pytest.raises(RuntimeError, match="shard"):
            mgr.wait()
        assert mgr.latest_step() is None  # torn save stayed un-COMMITted
    finally:
        mgr.close()  # must not re-raise the consumed publish failure


def test_ckpt_lc_policy_joins_per_save(tmpdir):
    """The LC baseline joins inside every save — the contrast the
    adoption benchmark's DCAFE<=LC gate rests on."""
    mgr = CheckpointManager(tmpdir, sched_policy="lc")
    try:
        for s in (1, 2):
            mgr.save(s, _tree(), blocking=True)
        assert mgr.telemetry.joins == 2
        assert mgr.all_steps() == [1, 2]
    finally:
        mgr.close()


# ---------------------------------------------------------------------------
# Train-step surface: chunk-plan gradient bucketing
# ---------------------------------------------------------------------------


def _grads():
    rng = np.random.default_rng(0)
    return {
        "emb": jnp.asarray(rng.normal(size=(128, 16)), jnp.float32),
        "l0": {"w": jnp.asarray(rng.normal(size=(64, 8)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(64,)), jnp.float32)},
        "l1": {"w": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32)},
        "head": jnp.asarray(rng.normal(size=(16, 128)), jnp.float32),
    }


def test_bucketize_all_busy_matches_fixed_bucket_oracle():
    """With zero idle reduction streams DLBC takes the serial arm, which
    must partition leaves identically to the fixed-bucket LPT oracle."""
    grads = _grads()
    leaves = jax.tree.leaves(grads)
    flat_o, unflat_o = _bucketize(grads, 4)
    flat_s, unflat_s = _bucketize(grads, 4, policy=DLBC(),
                                  capacity=FixedCapacity(0, 4))
    b_o, b_s = flat_o(leaves), flat_s(leaves)
    assert len(b_o) == len(b_s)
    for a, b in zip(b_o, b_s):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucketize_plan_driven_caller_keeps_smallest():
    """With idle streams the bucket count comes from the chunk plan and
    the caller's (last) bucket holds the smallest leaves."""
    grads = _grads()
    leaves = jax.tree.leaves(grads)
    n = 3
    flat, unflat = _bucketize(grads, n, policy="dlbc")
    buckets = flat(leaves)
    assert len(buckets) == n  # chunk_plan over 6 leaves, 3 streams
    # idle-worker-aware: fewer idle reduction streams → fewer buckets
    flat2, _ = _bucketize(grads, 4, policy=DLBC(),
                          capacity=FixedCapacity(1, 4))
    assert len(flat2(leaves)) == 2  # 1 idle stream + the caller
    # every element exactly once
    assert sum(b.size for b in buckets) == sum(l.size for l in leaves)
    # caller bucket is the plan's smallest chunk of the size-ordered
    # leaf list → it cannot hold more payload than any spawned bucket
    assert buckets[-1].size == min(b.size for b in buckets)
    # round trip
    out = unflat(buckets)
    for k_path, a in zip(jax.tree.leaves(grads), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(k_path), np.asarray(a))


def test_build_train_step_sched_counts_ladder():
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.train.optimizer import AdamWConfig

    cfg = get_config("phi3-mini-3.8b", smoke=True)
    shape = ShapeConfig("t", 32, 8, "train", microbatches=4)
    counts = {}
    for pol in ("serial", "lc", "dlbc", "dcafe"):
        scfg = StepConfig(policy="afe_bucket", sched_policy=pol,
                          q_chunk=32, k_chunk=32, ssm_chunk=16)
        step, _ = build_train_step(cfg, shape, scfg, AdamWConfig())
        counts[pol] = step.sched_counts
    assert counts["serial"]["spawns"] == 0
    assert counts["serial"]["mb_unroll"] == 1
    assert counts["lc"]["spawns"] > 0
    assert counts["dlbc"]["spawns"] > 0
    # DCAFE chunks exactly like DLBC but escapes the per-step join
    assert counts["dcafe"]["spawns"] == counts["dlbc"]["spawns"]
    assert counts["dlbc"]["joins"] == 1 and counts["dcafe"]["joins"] == 0
    assert counts["dcafe"]["escape_join"]


# ---------------------------------------------------------------------------
# MoE surface: expert-capacity admission + kernel dispatch path
# ---------------------------------------------------------------------------


def test_expert_capacity_provider_arithmetic():
    cap = ExpertCapacityProvider(n_experts=4, slots_per_expert=8)
    assert cap.total() == 32
    assert cap.idle() == 32
    pos = jnp.asarray([[0, 7], [8, 3]])
    np.testing.assert_array_equal(
        np.asarray(cap.admit_mask(pos)),
        np.asarray([[True, True], [False, True]]))
    load = jnp.asarray([0, 8, 12, 5])
    np.testing.assert_array_equal(
        np.asarray(cap.residual(load)), np.asarray([8, 0, 0, 3]))


@pytest.mark.parametrize("dispatch", ["lc", "dlbc"])
def test_moe_apply_stats_sched_vocabulary(dispatch):
    import dataclasses

    from repro.configs import get_config
    from repro.models import moe as MOE

    cfg = dataclasses.replace(get_config("mixtral-8x7b", smoke=True),
                              moe_dispatch=dispatch)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    y, stats = MOE.moe_apply(p, cfg, x, return_stats=True)
    assert y.shape == x.shape
    # spawns (admitted pairs) + drops account for every (token, choice)
    total_pairs = 64 * cfg.top_k
    spawns = int(stats["spawns"])
    dropped = float(stats["dropped_frac"]) * total_pairs
    assert spawns + round(dropped) == total_pairs
    assert int(stats["joins"]) == 1
    assert stats["rounds"] == (1 if dispatch == "lc" else 2)


def test_moe_kernel_dispatch_matches_einsum_path(monkeypatch):
    """The Pallas grouped-matmul dispatch path (use_kernel=True, run in
    interpret mode here) agrees with the XLA einsum path."""
    import dataclasses
    import functools

    from repro.configs import get_config
    from repro.kernels.moe_dispatch import ops as MOE_OPS
    from repro.models import moe as MOE

    monkeypatch.setattr(MOE_OPS, "moe_gmm_op",
                        functools.partial(MOE_OPS.moe_gmm_op, interpret=True))

    cfg = get_config("mixtral-8x7b", smoke=True)
    assert cfg.act == "swiglu"
    cfg = dataclasses.replace(cfg, moe_dispatch="dlbc")
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (256, cfg.d_model)) * 0.5
    y_xla = MOE.moe_apply(p, cfg, x)
    y_krn = MOE.moe_apply(p, cfg, x, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_krn),
                               atol=2e-4, rtol=2e-4)
