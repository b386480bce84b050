"""Per-architecture smoke tests: reduced configs, one forward/train step
and one decode step on CPU; output shapes + finiteness asserted.
(Full configs are exercised only by the dry-run — ShapeDtypeStruct only.)"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeConfig
from repro.models import model as MDL
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import StepConfig, build_decode_step, \
    build_train_step

B, S = 2, 64


def _batch(cfg):
    b = {"tokens": jnp.ones((B, S), jnp.int32),
         "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.family == "encdec":
        b["enc_frames"] = jnp.zeros((B, cfg.enc_seq, cfg.d_model),
                                    jnp.bfloat16)
    if cfg.family == "vlm":
        b["vis_embed"] = jnp.zeros((B, cfg.vis_seq, cfg.d_model),
                                   jnp.bfloat16)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_train_step(arch):
    cfg = get_config(arch, smoke=True)
    params = MDL.init_params(cfg, jax.random.PRNGKey(0))
    scfg = StepConfig(q_chunk=32, k_chunk=32, ssm_chunk=16)
    step, _ = build_train_step(cfg, ShapeConfig("t", S, B, "train", 2),
                               scfg, AdamWConfig())
    opt = init_opt_state(params, AdamWConfig())
    p2, o2, m = jax.jit(step)(params, opt, _batch(cfg))
    assert bool(jnp.isfinite(m["grad_norm"]))
    assert int(o2["step"]) == 1
    # params actually changed
    l0 = jax.tree.leaves(params)[0]
    l1 = jax.tree.leaves(p2)[0]
    assert l0.shape == l1.shape


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_forward_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    params = MDL.init_params(cfg, jax.random.PRNGKey(0))
    logits = MDL.forward(params, cfg, _batch(cfg), q_chunk=32, k_chunk=32,
                         ssm_chunk=16, remat=False)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_decode_step(arch):
    cfg = get_config(arch, smoke=True)
    params = MDL.init_params(cfg, jax.random.PRNGKey(0))
    cache = MDL.init_cache(cfg, B, 64, 1)
    serve = build_decode_step(cfg)
    batch = {"tokens": jnp.ones((B, 1), jnp.int32),
             "cache_index": jnp.asarray(3, jnp.int32)}
    logits, cache2 = jax.jit(serve)(params, cache, batch)
    assert logits.shape == (B, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))
    # cache must actually advance
    flat0 = jax.tree.leaves(cache)
    flat1 = jax.tree.leaves(cache2)
    assert any(
        not jnp.array_equal(a, b) for a, b in zip(flat0, flat1))


def test_decode_matches_forward_dense():
    """Token-by-token decode reproduces the forward pass logits."""
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    params = MDL.init_params(cfg, jax.random.PRNGKey(0))
    T = 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0, cfg.vocab)
    full = MDL.forward(params, cfg, {"tokens": tokens}, q_chunk=8,
                       k_chunk=8, remat=False).astype(jnp.float32)
    cache = MDL.init_cache(cfg, 1, 16, 1)
    outs = []
    for t in range(T):
        logits, cache = MDL.decode_step(
            params, cfg, cache,
            {"tokens": tokens[:, t:t + 1],
             "cache_index": jnp.asarray(t, jnp.int32)})
        outs.append(logits.astype(jnp.float32))
    import numpy as np

    dec = jnp.stack(outs, axis=1)  # (1, T, V)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               atol=0.15, rtol=0.15)
    # argmax agreement is the functional bar (bf16 params)
    assert bool(jnp.all(jnp.argmax(dec, -1) == jnp.argmax(full, -1)))


def test_decode_matches_forward_ssm():
    cfg = get_config("falcon-mamba-7b", smoke=True)
    params = MDL.init_params(cfg, jax.random.PRNGKey(0))
    T = 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0, cfg.vocab)
    full = MDL.forward(params, cfg, {"tokens": tokens}, ssm_chunk=4,
                       remat=False).astype(jnp.float32)
    cache = MDL.init_cache(cfg, 1, 16, 1)
    outs = []
    for t in range(T):
        logits, cache = MDL.decode_step(
            params, cfg, cache,
            {"tokens": tokens[:, t:t + 1],
             "cache_index": jnp.asarray(t, jnp.int32)})
        outs.append(logits.astype(jnp.float32))
    dec = jnp.stack(outs, axis=1)
    assert bool(jnp.all(jnp.argmax(dec, -1) == jnp.argmax(full, -1)))


def test_moe_dispatch_dlbc_drops_fewer():
    import dataclasses

    from repro.models import moe as MOE

    cfg = get_config("mixtral-8x7b", smoke=True)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    base = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.d_model))
    x = jnp.repeat(base, 64, axis=0) + 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (512, cfg.d_model))
    drops = {}
    for dispatch in ("lc", "dlbc"):
        c = dataclasses.replace(cfg, moe_dispatch=dispatch,
                                moe_capacity_factor=1.0)
        _, stats = MOE.moe_apply(p, c, x, return_stats=True)
        drops[dispatch] = float(stats["dropped_frac"])
    assert drops["dlbc"] < drops["lc"]


def test_moe_matches_ref_when_capacity_ample():
    """With enough capacity both dispatchers equal the dense oracle."""
    import dataclasses

    import numpy as np

    from repro.models import moe as MOE

    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m", smoke=True),
                              moe_capacity_factor=8.0)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    ref = MOE.moe_ref(p, cfg, x)
    for dispatch in ("lc", "dlbc"):
        c = dataclasses.replace(cfg, moe_dispatch=dispatch)
        y, stats = MOE.moe_apply(p, c, x, return_stats=True)
        assert float(stats["dropped_frac"]) == 0.0
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=2e-4, rtol=2e-3)
