"""Moonlight's latent attention and held-expert layer against the plain
reference (``chipbench/reference/mla_moe.py``) at the registry's SMOKE
size on the CPU, on seeded weights, comparing logits and layer outputs
(never sampled tokens).

The program runs in float32 here (the SMOKE config with ``dtype``
float32): the reference computes in float32 too, so the two differ only
by the order of float32 sums, and a tolerance of 1e-4 (against values of
order 1) is a thousand times that rounding and a thousand times below a
bfloat16 rounding of the same numbers.  In bfloat16 a router near-tie
can choose another expert than the float32 reference; the chip's
comparison and the CPU rehearsal (``tests/chipbench``) judge that path.
"""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import model as MDL
from repro.models import moe as M
from repro.serve.batcher import step_programs

ROOT = Path(__file__).resolve().parents[1]
TOL = 1e-4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "mla_moe_reference", ROOT / "chipbench" / "reference" / "mla_moe.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_config("moonlight-16b-a3b", smoke=True),
                               dtype="float32")


@pytest.fixture(scope="module")
def ref_cfg(cfg):
    """The reference's view of ``cfg``: the configuration file's keys."""
    return {"num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rms_norm_eps": 1e-5,
            "rope_theta": cfg.rope_theta, "num_experts_per_tok": cfg.top_k,
            "routed_scaling_factor": cfg.routed_scale,
            "expert_first": cfg.expert_first, "vocab": cfg.vocab,
            "cache_len": 128}


def _params(cfg, seed=0):
    from chipbench.weights import make_weights

    params = make_weights(MDL.param_shapes(cfg), seed)
    # a correction bias that is not zero, so that routing reads it
    bias = jax.random.normal(jax.random.PRNGKey(seed + 1),
                             params["layers"]["moe"]["router"]["bias"].shape)
    params["layers"]["moe"]["router"]["bias"] = 0.1 * bias
    return params


def _ref_logits(ref, ref_cfg, params, toks):
    h = ref._hidden(ref_cfg, params, toks, "float32")
    return np.asarray(jnp.matmul(h, params["lm_head"].astype(jnp.float32),
                                 precision="highest"))


def test_prefill_then_decode_through_the_cache_matches_the_reference(
        cfg, ref_cfg):
    """Chunked prefill and decode, as ``step_programs`` runs them on the
    batcher's cache, give the reference's full-forward logits at every
    position they produce (two rows at different positions)."""
    params = _params(cfg)
    rng = np.random.default_rng(0)
    n, T, chunk, lengths = 2, 128, 32, (70, 45)
    toks = rng.integers(0, cfg.vocab, (n, 96)).astype(np.int32)
    decode_fn, prefill_fn = step_programs(cfg)
    cache = MDL.init_cache(cfg, n, T, 1)
    got = {}
    for start in range(0, max(lengths), chunk):
        count = np.clip(np.array(lengths) - start, 0, chunk).astype(np.int32)
        lanes = np.zeros((n, chunk), np.int32)
        for b in range(n):
            lanes[b, :count[b]] = toks[b, start:start + count[b]]
        logits, cache, _ = prefill_fn(params, cache, {
            "tokens": jnp.asarray(lanes),
            "cache_index": jnp.full((n,), start, jnp.int32),
            "count": jnp.asarray(count)})
        for b in range(n):
            if count[b]:
                got[b, start + count[b] - 1] = np.asarray(logits[b])
    pos = np.array(lengths)
    for _ in range(20):
        logits, cache, _ = decode_fn(params, cache, {
            "tokens": jnp.asarray(toks[np.arange(n), pos][:, None]),
            "cache_index": jnp.asarray(pos, jnp.int32)})
        for b in range(n):
            got[b, int(pos[b])] = np.asarray(logits[b])
        pos = pos + 1
    ref, padded = _reference(), np.pad(toks, ((0, 0), (0, T - 96)))
    want = [_ref_logits(ref, ref_cfg, params, padded[b:b + 1])[0]
            for b in range(n)]
    for (b, p), g in got.items():
        np.testing.assert_allclose(g[:cfg.vocab], want[b][p, :cfg.vocab],
                                   atol=TOL, rtol=0)


def test_absorbed_attention_matches_expanded_attention(cfg, ref_cfg):
    """The program's absorbed latent attention (keys folded into the
    query, values applied after the weighted latent sum), over the whole
    sequence and token by token against the latent cache, against the
    reference's expanded form (``k_nope`` and ``v`` built per position)."""
    ref = _reference()
    p = jax.tree.map(lambda a: a[0], _params(cfg)["layers"]["attn"])
    S = 40
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, cfg.d_model))
    dims = (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, 1e-5, cfg.rope_theta)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._attention(p, x, dims, "float32"))
        whole = np.asarray(L.mla_apply(p, cfg, x, q_chunk=16, k_chunk=16))
        latent = jnp.zeros((1, 1, 64, L.mla_entry_width(cfg)), jnp.float32)
        steps = []
        for t in range(S):
            out, latent = L.mla_decode_apply(p, cfg, x[:, t:t + 1], latent,
                                             jnp.array([t]), ())
            steps.append(np.asarray(out[0, 0]))
    np.testing.assert_allclose(whole, want, atol=TOL, rtol=0)
    np.testing.assert_allclose(np.stack(steps), want[0], atol=TOL, rtol=0)


def _layer(cfg, first, held, full):
    """The expert layer that holds experts ``first .. first + held - 1``
    of ``full``'s (weights sliced from it)."""
    c = dataclasses.replace(cfg, experts_held=held, expert_first=first)
    p = dict(full, **{k: full[k][first:first + held]
                      for k in ("w1", "w3", "w2")})
    return c, p


def test_four_expert_shares_sum_to_the_uncut_layer(cfg):
    """Each of four chips holds a quarter of the experts: their parts,
    with the shared experts (which every chip computes) counted once,
    add up to what the reference gives for the whole layer."""
    E = cfg.n_experts
    full = M.held_moe_init(
        jax.random.PRNGKey(4),
        dataclasses.replace(cfg, experts_held=E, expert_first=0), jnp.float32)
    full["router"]["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5),
                                                     (E,))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        shared = L.mlp_apply(full["shared"], x, "swiglu")
        want = _reference()._experts(full, x, (cfg.top_k, cfg.routed_scale,
                                               0), "float32")
        parts = []
        for first in range(0, E, E // 4):
            c, p = _layer(cfg, first, E // 4, full)
            y, _ = M.held_moe_apply(p, c, x)
            parts.append(y - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(want), atol=TOL, rtol=0)


def test_every_token_on_one_expert_drops_none(cfg):
    """A bias that sends every token to expert 0 (held): all of them are
    computed, as the plain per-token sum of their experts gives, with no
    capacity to overflow."""
    c, p = _layer(cfg, 0, cfg.experts_held,
                  M.held_moe_init(jax.random.PRNGKey(7), cfg, jnp.float32))
    p["router"]["bias"] = p["router"]["bias"].at[0].set(100.0)
    T = 64
    x = jax.random.normal(jax.random.PRNGKey(8), (T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, stats = M.held_moe_apply(p, c, x)
        gates, ids = M.route_sigmoid(x, p["router"], c.top_k, c.routed_scale)
        want = L.mlp_apply(p["shared"], x, "swiglu")
        for e in range(c.experts_held):
            ffn = L.mlp_apply({k: p[k][e] for k in ("w1", "w3", "w2")}, x,
                              "swiglu")
            w = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
            want = want + w[:, None] * ffn
    assert bool(jnp.all(jnp.any(ids == 0, axis=-1)))
    assert int(stats["expert_max"]) == T
    assert int(stats["routed_here"]) == int(jnp.sum(ids < c.experts_held))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=TOL,
                               rtol=0)


def test_sigmoid_router_matches_a_hand_written_top_k(cfg):
    """Sigmoid scores, the top k chosen by score plus bias, the chosen
    scores (without the bias) normalised to sum 1 and scaled."""
    rng = np.random.default_rng(9)
    d, E, K = cfg.d_model, cfg.n_experts, cfg.top_k
    x = rng.standard_normal((32, d)).astype(np.float32)
    w = (rng.standard_normal((d, E)) * d ** -0.5).astype(np.float32)
    bias = (0.3 * rng.standard_normal(E)).astype(np.float32)
    gates, ids = M.route_sigmoid(jnp.asarray(x),
                                 {"w": jnp.asarray(w), "bias": jnp.asarray(bias)},
                                 K, cfg.routed_scale)
    scores = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w)))
    for t in range(len(x)):
        top = np.argsort(-(scores[t] + bias))[:K]
        assert sorted(np.asarray(ids[t]).tolist()) == sorted(top.tolist())
        want = scores[t, top] / scores[t, top].sum() * cfg.routed_scale
        got = dict(zip(np.asarray(ids[t]).tolist(), np.asarray(gates[t])))
        np.testing.assert_allclose([got[e] for e in top], want, rtol=1e-5)
