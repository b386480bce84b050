"""Per-family transformer blocks (pre-norm residual), stacked with
``jax.lax.scan`` over a leading layer dimension + rematerialisation.

Families: dense / moe / ssm (mamba-only, no FFN) / hybrid (parallel
attn+mamba heads, Hymba-style) / encdec (whisper) / vlm (periodic
cross-attention, Llama-3.2-Vision-style) / mla_moe (latent attention;
leading ``mla_dense`` layers, then ``mla_moe`` layers whose expert
layer holds a share of the experts, DeepSeek-V3-style).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..distributed.sharding import act_spec, shard, shard_act
from . import layers as L
from . import moe as M
from . import ssm as S


# ---------------------------------------------------------------------------
# Single-layer shapes / init / apply per family
# ---------------------------------------------------------------------------

#: layer kinds with latent attention over a latent cache
MLA_KINDS = ("mla_dense", "mla_moe")
#: layer kinds whose cache is position-indexed, so a prompt span can be
#: written in one launch (``layer_prefill_apply``)
SPAN_PREFILL_KINDS = ("dense", "moe") + MLA_KINDS


def _mla_layer_shapes(cfg, dtype, kind: str) -> dict:
    d, nk = cfg.d_model, cfg.norm
    out = {"ln1": L.norm_shapes(d, nk, dtype),
           "attn": L.mla_shapes(cfg, dtype),
           "ln2": L.norm_shapes(d, nk, dtype)}
    if kind == "mla_moe":
        out["moe"] = M.held_moe_shapes(cfg, dtype)
    else:
        out["mlp"] = L.mlp_shapes(d, cfg.dense_d_ff, cfg.act, dtype)
    return out


def layer_shapes(cfg, dtype, kind: str) -> dict:
    d = cfg.d_model
    nk = cfg.norm
    if kind in MLA_KINDS:
        return _mla_layer_shapes(cfg, dtype, kind)
    out = {"ln1": L.norm_shapes(d, nk, dtype)}
    if kind in ("dense", "moe", "hybrid", "enc", "dec", "cross"):
        out["attn"] = L.attn_shapes(cfg, dtype)
    if kind == "hybrid":
        out["ssm"] = S.ssm_shapes(cfg, dtype)
    if kind == "ssm":
        out["ssm"] = S.ssm_shapes(cfg, dtype)
        return out  # mamba block has no FFN (falcon-mamba d_ff=0)
    if kind == "dec":
        out["lnx"] = L.norm_shapes(d, nk, dtype)
        out["cross"] = L.attn_shapes(cfg, dtype)
    if kind == "cross":
        # VLM cross layer: attention reads vision embeddings
        pass
    out["ln2"] = L.norm_shapes(d, nk, dtype)
    if kind == "moe":
        out["moe"] = M.moe_shapes(cfg, dtype)
    else:
        out["mlp"] = L.mlp_shapes(d, cfg.d_ff, cfg.act, dtype)
    return out


def layer_init(key, cfg, dtype, kind: str) -> dict:
    ks = iter(jax.random.split(key, 8))
    d, nk = cfg.d_model, cfg.norm
    if kind in MLA_KINDS:
        out = {"ln1": L.norm_init(None, d, nk, dtype),
               "attn": L.mla_init(next(ks), cfg, dtype),
               "ln2": L.norm_init(None, d, nk, dtype)}
        if kind == "mla_moe":
            out["moe"] = M.held_moe_init(next(ks), cfg, dtype)
        else:
            out["mlp"] = L.mlp_init(next(ks), d, cfg.dense_d_ff, cfg.act,
                                    dtype)
        return out
    out = {"ln1": L.norm_init(next(ks), d, nk, dtype)}
    if kind in ("dense", "moe", "hybrid", "enc", "dec", "cross"):
        out["attn"] = L.attn_init(next(ks), cfg, dtype)
    if kind in ("hybrid", "ssm"):
        out["ssm"] = S.ssm_init(next(ks), cfg, dtype)
        if kind == "ssm":
            return out
    if kind == "dec":
        out["lnx"] = L.norm_init(next(ks), d, nk, dtype)
        out["cross"] = L.attn_init(next(ks), cfg, dtype)
    out["ln2"] = L.norm_init(next(ks), d, nk, dtype)
    if kind == "moe":
        out["moe"] = M.moe_init(next(ks), cfg, dtype)
    else:
        out["mlp"] = L.mlp_init(next(ks), d, cfg.d_ff, cfg.act, dtype)
    return out


def layer_apply(p: dict, cfg, x: jnp.ndarray, kind: str, *,
                ctx: Optional[jnp.ndarray] = None,
                causal: bool = True,
                schedule: str = "masked",
                q_chunk: int = 1024, k_chunk: int = 1024,
                ssm_chunk: int = 256) -> jnp.ndarray:
    """One block forward (training/prefill path)."""
    if kind in MLA_KINDS:
        h = L.norm_apply(p["ln1"], x, cfg.norm)
        x = x + shard_act(L.mla_apply(p["attn"], cfg, h, schedule=schedule,
                                      q_chunk=q_chunk, k_chunk=k_chunk))
        f, _ = _ffn(p, cfg, L.norm_apply(p["ln2"], x, cfg.norm), kind)
        return x + shard_act(f)
    if kind == "ssm":
        h = L.norm_apply(p["ln1"], x, cfg.norm)
        return x + shard_act(S.ssm_apply(p["ssm"], cfg, h, chunk=ssm_chunk))
    h = L.norm_apply(p["ln1"], x, cfg.norm)
    if kind == "cross":
        a = L.attn_apply(p["attn"], cfg, h, kv_src=ctx, causal=False,
                         schedule=schedule, q_chunk=q_chunk, k_chunk=k_chunk)
    else:
        a = L.attn_apply(p["attn"], cfg, h, causal=causal, schedule=schedule,
                         q_chunk=q_chunk, k_chunk=k_chunk)
    if kind == "hybrid":
        # Hymba: attention and mamba heads in parallel on the same input,
        # outputs mean-fused.
        s_out = S.ssm_apply(p["ssm"], cfg, h, chunk=ssm_chunk)
        a = (a + s_out) * 0.5
    x = x + shard_act(a)
    if kind == "dec":
        h = L.norm_apply(p["lnx"], x, cfg.norm)
        x = x + shard_act(
            L.attn_apply(p["cross"], cfg, h, kv_src=ctx, causal=False,
                         schedule=schedule, q_chunk=q_chunk, k_chunk=k_chunk))
    h = L.norm_apply(p["ln2"], x, cfg.norm)
    f, _ = _ffn(p, cfg, h, kind)
    return x + shard_act(f)


def _ffn(p: dict, cfg, h: jnp.ndarray, kind: str) -> tuple:
    """The block's feed-forward part: ``(out, stats)``, where ``stats``
    holds the held-expert layer's routing counts (``moe.held_experts``)
    and is empty for every other kind."""
    if kind == "mla_moe":
        return M.held_moe_apply(p["moe"], cfg, h)
    if kind == "moe":
        return M.moe_apply(p["moe"], cfg, h), {}
    return L.mlp_apply(p["mlp"], h, cfg.act), {}


# ---------------------------------------------------------------------------
# Decode (single token) per family
# ---------------------------------------------------------------------------


def layer_cache_shapes(cfg, kind: str, B: int, cache_len: int, dtype,
                       lane: int) -> dict:
    """One layer's decode cache.  Self-attention K/V are heads-major
    ``(B, KV, T, hp)``, the head dim padded up to a multiple of ``lane``
    (see ``model.cache_shapes``); latent attention keeps one entry per
    position, ``latent`` ``(B, 1, T, ep)``, padded the same way."""
    out = {}
    h, KV = cfg.head_dim, cfg.n_kv_heads
    if kind in MLA_KINDS:
        # one entry shared by every head per position (latent attention)
        e = -(-L.mla_entry_width(cfg) // lane) * lane
        out["latent"] = jax.ShapeDtypeStruct((B, 1, cache_len, e), dtype)
    if kind in ("dense", "moe", "hybrid", "dec", "cross"):
        T = min(cache_len, cfg.sliding_window) if cfg.sliding_window > 0 \
            else cache_len
        # Windowed archs only materialise the window (ring buffer) — this is
        # what keeps mixtral/hymba long_500k caches small.
        if kind != "cross":
            hp = -(-h // lane) * lane
            out["k"] = jax.ShapeDtypeStruct((B, KV, T, hp), dtype)
            out["v"] = jax.ShapeDtypeStruct((B, KV, T, hp), dtype)
    if kind in ("ssm", "hybrid"):
        out.update(S.ssm_cache_shapes(cfg, B, dtype))
    return out


def _ssm_decode(p: dict, cfg, h: jnp.ndarray, cache: dict, at: tuple):
    """Mamba decode on layer ``at`` of the stacked cache.  Recurrent state
    is small, so it keeps the slice → update → write-back path."""
    state = {k: L.layer_of(cache[k], at) for k in ("conv", "h")}
    y, new = S.ssm_decode_apply(p, cfg, h, state)
    return y, {k: L.put_layer(cache[k], new[k], at) for k in new}


def layer_decode_apply(p: dict, cfg, x: jnp.ndarray, cache: dict,
                       cache_index, kind: str, *, at: tuple = (),
                       ctx_kv: Optional[dict] = None):
    """One block, one token, on layer ``at`` of the stacked cache ``cache``
    (``at`` = the layer's leading indices; ``()`` when ``cache`` is one
    layer).  Returns ``(x, new_cache, stats)``: ``new_cache`` is the
    whole stack, into which attention writes its token's K/V at
    ``[*at, row, :, position]`` and from which it reads the layer;
    ``stats`` are as :func:`_ffn` gives them.

    For windowed caches the write index wraps (ring buffer) and the
    attention window covers the whole buffer.

    ``cache_index`` is a scalar (every row at the same position) or a
    per-row ``(B,)`` vector — continuous batching tracks each decode
    slot's position independently so a freshly refilled slot writes and
    masks at ITS OWN position, not a neighbour's.
    """
    new_cache = dict(cache)
    if kind == "ssm":
        h = L.norm_apply(p["ln1"], x, cfg.norm)
        y, sc = _ssm_decode(p["ssm"], cfg, h, cache, at)
        new_cache.update(sc)
        return x + y, new_cache, {}
    h = L.norm_apply(p["ln1"], x, cfg.norm)
    if kind in MLA_KINDS:
        a, new_cache["latent"] = L.mla_decode_apply(
            p["attn"], cfg, h, cache["latent"], cache_index, at)
    elif kind == "cross":
        a = L.cross_decode_apply(p["attn"], cfg, h, ctx_kv)
    else:
        T = cache["k"].shape[len(at) + 2]
        ci = jnp.asarray(cache_index, jnp.int32)
        idx = jnp.mod(ci, T) if cfg.sliding_window > 0 else ci
        # In the ring buffer every entry is valid once full; effective
        # index for masking is min(cache_index+1, T).
        p_attn = p["attn"]
        q = L.dense_apply(p_attn["wq"], h).reshape(
            x.shape[0], 1, cfg.n_heads, cfg.head_dim)
        k = L.dense_apply(p_attn["wk"], h).reshape(
            x.shape[0], 1, cfg.n_kv_heads, cfg.head_dim)
        v = L.dense_apply(p_attn["wv"], h).reshape(
            x.shape[0], 1, cfg.n_kv_heads, cfg.head_dim)
        if cfg.rope_theta > 0:
            pos = L.decode_positions(ci, x.shape[0])
            q = L.apply_rope(q, pos, cfg.rope_theta)
            k = L.apply_rope(k, pos, cfg.rope_theta)
        kc = L.kv_cache_update(cache["k"], k, idx, at)
        vc = L.kv_cache_update(cache["v"], v, idx, at)
        valid = jnp.minimum(ci + 1, T)
        a = L.decode_attention(q, L.layer_of(kc, at), L.layer_of(vc, at),
                               valid, window=0)
        a = L.dense_apply(p_attn["wo"], a.reshape(x.shape[0], 1, -1))
        new_cache["k"], new_cache["v"] = kc, vc
    if kind == "hybrid":
        y, sc = _ssm_decode(p["ssm"], cfg, h, cache, at)
        a = (a + y) * 0.5
        new_cache.update(sc)
    x = x + a
    if kind == "dec":
        h = L.norm_apply(p["lnx"], x, cfg.norm)
        x = x + L.cross_decode_apply(p["cross"], cfg, h, ctx_kv)
    f, stats = _ffn(p, cfg, L.norm_apply(p["ln2"], x, cfg.norm), kind)
    return x + f, new_cache, stats


def layer_prefill_apply(p: dict, cfg, x: jnp.ndarray, cache: dict,
                        cache_index, count, kind: str, *, at: tuple):
    """One block over a ``(B, C)`` token span (chunked prefill), on layer
    ``at`` of the stacked cache as in :func:`layer_decode_apply`.
    Returns ``(x, new_cache, stats)``.

    Only full-cache attention families are supported: recurrent state
    (ssm/hybrid) is not position-indexed, and ring-buffer
    (sliding-window) caches would need modular span writes.  The
    batcher rejects those configs at ``submit()``.
    """
    if kind not in SPAN_PREFILL_KINDS:
        raise NotImplementedError(
            f"span prefill is only defined for {SPAN_PREFILL_KINDS} "
            f"blocks, not kind={kind!r}")
    new_cache = dict(cache)
    h = L.norm_apply(p["ln1"], x, cfg.norm)
    if kind in MLA_KINDS:
        a, new_cache["latent"] = L.mla_prefill_apply(
            p["attn"], cfg, h, cache["latent"], cache_index, count, at)
    else:
        a, new_cache["k"], new_cache["v"] = L.attn_prefill_apply(
            p["attn"], cfg, h, cache, cache_index, count, at)
    x = x + a
    f, stats = _ffn(p, cfg, L.norm_apply(p["ln2"], x, cfg.norm), kind)
    return x + f, new_cache, stats
