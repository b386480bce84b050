"""Core NN layers: norms, RoPE, linear, MLP, and memory-efficient attention.

Everything is pure-functional: params are plain dict pytrees, and every
``init_*`` has a matching ``*_shapes`` so the dry-run can build
ShapeDtypeStruct pytrees without allocating (full configs are never
materialised on the CPU host).

Attention is chunked online-softmax ("flash in XLA"): the S×T score matrix
is never materialised.  Three schedules are provided —

* ``masked``   : scan over all KV chunks with a mask (small HLO; causal
                 pays 2× FLOPs — the unbalanced baseline);
* ``tri``      : python-unrolled lower-triangular chunk pairs (exact causal
                 FLOPs; bigger HLO) — the DLBC-balanced schedule on the XLA
                 path (each chunk pair does equal useful work);
* ``window``   : sliding-window attention visits only the O(w) diagonal
                 band (mixtral / hymba), which is what makes long-context
                 cells sub-quadratic.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Param helpers: every init has a shape-only twin
# ---------------------------------------------------------------------------


def _norm_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_shapes(d_in: int, d_out: int, bias: bool, dtype) -> dict:
    out = {"w": jax.ShapeDtypeStruct((d_in, d_out), dtype)}
    if bias:
        out["b"] = jax.ShapeDtypeStruct((d_out,), dtype)
    return out


def dense_init(key, d_in: int, d_out: int, bias: bool, dtype) -> dict:
    out = {"w": _norm_init(key, (d_in, d_out), d_in ** -0.5, dtype)}
    if bias:
        out["b"] = jnp.zeros((d_out,), dtype)
    return out


def dense_apply(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# Norms (fp32 accumulation)
# ---------------------------------------------------------------------------


def norm_shapes(d: int, kind: str, dtype) -> dict:
    out = {"scale": jax.ShapeDtypeStruct((d,), dtype)}
    if kind == "layernorm":
        out["bias"] = jax.ShapeDtypeStruct((d,), dtype)
    return out


def norm_init(key, d: int, kind: str, dtype) -> dict:
    out = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        out["bias"] = jnp.zeros((d,), dtype)
    return out


def norm_apply(p: dict, x: jnp.ndarray, kind: str, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32)
                            / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (..., S, H, dh); positions: (..., S)."""
    if theta <= 0:
        return x
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (dh/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,dh/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_shapes(d: int, f: int, act: str, dtype) -> dict:
    if act == "swiglu":
        return {
            "w1": jax.ShapeDtypeStruct((d, f), dtype),
            "w3": jax.ShapeDtypeStruct((d, f), dtype),
            "w2": jax.ShapeDtypeStruct((f, d), dtype),
        }
    return {
        "w1": jax.ShapeDtypeStruct((d, f), dtype),
        "b1": jax.ShapeDtypeStruct((f,), dtype),
        "w2": jax.ShapeDtypeStruct((f, d), dtype),
        "b2": jax.ShapeDtypeStruct((d,), dtype),
    }


def mlp_init(key, d: int, f: int, act: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    if act == "swiglu":
        return {
            "w1": _norm_init(k1, (d, f), d ** -0.5, dtype),
            "w3": _norm_init(k3, (d, f), d ** -0.5, dtype),
            "w2": _norm_init(k2, (f, d), f ** -0.5, dtype),
        }
    return {
        "w1": _norm_init(k1, (d, f), d ** -0.5, dtype),
        "b1": jnp.zeros((f,), dtype),
        "w2": _norm_init(k2, (f, d), f ** -0.5, dtype),
        "b2": jnp.zeros((d,), dtype),
    }


def mlp_apply(p: dict, x: jnp.ndarray, act: str) -> jnp.ndarray:
    if act == "swiglu":
        return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]
    h = jax.nn.gelu(x @ p["w1"] + p["b1"])
    return h @ p["w2"] + p["b2"]


# ---------------------------------------------------------------------------
# Attention (chunked online softmax)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _attn_chunk(q, k, v, qpos, kpos, *, causal: bool, window: int,
                kv_valid: int = 0, scale: float):
    """One (q-chunk × kv-chunk) block of online softmax.

    q: (B, qc, KV, G, dh); k/v: (B, kc, KV, dv).
    Returns (scores_max, exp_sum, acc) contributions in fp32.
    """
    s = jnp.einsum("bqkgd,bckd->bqkgc", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((q.shape[1], k.shape[1]), dtype=bool)
    if causal:
        mask = mask & (qpos[:, None] >= kpos[None, :])
    if window > 0:
        mask = mask & (qpos[:, None] - kpos[None, :] < window)
    if kv_valid:
        mask = mask & (kpos[None, :] < kv_valid)
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                  # (B,qc,KV,G)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask[None, :, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)                                  # (B,qc,KV,G)
    acc = jnp.einsum("bqkgc,bckd->bqkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _merge_online(carry, new):
    """Merge two online-softmax partials (m, l, acc)."""
    m0, l0, a0 = carry
    m1, l1, a1 = new
    m = jnp.maximum(m0, m1)
    c0 = jnp.exp(m0 - m)
    c1 = jnp.exp(m1 - m)
    return m, l0 * c0 + l1 * c1, a0 * c0[..., None] + a1 * c1[..., None]


def chunked_attention(
    q: jnp.ndarray,       # (B, S, H, dh)
    k: jnp.ndarray,       # (B, T, KV, dh)
    v: jnp.ndarray,       # (B, T, KV, dh)
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
    schedule: str = "masked",   # masked | tri
    q_offset: int = 0,          # absolute position of q[0] (cross/cache)
    scale: Optional[float] = None,  # default dh ** -0.5
) -> jnp.ndarray:
    """Memory-efficient multi-head attention with GQA.

    ``schedule='tri'`` unrolls only the lower-triangular (or in-window)
    chunk pairs — the load-balanced schedule (exact FLOPs); ``masked``
    visits every pair with masking (compact HLO, 2× causal FLOP waste).
    Values may be narrower than queries and keys (``v`` (B, T, KV, dv));
    the output then has ``dv``.
    """
    B, S, H, dh = q.shape
    T, KV, dv = k.shape[1], k.shape[2], v.shape[3]
    scale = dh ** -0.5 if scale is None else scale
    G = H // KV
    # Ragged lengths (whisper's 1500 frames, vision's 1601 patches): pad to
    # the chunk grid; padded KV is masked via kv_valid, padded q rows are
    # sliced off the output.
    S0, T0 = S, T
    q_chunk = min(q_chunk, S)
    k_chunk = min(k_chunk, T)
    kv_valid = 0
    if S % q_chunk:
        pad = q_chunk - S % q_chunk
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S += pad
    if T % k_chunk:
        pad = k_chunk - T % k_chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_valid = T0
        T += pad
    q = q.reshape(B, S, KV, G, dh)
    nq = S // q_chunk
    nk = T // k_chunk

    qs = q.reshape(B, nq, q_chunk, KV, G, dh)
    ks = k.reshape(B, nk, k_chunk, KV, dh)
    vs = v.reshape(B, nk, k_chunk, KV, dv)

    # banded window scan needs q/k chunk grids in lockstep
    kv_src_aligned = (q_chunk == k_chunk) and q_offset == 0

    def q_block(i, qi):
        qpos = q_offset + i * q_chunk + jnp.arange(q_chunk)

        def kv_visible(j):
            # Static reachability for pruning (tri/window schedules).
            q_lo = q_offset + i * q_chunk
            q_hi = q_lo + q_chunk - 1
            k_lo, k_hi = j * k_chunk, (j + 1) * k_chunk - 1
            if causal and k_lo > q_hi:
                return False
            if window > 0 and k_hi < q_lo - (window - 1) - (q_chunk - 1):
                return False
            return True

        if schedule == "tri":
            m = jnp.full((B, q_chunk, KV, G), NEG_INF, jnp.float32)
            l = jnp.zeros((B, q_chunk, KV, G), jnp.float32)
            acc = jnp.zeros((B, q_chunk, KV, G, dv), jnp.float32)
            carry = (m, l, acc)
            for j in range(nk):
                if not kv_visible(j):
                    continue
                kpos = j * k_chunk + jnp.arange(k_chunk)
                part = _attn_chunk(qi, ks[:, j], vs[:, j], qpos, kpos,
                                   causal=causal, window=window,
                                   kv_valid=kv_valid, scale=scale)
                carry = _merge_online(carry, part)
            m, l, acc = carry
        elif window > 0 and causal and kv_src_aligned:
            # Banded scan (DLBC "only do work where it exists", without the
            # unrolled-HLO blow-up of 'tri'): a sliding-window q chunk only
            # sees the diagonal band of ⌈w/kc⌉+1 KV chunks, visited via
            # dynamic indices relative to the q-chunk position.  Duplicate
            # clamped indices at the left edge are masked out (valid flag).
            noff = min(nk, (window + q_chunk - 1) // k_chunk + 1)

            def body(carry, off):
                j_raw = i - off
                j = jnp.clip(j_raw, 0, nk - 1)
                kj = jax.lax.dynamic_index_in_dim(ks, j, 1, keepdims=False)
                vj = jax.lax.dynamic_index_in_dim(vs, j, 1, keepdims=False)
                kpos = j * k_chunk + jnp.arange(k_chunk)
                part = _attn_chunk(qi, kj, vj, qpos, kpos, causal=causal,
                                   window=window, kv_valid=kv_valid,
                                   scale=scale)
                valid = (j_raw >= 0).astype(jnp.float32)
                part = (jnp.where(valid > 0, part[0], NEG_INF),
                        part[1] * valid, part[2] * valid)
                return _merge_online(carry, part), None

            init = (
                jnp.full((B, q_chunk, KV, G), NEG_INF, jnp.float32),
                jnp.zeros((B, q_chunk, KV, G), jnp.float32),
                jnp.zeros((B, q_chunk, KV, G, dv), jnp.float32),
            )
            (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(noff))
        else:
            def body(carry, j):
                kj = jax.lax.dynamic_index_in_dim(ks, j, 1, keepdims=False)
                vj = jax.lax.dynamic_index_in_dim(vs, j, 1, keepdims=False)
                kpos = j * k_chunk + jnp.arange(k_chunk)
                part = _attn_chunk(qi, kj, vj, qpos, kpos,
                                   causal=causal, window=window,
                                   kv_valid=kv_valid, scale=scale)
                return _merge_online(carry, part), None

            init = (
                jnp.full((B, q_chunk, KV, G), NEG_INF, jnp.float32),
                jnp.zeros((B, q_chunk, KV, G), jnp.float32),
                jnp.zeros((B, q_chunk, KV, G, dv), jnp.float32),
            )
            (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)

    if nq == 1:
        out = q_block(0, qs[:, 0])
        return out.reshape(B, S, H, dv)[:, :S0]
    # Unrolled python loop over q chunks in 'tri' (each body differs);
    # scan in 'masked'.
    if schedule == "tri":
        outs = [q_block(i, qs[:, i]) for i in range(nq)]
        out = jnp.stack(outs, axis=1)
    else:
        def qbody(_, i):
            return None, q_block(i, jax.lax.dynamic_index_in_dim(
                qs, i, 1, keepdims=False))

        _, out = jax.lax.scan(qbody, None, jnp.arange(nq))
        out = jnp.moveaxis(out, 0, 1)  # (B, nq, qc, KV, G, dv)
    return out.reshape(B, S, H, dv)[:, :S0]


def pad_head(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad the last (head) dim of ``x`` to ``width``."""
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def decode_attention(
    q: jnp.ndarray,        # (B, 1, H, dh)
    k_cache: jnp.ndarray,  # (B, KV, T, dc), dc >= dh
    v_cache: jnp.ndarray,
    cache_index: jnp.ndarray,  # () or (B,) int32 — valid cache entries
    *,
    window: int = 0,
    scale: Optional[float] = None,  # default dh ** -0.5
) -> jnp.ndarray:
    """One-token attention against a (possibly windowed) KV cache.

    ``cache_index`` may be a scalar (every row at the same position —
    training-style decode) or per-row ``(B,)`` (continuous batching,
    where a freshly refilled slot sits at position 0 while its
    neighbours are deep into their sequences).

    The cache is heads-major, each head's ``(T, dc)`` rows together, and
    its head dim ``dc`` may be padded past ``dh`` (see
    ``model.cache_shapes``): q is zero-padded to match, so the extra
    products are exact zeros, and the output is sliced back to ``dh``.
    """
    B, _, H, dh = q.shape
    KV, T, dc = k_cache.shape[1], k_cache.shape[2], k_cache.shape[3]
    G = H // KV
    qg = pad_head(q, dc).reshape(B, KV, G, dc)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache,
                   preferred_element_type=jnp.float32) * (
        dh ** -0.5 if scale is None else scale)
    pos = jnp.arange(T)
    ci = jnp.asarray(cache_index)
    if ci.ndim == 0:
        ci = jnp.full((B,), ci)
    mask = pos[None, :] < ci[:, None]                       # (B, T)
    if window > 0:
        mask = mask & (pos[None, :] >= ci[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out[..., :dh].reshape(B, 1, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention block (projections + rope + cache handling)
# ---------------------------------------------------------------------------


def attn_shapes(cfg, dtype, cross: bool = False) -> dict:
    d, h = cfg.d_model, cfg.head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    bias = cfg.qkv_bias
    return {
        "wq": dense_shapes(d, H * h, bias, dtype),
        "wk": dense_shapes(d, KV * h, bias, dtype),
        "wv": dense_shapes(d, KV * h, bias, dtype),
        "wo": dense_shapes(H * h, d, False, dtype),
    }


def attn_init(key, cfg, dtype, cross: bool = False) -> dict:
    d, h = cfg.d_model, cfg.head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    kq, kk, kv_, ko = jax.random.split(key, 4)
    bias = cfg.qkv_bias
    return {
        "wq": dense_init(kq, d, H * h, bias, dtype),
        "wk": dense_init(kk, d, KV * h, bias, dtype),
        "wv": dense_init(kv_, d, KV * h, bias, dtype),
        "wo": dense_init(ko, H * h, d, False, dtype),
    }


def attn_apply(
    p: dict, cfg, x: jnp.ndarray, *,
    kv_src: Optional[jnp.ndarray] = None,   # cross-attention source
    causal: bool = True,
    positions: Optional[jnp.ndarray] = None,
    schedule: str = "masked",
    q_chunk: int = 1024, k_chunk: int = 1024,
) -> jnp.ndarray:
    B, S, d = x.shape
    H, KV, h = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = x if kv_src is None else kv_src
    T = src.shape[1]
    q = dense_apply(p["wq"], x).reshape(B, S, H, h)
    k = dense_apply(p["wk"], src).reshape(B, T, KV, h)
    v = dense_apply(p["wv"], src).reshape(B, T, KV, h)
    if kv_src is None and cfg.rope_theta > 0:
        pos = positions if positions is not None else jnp.arange(S)[None, :]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    # Context-parallel attention: q stays sequence-sharded over the model
    # axis (matching the SP residual stream); k/v are gathered ONCE per
    # layer.  Without these constraints GSPMD reshards per KV-chunk inside
    # the online-softmax scan (an all-to-all every chunk — §Perf iter. 5).
    from ..distributed.sharding import current_mesh, fsdp_axes
    import jax as _jax
    from jax.sharding import NamedSharding as _NS, PartitionSpec as _P

    mesh = current_mesh()
    if mesh is not None and S > 1:
        fa = fsdp_axes(mesh)
        msize = mesh.shape["model"]
        dsize = 1
        for a in fa:
            dsize *= mesh.shape[a]
        b_ax = fa if B % dsize == 0 else None
        s_ax = "model" if S % msize == 0 and S >= q_chunk * msize else None
        q = _jax.lax.with_sharding_constraint(
            q, _NS(mesh, _P(b_ax, s_ax, None, None)))
        k = _jax.lax.with_sharding_constraint(
            k, _NS(mesh, _P(b_ax, None, None, None)))
        v = _jax.lax.with_sharding_constraint(
            v, _NS(mesh, _P(b_ax, None, None, None)))
    out = chunked_attention(
        q, k, v, causal=causal and kv_src is None,
        window=cfg.sliding_window if kv_src is None else 0,
        q_chunk=q_chunk, k_chunk=k_chunk, schedule=schedule,
    )
    return dense_apply(p["wo"], out.reshape(B, S, H * h))


def decode_positions(cache_index, B: int) -> jnp.ndarray:
    """Normalise a scalar-or-``(B,)`` cache index to per-row positions
    ``(B, 1)`` (rope / masking)."""
    ci = jnp.asarray(cache_index, jnp.int32)
    if ci.ndim == 0:
        return jnp.full((B, 1), ci, dtype=jnp.int32)
    return ci[:, None]


def layer_of(stack: jnp.ndarray, at: tuple) -> jnp.ndarray:
    """The layer ``stack[at]`` of a stacked cache leaf, where ``at`` holds
    the layer's leading indices (``()``: ``stack`` is one layer)."""
    for i in at:
        stack = jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)
    return stack


def put_layer(stack: jnp.ndarray, new: jnp.ndarray, at: tuple) -> jnp.ndarray:
    """Write a whole layer ``new`` back into ``stack`` at ``at`` — for
    small recurrent state only; K/V are written position by position."""
    return _put(stack, new, tuple(at) + (0,) * new.ndim)


def _put(stack: jnp.ndarray, upd: jnp.ndarray, start: tuple) -> jnp.ndarray:
    """``dynamic_update_slice`` of ``upd`` into ``stack`` at ``start``,
    ``upd`` taking size 1 on the leading dims it lacks."""
    upd = upd.reshape((1,) * (stack.ndim - upd.ndim) + upd.shape)
    return jax.lax.dynamic_update_slice(stack, upd.astype(stack.dtype), start)


def kv_cache_update(cache_arr: jnp.ndarray, new: jnp.ndarray, idx,
                    at: tuple = ()) -> jnp.ndarray:
    """Write a one-token K/V slice ``new`` (B, 1, KV, h) into the stacked
    cache ``cache_arr`` (*L, B, KV, T, dc) at ``[*at, b, :, idx[b]]``,
    where ``at`` are the layer's leading indices and ``idx`` is a scalar
    (every row at the same position) or per-row ``(B,)`` (continuous
    batching: each slot writes at ITS OWN position, so a refill
    mid-decode cannot clobber or land past a neighbour).

    Only the written positions move: the caller's launch donates the
    cache and the layer loop carries it, so XLA updates the one buffer
    in place instead of building and writing back a layer.  ``new`` is
    zero-padded to the cache's head dim ``dc``.  The write scatters one
    ``dc`` row per (row, head): a window of the cache's minor dim alone,
    which leaves the cache's layout free (a ``(KV, 1, dc)`` window would
    ask for KV-minor, and the launch would convert the cache to it)."""
    lead = len(at)
    B, KV, T = cache_arr.shape[lead:lead + 3]
    new = pad_head(new, cache_arr.shape[-1]).astype(cache_arr.dtype)
    idx = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (B,))
    # a caller that runs past the cache overwrites position T-1, as a
    # dynamic_update_slice would; an out-of-bounds scatter would DROP it
    idx = jnp.minimum(idx, T - 1)
    rows = jnp.arange(B)[:, None, None]
    heads = jnp.arange(KV)[None, None, :]
    return cache_arr.at[tuple(at) + (rows, heads, idx[:, None, None])].set(
        new)


def kv_cache_update_span(cache_arr: jnp.ndarray, new: jnp.ndarray,
                         idx: jnp.ndarray, count: jnp.ndarray,
                         at: tuple = ()) -> jnp.ndarray:
    """Write a K/V span ``new`` (B, C, KV, h) into the stacked cache at
    ``[*at, b, :, idx[b] + j]`` — the multi-token generalisation of
    :func:`kv_cache_update` for chunked prefill, in place in the same way.

    Only the first ``count[b]`` lanes of row b are written: padding
    lanes, and any lane that would land past the cache end, are DROPPED,
    so a masked row's cache is untouched bit-for-bit.  That drop is what
    isolates a prefilling slot's padded launch buffer from its
    neighbours in the batch.  Each row reads the ``(KV, W, dc)`` window
    it lands in, keeps the old values where a lane is dropped, and
    writes the window back: one ``dynamic_update_slice`` per row.

    The rows are unrolled in Python, so the program's op count and
    compile time grow with the slot count.  A scatter of the span's
    ``(row, head, lane)`` rows (which is also what a vmapped
    ``dynamic_update_slice`` lowers to) is just as copy-free, but on a
    TPU v5e it kept phi3's prefill launch at 35.8 ms against 19.1 ms
    for the unrolled windows (4 slots x 32 lanes)."""
    lead = len(at)
    B, T = cache_arr.shape[lead], cache_arr.shape[lead + 2]
    W = min(new.shape[1], T)          # no lane at or past T is written
    new = jnp.swapaxes(pad_head(new[:, :W], cache_arr.shape[-1]), 1, 2)
    new = new.astype(cache_arr.dtype)                   # (B, KV, W, dc)
    idx = jnp.asarray(idx, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    for b in range(B):
        lo = jnp.clip(idx[b], 0, T - W)   # the window [lo, lo + W) of T
        lane = lo + jnp.arange(W, dtype=jnp.int32) - idx[b]
        keep = (lane >= 0) & (lane < count[b])
        start = tuple(at) + (b, 0, lo, 0)
        old = jax.lax.dynamic_slice(
            cache_arr, start, (1,) * (lead + 1) + new.shape[1:]
        ).reshape(new.shape[1:])
        span = jnp.take(new[b], jnp.clip(lane, 0, W - 1), axis=1)
        cache_arr = _put(cache_arr, jnp.where(keep[:, None], span, old),
                         start)
    return cache_arr


def prefill_attention(
    q: jnp.ndarray,        # (B, C, H, dh)
    k_cache: jnp.ndarray,  # (B, KV, T, dc), dc >= dh
    v_cache: jnp.ndarray,
    cache_index: jnp.ndarray,  # (B,) absolute position of q[:, 0]
    scale: Optional[float] = None,  # default dh ** -0.5
) -> jnp.ndarray:
    """Causal attention of a C-token span against the full KV cache.

    Query ``j`` of row ``b`` sits at absolute position
    ``cache_index[b] + j`` and sees every cache position ``<=`` its own
    — which, with the span's own K/V already written, is exactly the
    full-softmax semantics of :func:`decode_attention` applied per lane.
    Because each query's scores reduce over the same (dh, T) axes
    regardless of where the chunk boundary falls, the outputs are
    BITWISE identical across chunkings of the same prompt (the chunked
    == whole-prompt exactness the serving tests pin).  A head dim padded
    in the cache is handled as in :func:`decode_attention`.

    Padded lanes (callers mask them via the span write's ``count``)
    produce garbage that callers must never read; their KV writes are
    dropped and their logits are never consumed.
    """
    B, C, H, dh = q.shape
    KV, T, dc = k_cache.shape[1], k_cache.shape[2], k_cache.shape[3]
    G = H // KV
    # heads-major like the cache: (b, k) lead both operands of both dots
    qg = pad_head(q, dc).reshape(B, C, KV, G, dc).transpose(0, 2, 3, 1, 4)
    s = jnp.einsum("bkgcd,bktd->bkgct", qg, k_cache,
                   preferred_element_type=jnp.float32) * (
        dh ** -0.5 if scale is None else scale)
    ci = jnp.asarray(cache_index, jnp.int32)
    qpos = ci[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]   # (B, C)
    mask = jnp.arange(T)[None, None, :] <= qpos[..., None]         # (B, C, T)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgct,bktd->bkgcd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out[..., :dh].transpose(0, 3, 1, 2, 4)          # (B, C, KV, G, dh)
    return out.reshape(B, C, H, dh).astype(q.dtype)


def attn_prefill_apply(p: dict, cfg, x: jnp.ndarray, cache: dict,
                       cache_index, count, at: tuple) -> tuple:
    """Span prefill: project a (B, C, d) chunk, write its K/V into the
    stacked cache at ``[*at, b, :, cache_index[b] + lane]`` (``count`` masks
    each row's valid lanes), attend causally over the layer's cache.
    Returns ``(out, k_stack, v_stack)``.

    RoPE is applied at the absolute positions ``cache_index + lane``,
    so a chunk boundary never shifts a token's rotary phase."""
    B, C, d = x.shape
    H, KV, h = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense_apply(p["wq"], x).reshape(B, C, H, h)
    k = dense_apply(p["wk"], x).reshape(B, C, KV, h)
    v = dense_apply(p["wv"], x).reshape(B, C, KV, h)
    ci = jnp.asarray(cache_index, jnp.int32)
    if ci.ndim == 0:
        ci = jnp.full((B,), ci, jnp.int32)
    cnt = jnp.asarray(count, jnp.int32)
    if cfg.rope_theta > 0:
        pos = ci[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    k_stack = kv_cache_update_span(cache["k"], k, ci, cnt, at)
    v_stack = kv_cache_update_span(cache["v"], v, ci, cnt, at)
    out = prefill_attention(q, layer_of(k_stack, at), layer_of(v_stack, at),
                            ci)
    y = dense_apply(p["wo"], out.reshape(B, C, H * h))
    return y, k_stack, v_stack


# ---------------------------------------------------------------------------
# Latent attention (DeepSeek-V2/V3 MLA) over a latent cache
# ---------------------------------------------------------------------------
#
# Every position caches one entry shared by all heads: the normalised
# latent ``c`` (kv_lora_rank) and the rotated key ``k_rope``
# (qk_rope_head_dim), ``[c, k_rope]``.  Head h's key is
# ``[c @ Wk_h, k_rope]`` and its value ``c @ Wv_h``, with ``Wk_h`` and
# ``Wv_h`` the halves of ``wkv_b``.  The attention is computed in the
# absorbed form: ``Wk_h`` is folded into the query
# (``q_lat = Wk_h @ q_nope``), so each head scores ``[q_lat, q_rope]``
# against the entries themselves, and ``Wv_h`` is applied to the
# softmax-weighted sum of the latents.  That is attention with one
# shared key/value head (``KV = 1``), keys of ``rank + rope`` and values
# the entry's first ``rank``: the cache is ``(B, 1, T, e)`` and the
# in-place writes and the attention of the other families serve it.


def mla_shapes(cfg, dtype) -> dict:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"wq": dense_shapes(d, H * (nope + rope), False, dtype),
            "wkv_a": dense_shapes(d, r + rope, False, dtype),
            "kv_norm": norm_shapes(r, "rmsnorm", dtype),
            "wkv_b": dense_shapes(r, H * (nope + dv), False, dtype),
            "wo": dense_shapes(H * dv, d, False, dtype)}


def mla_init(key, cfg, dtype) -> dict:
    shapes = mla_shapes(cfg, dtype)
    keys = iter(jax.random.split(key, 4))
    return {k: (norm_init(None, v["scale"].shape[0], "rmsnorm", dtype)
                if k == "kv_norm" else
                dense_init(next(keys), *v["w"].shape, False, dtype))
            for k, v in shapes.items()}


def mla_entry_width(cfg) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def mla_scale(cfg) -> float:
    """The softmax scale: one over the root of the query-key head size."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _wkv_b(p: dict, cfg):
    """``wkv_b`` as ``(rank, H, nope + dv)``: the key and value halves."""
    return p["wkv_b"]["w"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)


def mla_project(p: dict, cfg, x: jnp.ndarray, pos: jnp.ndarray):
    """Queries and cache entries of a ``(B, S, d)`` span at positions
    ``pos`` ``(B, S)``: absorbed queries ``(B, S, H, rank + rope)`` and
    entries ``(B, S, 1, rank + rope)``."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = dense_apply(p["wq"], x).reshape(B, S, H, nope + rope)
    kv = dense_apply(p["wkv_a"], x)
    c = norm_apply(p["kv_norm"], kv[..., :r], "rmsnorm")
    k_rope = apply_rope(kv[..., r:].reshape(B, S, 1, rope), pos,
                        cfg.rope_theta)
    q_rope = apply_rope(q[..., nope:], pos, cfg.rope_theta)
    q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :nope],
                       _wkv_b(p, cfg)[..., :nope],
                       preferred_element_type=jnp.float32).astype(x.dtype)
    return (jnp.concatenate([q_lat, q_rope], axis=-1),
            jnp.concatenate([c[:, :, None, :], k_rope], axis=-1))


def mla_output(p: dict, cfg, o_lat: jnp.ndarray) -> jnp.ndarray:
    """Each head's value projection of its weighted latent sum
    ``o_lat[..., :rank]`` ``(B, S, H, >= rank)``, then the output
    projection."""
    B, S = o_lat.shape[:2]
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    o = jnp.einsum("bshr,rhv->bshv", o_lat[..., :r],
                   _wkv_b(p, cfg)[..., nope:],
                   preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return dense_apply(p["wo"], o.reshape(B, S, -1))


def mla_apply(p: dict, cfg, x: jnp.ndarray, *, schedule: str = "masked",
              q_chunk: int = 1024, k_chunk: int = 1024) -> jnp.ndarray:
    """Causal latent attention over a whole ``(B, S, d)`` sequence
    (training / forward)."""
    with jax.named_scope("mla_attention"):
        B, S, _ = x.shape
        q, e = mla_project(p, cfg, x, jnp.arange(S)[None, :])
        o = chunked_attention(q, e, e[..., :cfg.kv_lora_rank], causal=True,
                              q_chunk=q_chunk, k_chunk=k_chunk,
                              schedule=schedule, scale=mla_scale(cfg))
        return mla_output(p, cfg, o)


def mla_decode_apply(p: dict, cfg, x: jnp.ndarray, latent: jnp.ndarray,
                     cache_index, at: tuple) -> tuple:
    """One token per row: write its entry into the stacked latent cache
    at ``[*at, row, 0, cache_index[row]]`` and attend over the layer's
    valid entries.  Returns ``(out, latent)``."""
    with jax.named_scope("mla_attention"):
        B = x.shape[0]
        ci = jnp.asarray(cache_index, jnp.int32)
        q, e = mla_project(p, cfg, x, decode_positions(ci, B))
        latent = kv_cache_update(latent, e, ci, at)
        c = layer_of(latent, at)
        T = c.shape[2]
        o = decode_attention(q, c, c, jnp.minimum(ci + 1, T),
                             scale=mla_scale(cfg))
        return mla_output(p, cfg, o), latent


def mla_prefill_apply(p: dict, cfg, x: jnp.ndarray, latent: jnp.ndarray,
                      cache_index, count, at: tuple) -> tuple:
    """Span prefill: write each row's first ``count`` entries at
    ``cache_index + lane`` and attend causally over the layer's cache,
    as :func:`attn_prefill_apply` does.  Returns ``(out, latent)``."""
    with jax.named_scope("mla_attention"):
        B, C, _ = x.shape
        ci = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (B,))
        pos = ci[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        q, e = mla_project(p, cfg, x, pos)
        latent = kv_cache_update_span(latent, e, ci,
                                      jnp.asarray(count, jnp.int32), at)
        c = layer_of(latent, at)
        o = prefill_attention(q, c, c, ci, scale=mla_scale(cfg))
        return mla_output(p, cfg, o), latent


def cross_decode_apply(p: dict, cfg, x: jnp.ndarray, cross_kv: dict):
    """Decode-time cross attention against precomputed encoder K/V
    (heads-major, ``(B, KV, S, h)``)."""
    B = x.shape[0]
    H, KV, h = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense_apply(p["wq"], x).reshape(B, 1, H, h)
    T = cross_kv["k"].shape[2]
    out = decode_attention(q, cross_kv["k"], cross_kv["v"],
                           jnp.asarray(T, jnp.int32), window=0)
    return dense_apply(p["wo"], out.reshape(B, 1, H * h))
