"""Flash-attention forward Pallas TPU kernel with DLBC-balanced causal
scheduling.

TPU adaptation of the paper's load-balancing insight: causal attention is
an unbalanced triangular loop (query block i needs i+1 KV blocks).  The
``masked`` XLA path does the full rectangle and masks (2× FLOP waste —
the LC-style static chunking).  This kernel bounds the KV loop *per query
block* (``hi = i+1`` blocks) so every grid step does exactly the useful
work — the DLBC "spawn work only where it exists" policy on the MXU grid.
Sliding-window attention additionally lower-bounds the loop
(``lo = i - w/blk``), making long-context cells O(S·w).

Grid: (batch·kv_heads, q_blocks); the KV loop runs inside the kernel via
``jax.lax.fori_loop`` over VMEM blocks fetched with explicit BlockSpec
index maps.  Online softmax state (m, l, acc) lives in VMEM scratch.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, seq_k: int,
                 causal: bool, window: int, sm_scale: float):
    """One (bh, q_block) grid cell.

    q_ref: (block_q, G, dh) — G = query heads per kv head (GQA folded).
    k_ref/v_ref: (seq_k, dh) — full KV stream for this bh (VMEM-resident
    blocks are sliced inside the loop).
    """
    block_q, G, dh = q_ref.shape
    qi = pl.program_id(1)
    q_lo = qi * block_q

    q = q_ref[...].astype(jnp.float32) * sm_scale  # (bq, G, dh)

    nk = seq_k // block_k
    if causal:
        # DLBC-balanced bound: only blocks that intersect the triangle.
        hi = jnp.minimum((q_lo + block_q + block_k - 1) // block_k + 0, nk)
        hi = (q_lo + block_q + block_k - 1) // block_k
        hi = jnp.minimum(hi, nk)
    else:
        hi = nk
    if window > 0:
        lo = jnp.maximum((q_lo - (window - 1)) // block_k, 0)
    else:
        lo = 0

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q.reshape(block_q * G, dh), k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(block_q, G, block_k)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, 1, block_k), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1, block_k), 2)
        mask = jnp.ones_like(qpos, dtype=jnp.bool_)
        if causal:
            mask = mask & (qpos >= kpos)
        if window > 0:
            mask = mask & (qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        scale = jnp.exp(m - m_new)
        l_new = l * scale + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.reshape(block_q * G, block_k), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(block_q, G, dh)
        acc_new = acc * scale[..., None] + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, G), jnp.float32)
    a0 = jnp.zeros((block_q, G, dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, S, H, dh)
    k: jnp.ndarray,  # (B, T, KV, dh)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    sm_scale = dh ** -0.5

    # Layout: (B·KV, S, G, dh) so each grid row owns one kv-head stream.
    qr = q.reshape(B, S, KV, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B * KV, S, G, dh)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, T, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, T, dh)

    grid = (B * KV, S // block_q)
    kernel = functools.partial(
        _attn_kernel, block_k=block_k, seq_k=T, causal=causal,
        window=window, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, G, dh), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((None, T, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, T, dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, G, dh),
                               lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, S, G, dh), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, KV, S, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, dh)
