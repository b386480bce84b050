"""Grouped expert-FFN Pallas kernel: fused SwiGLU over capacity buffers.

Computes ``out[e] = (silu(buf[e]·w1[e]) ⊙ (buf[e]·w3[e])) · w2[e]`` for
every expert — the compute hot-spot behind the DLBC/LC MoE dispatch
(repro/models/moe.py builds the (E, C, d) buffers; this kernel is the
(E,C,d)×(E,d,f)×(E,f,d) contraction with explicit VMEM tiling).

Grid: (E, C/block_c).  Per grid cell the full (d, f_blk) weight slices
stream through VMEM via an inner fori loop over f blocks, accumulating
the down-projection in fp32 scratch — d and f block sizes are chosen so
the working set  block_c·d + d·block_f + block_c·block_f  fits VMEM with
MXU-aligned (×128) dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gmm_kernel(buf_ref, w1_ref, w3_ref, w2_ref, o_ref, *, block_f: int,
                d_ff: int):
    """buf_ref: (block_c, d); w*_ref: (d, f)/(f, d); o_ref: (block_c, d)."""
    x = buf_ref[...].astype(jnp.float32)
    nf = d_ff // block_f
    d = x.shape[-1]

    def body(j, acc):
        w1 = w1_ref[:, pl.ds(j * block_f, block_f)].astype(jnp.float32)
        w3 = w3_ref[:, pl.ds(j * block_f, block_f)].astype(jnp.float32)
        w2 = w2_ref[pl.ds(j * block_f, block_f), :].astype(jnp.float32)
        h = jax.nn.silu(x @ w1) * (x @ w3)       # (block_c, block_f)
        return acc + h @ w2                      # (block_c, d)

    acc = jnp.zeros_like(x)
    acc = jax.lax.fori_loop(0, nf, body, acc)
    o_ref[...] = acc.astype(o_ref.dtype)


def moe_gmm(
    buf: jnp.ndarray,   # (E, C, d)
    w1: jnp.ndarray,    # (E, d, f)
    w3: jnp.ndarray,    # (E, d, f)
    w2: jnp.ndarray,    # (E, f, d)
    *,
    block_c: int = 128,
    block_f: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    E, C, d = buf.shape
    f = w1.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    assert C % block_c == 0 and f % block_f == 0, (C, f, block_c, block_f)
    kernel = functools.partial(_gmm_kernel, block_f=block_f, d_ff=f)
    return pl.pallas_call(
        kernel,
        grid=(E, C // block_c),
        in_specs=[
            pl.BlockSpec((None, block_c, d), lambda e, c: (e, c, 0)),
            pl.BlockSpec((None, d, f), lambda e, c: (e, 0, 0)),
            pl.BlockSpec((None, d, f), lambda e, c: (e, 0, 0)),
            pl.BlockSpec((None, f, d), lambda e, c: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_c, d), lambda e, c: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), buf.dtype),
        interpret=interpret,
    )(buf, w1, w3, w2)
