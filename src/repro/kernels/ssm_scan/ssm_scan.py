"""Selective-scan (Mamba-1) Pallas TPU kernel.

Computes ``h_t = dA_t ⊙ h_{t-1} + dBx_t;  y_t = ⟨h_t, C_t⟩`` over the
sequence, with the recurrence carried across sequence chunks in VMEM
scratch: the grid's last dimension walks chunks **sequentially** on TPU,
so the (N, block_d) state persists between grid steps — HBM traffic is
exactly one read of (dA, dBx, C) and one write of y per chunk
(roofline-minimal for this memory-bound op).

Grid: (B, d_inner/block_d, L/chunk); within a chunk the recurrence is an
in-VMEM ``fori_loop`` over time.  The kernel sees the state as
(N, block_d), d_inner on the lanes: TPU's default layout for a
(B, L, Di, N) array already keeps Di minor, so the swap below is free,
where an N-minor block would pad N=16 to 128 lanes (8× the VMEM and HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(dA_ref, dBx_ref, C_ref, y_ref, h_scratch, *, chunk: int):
    """One (b, d-block, chunk) cell.

    dA_ref/dBx_ref: (chunk, N, block_d); C_ref: (chunk, N, 1);
    y_ref: (chunk, block_d); h_scratch: (N, block_d) persistent state.
    """
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    def body(t, h):
        h = dA_ref[t].astype(jnp.float32) * h + dBx_ref[t].astype(jnp.float32)
        y = jnp.sum(h * C_ref[t].astype(jnp.float32), axis=0, keepdims=True)
        y_ref[pl.ds(t, 1), :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_scratch[...])
    h_scratch[...] = h


def ssm_scan(
    dA: jnp.ndarray,    # (B, L, Di, N) fp32
    dBx: jnp.ndarray,   # (B, L, Di, N) fp32
    C: jnp.ndarray,     # (B, L, N) fp32
    *,
    chunk: int = 128,
    block_d: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y: (B, L, Di) fp32 (caller adds the D·x skip and gating)."""
    B, L, Di, N = dA.shape
    chunk = min(chunk, L)
    block_d = min(block_d, Di)
    assert L % chunk == 0 and Di % block_d == 0, (L, chunk, Di, block_d)
    grid = (B, Di // block_d, L // chunk)
    kernel = functools.partial(_ssm_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, N, block_d),
                         lambda b, d, c: (b, c, 0, d)),
            pl.BlockSpec((None, chunk, N, block_d),
                         lambda b, d, c: (b, c, 0, d)),
            pl.BlockSpec((None, chunk, N, 1), lambda b, d, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, chunk, block_d),
                               lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, L, Di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.swapaxes(dA, -1, -2), jnp.swapaxes(dBx, -1, -2), C[..., None])
