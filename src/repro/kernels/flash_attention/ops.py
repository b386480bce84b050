"""Jit'd public wrapper for the flash-attention kernel.

It compiles to Mosaic for the TPU; callers without a TPU (tests) pass
``interpret=True``.
"""

from __future__ import annotations

from functools import partial

import jax

from .flash_attention import flash_attention


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention_op(q, k, v, *, causal=True, window=0, block_q=128,
                       block_k=128, interpret=False):
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)

