"""Jit'd wrapper for the selective-scan kernel (tests off the TPU pass
``interpret=True``)."""

from __future__ import annotations

from functools import partial

import jax

from .ssm_scan import ssm_scan


@partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def ssm_scan_op(dA, dBx, C, *, chunk=128, block_d=256, interpret=False):
    return ssm_scan(dA, dBx, C, chunk=chunk, block_d=block_d,
                    interpret=interpret)

