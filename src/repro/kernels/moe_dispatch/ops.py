"""MoE dispatch ops: token routing into capacity buffers + expert FFN.

This module owns the *mechanics* of MoE dispatch — scattering admitted
(token, choice) pairs into per-expert ``(E, C, d)`` capacity buffers,
running the expert FFN (XLA einsum or the Pallas grouped-matmul kernel),
and gathering/combining the results.  The *admission decision* (which
pairs get a slot, which overflow) is made by the caller through
:class:`repro.sched.capacity.ExpertCapacityProvider` — the one DLBC/LC
drop arithmetic shared with every other execution surface; no private
drop policy lives here or in :mod:`repro.models.moe` anymore.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .moe_gmm import moe_gmm


@partial(jax.jit, static_argnames=("block_c", "block_f", "interpret"))
def moe_gmm_op(buf, w1, w3, w2, *, block_c=128, block_f=128,
               interpret=False):
    return moe_gmm(buf, w1, w3, w2, block_c=block_c, block_f=block_f,
                   interpret=interpret)


def dispatch_tokens(x, keep, ids, pos, E: int, C: int):
    """Scatter admitted tokens into (E, C, d) buffers.

    ``keep`` is the admission mask from the capacity provider; dropped
    pairs scatter a zero contribution (their slot index is clamped).
    Returns (buf, slot) — ``slot`` is reused by :func:`combine_tokens`.
    """
    T, d = x.shape
    K = ids.shape[1]
    slot = ids * C + jnp.minimum(pos, C - 1)  # (T, K)
    keepf = keep.astype(x.dtype)
    buf = jnp.zeros((E * C, d), x.dtype)
    # Slots are unique per (expert, pos) by construction → add == set.
    buf = buf.at[slot.reshape(-1)].add(
        (x[:, None, :] * keepf[..., None]).reshape(T * K, d))
    return buf.reshape(E, C, d), slot


def combine_tokens(out, slot, gates, keep, gate_dtype=None):
    """Gather expert outputs back to token order and gate-combine."""
    E, C, d = out.shape
    T, K = slot.shape
    gathered = out.reshape(E * C, d)[slot.reshape(-1)].reshape(T, K, d)
    w = (gates * keep).astype(gate_dtype or gathered.dtype)
    return jnp.einsum("tkd,tk->td", gathered, w)


def _tile(n: int, cap: int = 128) -> int:
    """Largest block size ≤ cap that divides n (n ≥ 1 ⇒ always exists)."""
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def expert_ffn(buf, p: dict, act: str, use_kernel: bool = False):
    """The (E, C, d) × expert-weights contraction: XLA einsum by default,
    the Pallas grouped-matmul kernel when ``use_kernel`` (SwiGLU only —
    gelu experts fall back to einsum)."""
    E, C, d = buf.shape
    if use_kernel and act == "swiglu":
        f = p["w1"].shape[-1]
        return moe_gmm_op(buf, p["w1"].astype(buf.dtype),
                          p["w3"].astype(buf.dtype),
                          p["w2"].astype(buf.dtype),
                          block_c=_tile(C), block_f=_tile(f))
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w1"])) * \
            jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, p["w1"]))
    return jnp.einsum("ecf,efd->ecd", h, p["w2"])


def dispatch_combine(x, gates, ids, pos, keep, E: int, C: int, p: dict,
                     act: str, use_kernel: bool = False):
    """dispatch → expert FFN → combine, for pre-decided admissions."""
    buf, slot = dispatch_tokens(x, keep, ids, pos, E, C)
    out = expert_ffn(buf, p, act, use_kernel=use_kernel)
    return combine_tokens(out, slot, gates, keep, gate_dtype=x.dtype)
