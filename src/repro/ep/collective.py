"""Token exchange collectives over the ``expert`` mesh axis.

The exchange moves capacity-padded lane buffers between expert shards:
every shard holds a ``(S · lane_capacity, ...)`` buffer whose block
``j`` is its outgoing lane for shard ``j``; after the exchange, block
``i`` of the result is the lane *from* source ``i``.  Ragged per-shard
counts are absorbed by the padding (the :mod:`repro.ep.plan` arithmetic
bounds every lane by ``lane_capacity``), so the collective itself is a
static-shape ``jax.lax.all_to_all`` — or an equivalent ``ppermute``
ring for backends where the fused all-to-all is unavailable.  Both run
inside a ``shard_map`` over the ``expert`` axis (see
:mod:`repro.ep.dispatch`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# distributed.sharding owns both the axis name and "does this mesh
# carve it, how wide" (expert_axis_size: 0 when absent); re-exported
# here so EP callers have one import surface.
from ..distributed.sharding import (  # noqa: F401
    EXPERT_AXIS, expert_axis_size,
)


def has_expert_axis(mesh) -> bool:
    return mesh is not None and EXPERT_AXIS in mesh.axis_names


def exchange(buf: jax.Array, n_shards: int, *,
             axis_name: str = EXPERT_AXIS,
             impl: str = "all_to_all") -> jax.Array:
    """All-to-all the lane blocks of ``buf`` (leading dim ``S·C``).

    Outgoing block ``j`` (rows ``[j·C, (j+1)·C)``) goes to shard ``j``;
    incoming block ``i`` of the result came from source ``i``.  The
    exchange is an involution-shaped transpose: applying it twice
    returns every row home, which is exactly how the combine leg reuses
    it.  Must be called inside a ``shard_map`` over ``axis_name``.

    ``impl="all_to_all"`` — the fused collective (one ICI barrier);
    ``impl="ppermute"`` — an ``S - 1``-step rotation ring that moves
    identical bytes for backends without a fused all-to-all lowering.
    """
    if buf.shape[0] % n_shards != 0:
        raise ValueError(
            f"lane buffer dim {buf.shape[0]} not divisible by "
            f"{n_shards} shards")
    if impl == "all_to_all":
        return jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)
    if impl == "ppermute":
        return _exchange_ppermute(buf, n_shards, axis_name)
    raise ValueError(f"unknown exchange impl {impl!r}; "
                     "choose all_to_all or ppermute")


def _exchange_ppermute(buf: jax.Array, n_shards: int,
                       axis_name: str) -> jax.Array:
    """Rotation-ring all-to-all: at offset ``o`` every shard forwards
    the block addressed to ``(me + o) % S`` one hop of a static
    ``i → i + o`` permutation and files what arrives under its source
    ``(me - o) % S``.  Block 0 of the rotation (``o = 0``) stays home."""
    S = n_shards
    C = buf.shape[0] // S
    me = jax.lax.axis_index(axis_name)
    # o = 0: my own lane to myself stays in place (block index == me).
    own = jax.lax.dynamic_slice_in_dim(buf, me * C, C, axis=0)
    out = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros_like(buf), own, me * C, axis=0)
    for o in range(1, S):
        perm = [(i, (i + o) % S) for i in range(S)]
        block = jax.lax.dynamic_slice_in_dim(
            buf, ((me + o) % S) * C, C, axis=0)
        got = jax.lax.ppermute(block, axis_name, perm)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, got, ((me - o) % S) * C, axis=0)
    return out


def token_shards(T: int, E: int, mesh,
                 axis_name: str = EXPERT_AXIS) -> Optional[int]:
    """How many ways the EP path can shard this call, or ``None`` when
    the mesh has no expert axis or the static shapes don't divide
    (callers fall back to the single-host dispatch rather than
    mis-shard)."""
    S = expert_axis_size(mesh)
    if S <= 1 or T % S != 0 or E % S != 0:
        return None
    return S
