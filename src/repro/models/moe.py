"""Mixture-of-Experts with DLBC-balanced dispatch.

The paper's DLBC policy, mapped onto MoE token routing (DESIGN.md §2.2):

* **LC dispatch** (`moe_dispatch="lc"`) — the static-chunking baseline:
  classic GShard top-k with fixed per-expert capacity
  ``C = ceil(T·top_k/E)·cf``; tokens whose position in their chosen expert
  exceeds C are **dropped** (the residual/identity path carries them).
  This is the "chunking oblivious to actual load" failure mode the paper
  attributes to LC.

* **DLBC dispatch** (`moe_dispatch="dlbc"`) — two-round load balancing:
  round 1 fills the eqChunk-balanced capacity; overflow tokens are
  *re-routed* in round 2 to their next-choice expert against the residual
  capacity — the "re-check for idle workers after serial iterations"
  mechanism in static-shape SPMD form.  Same total buffer, strictly fewer
  dropped tokens (measured in tests/benchmarks).

Admission (who gets a slot, who overflows) is decided by
:class:`repro.sched.capacity.ExpertCapacityProvider` — the shared
DLBC/LC engine's view of per-expert slots; this module no longer owns
any drop arithmetic.  The dispatch/FFN/combine mechanics live next to
the Pallas kernel in :mod:`repro.kernels.moe_dispatch.ops` (einsum on
the XLA path, the grouped-matmul kernel with ``use_kernel=True``).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..kernels.moe_dispatch.ops import dispatch_combine
from ..sched import ExpertCapacityProvider
from . import layers as L
from .layers import _norm_init


def moe_shapes(cfg, dtype) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": jax.ShapeDtypeStruct((d, E), jnp.float32),
        "w1": jax.ShapeDtypeStruct((E, d, f), dtype),
        "w3": jax.ShapeDtypeStruct((E, d, f), dtype),
        "w2": jax.ShapeDtypeStruct((E, f, d), dtype),
    }


def moe_init(key, cfg, dtype) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    k0, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": _norm_init(k0, (d, E), d ** -0.5, jnp.float32),
        "w1": _norm_init(k1, (E, d, f), d ** -0.5, dtype),
        "w3": _norm_init(k3, (E, d, f), d ** -0.5, dtype),
        "w2": _norm_init(k2, (E, f, d), f ** -0.5, dtype),
    }


def capacity(T: int, E: int, top_k: int, cf: float) -> int:
    c = int(math.ceil(T * top_k / E * cf))
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for TPU lane alignment


def _positions_in_expert(expert_ids: jnp.ndarray, E: int,
                         base: jnp.ndarray = None) -> jnp.ndarray:
    """Running slot index of each (token, choice) within its expert.

    expert_ids: (T, K) int32.  Counts in choice-major order (all k=0 first)
    so primary choices win slots — the paper's "current worker gets the
    smallest chunk" priority rule for remainder distribution.
    ``base``: (E,) pre-occupied slots per expert (round 2).
    """
    T, K = expert_ids.shape
    flat = expert_ids.T.reshape(-1)  # choice-major (K*T,)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)  # (K*T, E)
    pos = jnp.cumsum(onehot, axis=0) - 1  # position among same-expert slots
    pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    if base is not None:
        pos = pos + base[flat]
    return pos.reshape(K, T).T  # (T, K)


def _expert_load(expert_ids: jnp.ndarray, mask: jnp.ndarray, E: int):
    flat = expert_ids.reshape(-1)
    return jnp.sum(
        jax.nn.one_hot(flat, E, dtype=jnp.int32)
        * mask.reshape(-1)[:, None], axis=0)


def dlbc_reroute(ids, gates, probs, pos1, keep1, load, provider,
                 n_groups: int, expert_open, group_of=None):
    """The DLBC round-2 re-route, shared by single-host dispatch (a
    "group" is an expert) and EP lane planning (a group is an expert
    *shard* — :mod:`repro.ep.dispatch`, where any change to this idiom
    must keep the EP ↔ single-host equivalence tests green).

    Overflow (token, choice) pairs re-route once to the token's best
    expert among ``expert_open`` (the (E,) availability mask derived
    from the provider's residual), take positions after the ``load``
    already admitted per group, and are re-admitted against the same
    provider.  Returns ``(ids_f, group_f, pos_f, keep, gates_f,
    overflow)`` — rerouted pairs weighted by the probability of the
    expert that actually serves them (router-consistent combine).
    """
    group_of = group_of or (lambda i: i)
    overflow = ~keep1                                  # (T, K)
    avail = probs * expert_open[None, :]
    alt_ids = jnp.argmax(avail, axis=-1).astype(jnp.int32)  # (T,)
    ids2 = jnp.where(overflow, alt_ids[:, None], ids)
    group2 = group_of(ids2)
    pos2 = _positions_in_expert(
        jnp.where(overflow, group2, n_groups),  # only overflow counts
        n_groups + 1,
        base=jnp.concatenate([load, jnp.zeros((1,), load.dtype)]))
    ids_f = jnp.where(overflow, ids2, ids)
    group_f = jnp.where(overflow, group2, group_of(ids))
    pos_f = jnp.where(overflow, pos2, pos1)
    keep = provider.admit_mask(pos_f)
    alt_gate = jnp.take_along_axis(probs, ids_f.astype(jnp.int32),
                                   axis=-1).astype(gates.dtype)
    gates_f = jnp.where(overflow, alt_gate, gates)
    return ids_f, group_f, pos_f, keep, gates_f, overflow


def route(x: jnp.ndarray, router_w: jnp.ndarray, top_k: int):
    """x: (T, d) → (gates (T,K) fp32, expert_ids (T,K) int32, full probs)."""
    logits = x.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, ids.astype(jnp.int32), probs


def moe_apply(p: dict, cfg, x: jnp.ndarray,
              return_stats: bool = False, use_kernel: bool = False):
    """x: (B, S, d) or (T, d).  Dispatch per cfg.moe_dispatch."""
    # NOTE (refuted hypothesis — EXPERIMENTS.md §Perf iteration 7):
    # constraining the flattened token dim to (data × model) sharding was
    # expected to shrink dispatch buffers 16×; measured: GSPMD reshards
    # the slot scatter/gather with MORE collectives (mixtral train_4k
    # collective term 62 s → 158 s).  The principled fix is the
    # expert-parallel all-to-all dispatch below (repro.ep): explicit
    # token exchange between expert shards instead of letting the
    # partitioner guess.
    orig_shape = x.shape
    if x.ndim == 3:
        x = x.reshape(-1, x.shape[-1])
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    if cfg.expert_parallel:
        # Expert-parallel all-to-all dispatch (repro.ep): taken when the
        # mesh carves an "expert" axis whose size divides E (the same
        # static predicate that shards expert weights E → "expert", so
        # the single-host gather never runs over expert-sharded weights).
        # A token count not divisible by S — ragged last serving batch —
        # is zero-padded up to the next multiple and sliced back: at
        # most S-1 pad tokens ride the round, a negligible capacity
        # perturbation vs falling back to the resharded gather.
        from ..distributed.sharding import current_mesh, expert_axis_size
        mesh = current_mesh()
        S = expert_axis_size(mesh)
        if S > 1 and E % S == 0:
            from ..ep.dispatch import ep_dispatch_combine
            pad = (-T) % S
            xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
            y, ep_stats = ep_dispatch_combine(
                p, cfg, xp, mesh=mesh, use_kernel=use_kernel,
                return_stats=True)
            y = (y[:T] if pad else y).reshape(orig_shape)
            if return_stats:
                ep_stats["padded_tokens"] = pad
                return y, ep_stats
            return y
    C = capacity(T, E, K, cfg.moe_capacity_factor)
    cap = ExpertCapacityProvider(E, C)
    gates, ids, probs = route(x, p["router"], K)
    rounds = 1

    if cfg.moe_dispatch == "lc":
        # Static chunking: one admission round against fixed capacity;
        # overflow is dropped (the residual path carries those tokens).
        pos = _positions_in_expert(ids, E)
        keep = cap.admit_mask(pos)
        y = dispatch_combine(x, gates, ids, pos, keep, E, C, p, cfg.act,
                             use_kernel=use_kernel)
        dropped = jnp.sum(~keep)
    else:
        # --- DLBC round 1: eqChunk-balanced primary dispatch -------------
        pos1 = _positions_in_expert(ids, E)
        keep1 = cap.admit_mask(pos1)
        # --- round 2: overflow re-routed to the next-best expert --------
        # (the serial block's "re-check for idle workers": tokens that
        # found their expert full try the least-loaded alternative).
        rounds = 2
        load = _expert_load(ids, keep1, E)          # (E,) used slots
        resid = cap.residual(load)                  # idle capacity
        ids_final, _, pos_final, keep, gates_final, _ = dlbc_reroute(
            ids, gates, probs, pos1, keep1, load, cap, E,
            expert_open=resid > 0)
        y = dispatch_combine(x, gates_final, ids_final, pos_final, keep, E,
                             C, p, cfg.act, use_kernel=use_kernel)
        dropped = jnp.sum(~keep)

    y = y.reshape(orig_shape)
    if return_stats:
        frac = dropped / (T * K)
        # SchedTelemetry vocabulary for the host side: an admitted
        # (token, choice) pair is a spawn; the single gate-combine is the
        # join regardless of how many admission rounds ran.
        return y, {"dropped_frac": frac, "spawns": jnp.sum(keep),
                   "joins": 1, "rounds": rounds,
                   "total_slots": cap.total()}
    return y


def moe_ref(p: dict, cfg, x: jnp.ndarray) -> jnp.ndarray:
    """Dense oracle: every token through its top-k experts, no capacity.
    The no-drop ground truth that dispatch quality is measured against."""
    orig_shape = x.shape
    if x.ndim == 3:
        x = x.reshape(-1, x.shape[-1])
    gates, ids, _ = route(x, p["router"], cfg.top_k)
    T, d = x.shape
    outs = []
    for e in range(cfg.n_experts):
        if cfg.act == "swiglu":
            h = jax.nn.silu(x @ p["w1"][e]) * (x @ p["w3"][e])
        else:
            h = jax.nn.gelu(x @ p["w1"][e])
        outs.append(h @ p["w2"][e])
    dense = jnp.stack(outs, axis=1)  # (T, E, d)
    sel = jnp.take_along_axis(dense, ids[..., None], axis=1)  # (T, K, d)
    return jnp.einsum("tkd,tk->td", sel, gates.astype(x.dtype)).reshape(
        orig_shape)


# ---------------------------------------------------------------------------
# The expert layer that holds a share of the experts (mla_moe), dropless
# ---------------------------------------------------------------------------
#
# The router scores all ``n_experts``; this layer holds
# ``experts_held`` of them (``expert_first ..``) and computes exactly
# the (token, choice) pairs routed to those, with nothing dropped or
# rerouted: the pairs are sorted by expert and each held expert's rows
# go through one grouped product (``jax.lax.ragged_dot``).  Pairs routed
# to experts held elsewhere are left out; their part of the layer is
# another chip's.  The shared experts are computed for every token.
# The capacity dispatch above (LC/DLBC) stays the training path of the
# ``moe`` family.


def held_moe_shapes(cfg, dtype) -> dict:
    d, f, E, Eh = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.experts_held
    return {
        "router": {"w": jax.ShapeDtypeStruct((d, E), jnp.float32),
                   "bias": jax.ShapeDtypeStruct((E,), jnp.float32)},
        "w1": jax.ShapeDtypeStruct((Eh, d, f), dtype),
        "w3": jax.ShapeDtypeStruct((Eh, d, f), dtype),
        "w2": jax.ShapeDtypeStruct((Eh, f, d), dtype),
        "shared": L.mlp_shapes(d, cfg.n_shared_experts * f, "swiglu", dtype),
    }


def held_moe_init(key, cfg, dtype) -> dict:
    d, f, E, Eh = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.experts_held
    k0, k1, k2, k3, k4 = jax.random.split(key, 5)
    return {
        "router": {"w": _norm_init(k0, (d, E), d ** -0.5, jnp.float32),
                   "bias": jnp.zeros((E,), jnp.float32)},
        "w1": _norm_init(k1, (Eh, d, f), d ** -0.5, dtype),
        "w3": _norm_init(k3, (Eh, d, f), d ** -0.5, dtype),
        "w2": _norm_init(k2, (Eh, f, d), f ** -0.5, dtype),
        "shared": L.mlp_init(k4, d, cfg.n_shared_experts * f, "swiglu",
                             dtype),
    }


def route_sigmoid(x: jnp.ndarray, router: dict, top_k: int, scale: float):
    """DeepSeek-V3's router over every expert: sigmoid scores (float32 at
    full precision), the top ``top_k`` by score plus the correction
    ``bias``, their scores normalised to sum 1 and times ``scale``.
    x: (T, d) → (gates (T, K) float32, expert ids (T, K) int32)."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router["w"],
        precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(scores + router["bias"], top_k)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20) * scale
    return gates, ids.astype(jnp.int32)


def held_experts(p: dict, cfg, x: jnp.ndarray, gates: jnp.ndarray,
                 ids: jnp.ndarray):
    """The held experts' part of the layer for tokens ``x`` (T, d) routed
    to ``ids`` with ``gates``: every pair on a held expert, none else.
    Returns ``(y (T, d) float32, stats)``, ``stats`` the layer's counts:
    ``routed_here``, the pairs on held experts; ``experts_hit``, the held
    experts with at least one; ``expert_max``, the most pairs on one.
    The layer is dropless by construction: the group sizes count every
    pair on a held expert, so each is a row of the grouped product."""
    T, K = ids.shape
    Eh = cfg.experts_held
    local = ids - cfg.expert_first
    here = (local >= 0) & (local < Eh)
    group = jnp.where(here, local, Eh).reshape(-1)      # absent: group Eh
    order = jnp.argsort(group, stable=True)
    held = jnp.bincount(group, length=Eh + 1)[:Eh]
    rows = jnp.take(x, order // K, axis=0)              # (T*K, d) by expert

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, held,
                                  preferred_element_type=jnp.float32)

    h = jax.nn.silu(grouped(rows, p["w1"])) * grouped(rows, p["w3"])
    y = grouped(h.astype(x.dtype), p["w2"])
    # back to (token, choice) order; rows past the held groups are not
    # written by the grouped product and are masked, not read
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(T, K, -1)
    y = jnp.where(here[..., None], y, 0.0)
    out = jnp.einsum("tkd,tk->td", y, jnp.where(here, gates, 0.0))
    stats = {"routed_here": jnp.sum(held),
             "experts_hit": jnp.sum(held > 0),
             "expert_max": jnp.max(held)}
    return out, stats


def held_moe_apply(p: dict, cfg, x: jnp.ndarray):
    """The expert layer of ``mla_moe``: shared experts plus the held
    experts' part.  x: (B, S, d) → (y (B, S, d), stats)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    with jax.named_scope("moe_router"):
        gates, ids = route_sigmoid(x, p["router"], cfg.top_k,
                                   cfg.routed_scale)
    with jax.named_scope("moe_experts"):
        y, stats = held_experts(p, cfg, x, gates, ids)
        y = y + L.mlp_apply(p["shared"], x, "swiglu").astype(jnp.float32)
    return y.astype(x.dtype).reshape(shape), stats


def reduce_stats(stats: dict) -> dict:
    """Per-layer counts (leading layer axis) to one launch's: sums, and
    the largest ``expert_max``."""
    return {k: (jnp.max(v) if k == "expert_max" else jnp.sum(v))
            for k, v in stats.items()}
