"""``ep_dispatch_combine`` — the expert-parallel dispatch round.

One round, four legs, ONE join (shard-locally: route → pack; globally:
all-to-all → expert FFN → all-to-all back → combine):

1. **Shard-local route** — each expert shard top-k routes its own slice
   of the tokens against the replicated router, then runs the DLBC lane
   admission *in traced form*: over-capacity residuals reassigned to an
   expert on a shard with idle lane capacity **before** the collective
   (the single-probe round-2 re-route of ``models.moe`` lifted from
   experts to expert shards; the host-side
   :func:`repro.ep.plan.plan_exchange` re-probes to exhaustion, so its
   drop count lower-bounds this round's).
2. **Dispatch all-to-all** — capacity-padded lane buffers exchanged
   over the ``expert`` mesh axis (:func:`repro.ep.collective.exchange`).
3. **Per-shard expert FFN** — received pairs admitted into the local
   ``(E/S, C, d)`` capacity buffers (the same
   :class:`~repro.sched.capacity.ExpertCapacityProvider` arithmetic as
   the single-host path) and pushed through ``expert_ffn``.
4. **Combine all-to-all** — expert outputs retrace the exchange home
   and gate-combine in token order.

AFE is the synchronization story: the whole round is one bulk step with
a single logical barrier.  No per-expert or per-shard joins exist to
eliminate — the host wrapper :func:`ep_round` runs each round under a
DCAFE :class:`~repro.sched.executors.FinishScope`, so telemetry shows
exactly ``joins == rounds`` (gated in CI from the ``bench_ep``
artifact).

Numerics: with ample capacity the result equals the single-host
``dispatch_combine`` up to token order (asserted in
``tests/test_ep.py``); under pressure the DLBC plan strictly dominates
per-shard dropping (overflow is reassigned, not dropped).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..kernels.moe_dispatch.ops import (
    combine_tokens, dispatch_tokens, expert_ffn,
)
from ..obs import metrics as obs_metrics
from ..obs import monitor as obs_monitor
from ..obs import trace as obs
from ..models.moe import (
    _expert_load, _positions_in_expert, capacity, dlbc_reroute, route,
)
from ..sched import ExpertCapacityProvider, SchedTelemetry
from ..sched import faults
from ..sched.executors import FinishScope
from ..sched.faults import ShardLossError
from .collective import EXPERT_AXIS, exchange, token_shards
from .plan import lane_capacity


def _ep_shard(x, router, w1, w3, w2, *, E: int, S: int, K: int,
              C_lane: int, C_local: int, act: str, use_kernel: bool,
              impl: str, reassign: bool, dead_shards: tuple = ()):
    """One expert shard's slice of the dispatch round (under shard_map).

    Returns ``(y_local, stats_row)`` where ``stats_row`` is the shard's
    ``[sent, received, reassigned, admitted]`` counts — summed over the
    expert axis by the caller.
    """
    Tl, d = x.shape
    E_local = E // S
    lane_cap = ExpertCapacityProvider(n_experts=S, slots_per_expert=C_lane)
    local_cap = ExpertCapacityProvider(n_experts=E_local,
                                       slots_per_expert=C_local)

    # --- leg 1: shard-local route + DLBC lane plan ----------------------
    gates, ids, probs = route(x, router, K)          # (Tl, K)
    dest = ids // E_local                            # destination shard
    pos = _positions_in_expert(dest, S)              # rank in my lane
    # Graceful degradation: a dead shard's lanes are CLOSED at the
    # admission mask, so no pair is ever packed toward it — under
    # ``reassign`` the re-route below moves those pairs onto live
    # shards with lane residual BEFORE the collective (dlbc_reroute,
    # the same round-2 machinery), under LC they drop like any
    # overflow.  ``dead_shards`` is static (a traced attempt per dead
    # set), so XLA sees a constant mask.
    alive_v = jnp.asarray([s not in dead_shards for s in range(S)])
    keep1 = lane_cap.admit_mask(pos) & alive_v[dest]
    # Overflow reassignment, single-probe (static shapes): a pair whose
    # lane is full re-routes ONCE to its best expert on a shard whose
    # lane still has residual rows — reassigned before the collective,
    # so the receiving shard never sees (and never drops) the overflow.
    # Unlike the host-side plan_exchange loop this does not re-probe, so
    # pairs whose probe lands on a lane that fills up are dropped even
    # if another lane still has room (the same trade the single-host
    # DLBC round 2 makes).  The re-route itself IS the single-host
    # round 2 with expert shards as the groups (dlbc_reroute).
    if reassign:
        lane_load = _expert_load(dest, keep1, S)     # (S,) kept per lane
        resid = lane_cap.residual(lane_load)
        ids_f, dest_f, pos_f, keep, gates_f, overflow = dlbc_reroute(
            ids, gates, probs, pos, keep1, lane_load, lane_cap, S,
            expert_open=jnp.repeat((resid > 0) & alive_v, E_local),
            group_of=lambda i: i // E_local)
    else:
        # LC lane semantics (moe_dispatch="lc"): static single-round
        # admission, overflow dropped — the per-shard baseline the DLBC
        # plan is measured against.  overflow == ~keep makes the
        # reassigned stat (overflow & keep) identically zero.
        ids_f, dest_f, pos_f, keep, gates_f = ids, dest, pos, keep1, gates
        overflow = ~keep1

    # --- pack lanes + dispatch all-to-all -------------------------------
    slot = dest_f * C_lane + jnp.minimum(pos_f, C_lane - 1)  # (Tl, K)
    keepf = keep.astype(x.dtype)
    # The local expert id rides the exchange as payload column d,
    # encoded +1 so an untouched row reads 0 ("empty"): kept slots are
    # unique so scatter-add fills them exactly once, dropped pairs add
    # zero, and the dispatch leg stays ONE all-to-all.  Exact in every
    # payload dtype (ep_dispatch_combine bounds E_local + 1 by the
    # mantissa for sub-f32 dtypes).
    meta = (ids_f % E_local + 1).astype(x.dtype) * keepf     # (Tl, K)
    payload = jnp.concatenate(
        [x[:, None, :] * keepf[..., None], meta[..., None]], axis=-1)
    sendx = jnp.zeros((S * C_lane, d + 1), x.dtype).at[
        slot.reshape(-1)].add(payload.reshape(Tl * K, d + 1))
    recv = exchange(sendx, S, impl=impl)
    recvx = recv[:, :d]
    recv_eid = recv[:, d].astype(jnp.int32) - 1      # -1 = empty row

    # --- leg 3: local admission + expert FFN ----------------------------
    valid = recv_eid >= 0
    rids = jnp.maximum(recv_eid, 0)
    rpos = _positions_in_expert(
        jnp.where(valid, recv_eid, E_local)[:, None], E_local + 1)[:, 0]
    keep_loc = (valid & local_cap.admit_mask(rpos))[:, None]
    buf, slot_loc = dispatch_tokens(recvx, keep_loc, rids[:, None],
                                    rpos[:, None], E_local, C_local)
    out = expert_ffn(buf, {"w1": w1, "w3": w3, "w2": w2}, act,
                     use_kernel=use_kernel)
    ones = jnp.ones(keep_loc.shape, recvx.dtype)
    y_recv = combine_tokens(out, slot_loc, ones, keep_loc)   # (S·C_lane, d)

    # --- leg 4: combine all-to-all + gate-combine -----------------------
    # The exchange is its own inverse on lane layout: block i of y_recv
    # holds results for source i's lane, so one more exchange files each
    # shard's own lane results back under the slots it packed them from.
    backx = exchange(y_recv, S, impl=impl)
    gathered = backx[slot.reshape(-1)].reshape(Tl, K, d)
    w = (gates_f * keep).astype(x.dtype)
    y = jnp.einsum("tkd,tk->td", gathered, w)

    stats_row = jnp.stack([
        jnp.sum(keep), jnp.sum(valid), jnp.sum(overflow & keep),
        jnp.sum(keep_loc),
    ]).astype(jnp.int32)[None, :]
    return y, stats_row


def ep_dispatch_combine(p: dict, cfg, x, *, mesh, use_kernel: bool = False,
                        impl: str = "all_to_all",
                        return_stats: bool = False,
                        dead_shards: tuple = ()):
    """Expert-parallel dispatch → FFN → combine over the ``expert`` axis.

    ``x`` is the flattened ``(T, d)`` token matrix; the shard_map
    reshards it ``T``-major onto the expert axis, so callers need no
    special input placement.  Requires ``T % S == 0 and E % S == 0``
    (checked — callers use :func:`repro.ep.collective.token_shards` to
    fall back to the single-host path otherwise).

    ``dead_shards`` runs the round DEGRADED: the listed shards' lanes
    are closed at admission, so their traffic re-routes to live shards
    (DLBC) or drops (LC) before the collective — see
    :func:`ep_round` for the retry loop that discovers the dead set.
    """
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    S = token_shards(T, E, mesh)
    if S is None:
        raise ValueError(
            f"EP dispatch needs an expert axis dividing T={T} and "
            f"E={E}; mesh axes {getattr(mesh, 'axis_names', None)}")
    dead_shards = tuple(sorted({int(s) for s in dead_shards}))
    if dead_shards:
        bad = [s for s in dead_shards if not 0 <= s < S]
        if bad:
            raise ValueError(f"dead_shards {bad} outside [0, {S})")
        if len(dead_shards) >= S:
            raise ValueError(
                f"all {S} shards dead — nothing left to degrade onto")
    C_lane = lane_capacity(T // S, K, S, cfg.moe_capacity_factor)
    # Per-expert capacity matches the single-host formula on the GLOBAL
    # token count, so admission (and numerics) line up shard-for-shard.
    C_local = capacity(T, E, K, cfg.moe_capacity_factor)
    if jnp.issubdtype(x.dtype, jnp.inexact):
        # the expert-id metadata rides as a payload column, +1-encoded:
        # it must be exactly representable in the payload dtype
        max_exact = 2 ** (jnp.finfo(x.dtype).nmant + 1)
        if E // S + 1 > max_exact:
            raise ValueError(
                f"E/S + 1 = {E // S + 1} local expert ids do not fit "
                f"exactly in {x.dtype} (max {max_exact}); cast tokens "
                "to a wider dtype for EP dispatch")
    fn = partial(_ep_shard, E=E, S=S, K=K, C_lane=C_lane, C_local=C_local,
                 act=cfg.act, use_kernel=use_kernel, impl=impl,
                 # "lc" keeps its static single-round semantics on the EP
                 # substrate too (no reassignment) so the LC-vs-DLBC
                 # comparison stays meaningful shard-side
                 reassign=cfg.moe_dispatch != "lc",
                 dead_shards=dead_shards)
    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(P(EXPERT_AXIS, None), P(None, None),
                  P(EXPERT_AXIS, None, None), P(EXPERT_AXIS, None, None),
                  P(EXPERT_AXIS, None, None)),
        out_specs=(P(EXPERT_AXIS, None), P(EXPERT_AXIS, None)),
        check_vma=False)
    y, stats_rows = mapped(x, p["router"].astype(jnp.float32),
                           p["w1"], p["w3"], p["w2"])
    if not return_stats:
        return y
    totals = jnp.sum(stats_rows, axis=0)             # (4,)
    sent, received, reassigned, admitted = (totals[0], totals[1],
                                            totals[2], totals[3])
    total_pairs = T * K
    stats = {
        # the shared moe_apply vocabulary (spawns + dropped == T·K):
        "dropped_frac": (total_pairs - admitted) / total_pairs,
        "spawns": admitted,
        "joins": 1,              # ONE barrier for the whole round (AFE)
        "rounds": 1,
        "total_slots": S * (E // S) * C_local,
        # the exchange vocabulary (SchedTelemetry.exchange):
        "sent": sent,
        "received": received,
        "reassigned": reassigned,
        "dropped": total_pairs - admitted,
        "n_shards": S,
        "lane_capacity": C_lane,
    }
    return y, stats


def ep_round(p: dict, cfg, x, *, mesh,
             telemetry: Optional[SchedTelemetry] = None,
             use_kernel: bool = False, impl: str = "all_to_all"):
    """One dispatch round under a DCAFE :class:`FinishScope`.

    The host-side entry for serving/benchmarks: runs the round, blocks
    on the result (the scope exit IS the round's single barrier), and
    folds the exchange counts into ``telemetry`` — ``spawns`` advance by
    the admitted pairs, ``joins`` by exactly one, and
    ``telemetry.exchange`` by the sent/received/reassigned/dropped
    counts.  Returns ``(y, stats)`` with host-int stats.

    Shard loss degrades, it does not abort: a
    :class:`~repro.sched.faults.ShardLossError` (raised by the
    fault-injection hook before the round posts, or by a caller-side
    health check) adds the shard to the round's dead set, bumps the
    retry telemetry, and re-attempts with that shard's lanes closed —
    the traffic re-routes to live shards via the existing
    ``dlbc_reroute`` before the collective.  A degraded round that
    completes counts ``exchange.degraded_rounds`` (and the stats carry
    ``degraded``/``dead_shards``); losing the LAST live shard, or the
    same shard twice, re-raises.  The loss check runs before ``posted``
    is counted, so posted == completed holds under degradation.
    """
    telemetry = telemetry if telemetry is not None else SchedTelemetry()
    plan = faults.active()
    dead: set = set()
    S = token_shards(x.shape[0], cfg.n_experts, mesh)
    # obs round edges (cat="ep"): ``round_posted`` when the round's
    # collectives are launched, ``round_completed`` when its single
    # barrier lands — the same two edges ``ExchangeCounters.posted`` /
    # ``completed`` count, so the trace↔telemetry cross-check covers
    # them.  Today the round blocks before returning (posted ==
    # completed at quiescence); the double-buffered overlap (ROADMAP)
    # will separate the edges without touching this vocabulary.
    # The in-jit legs (dispatch a2a → expert FFN → combine a2a) are one
    # XLA computation and not separately host-visible — the host phases
    # are launch (trace+compile+enqueue) and barrier (device work).
    while True:
        try:
            if plan is not None:
                shard = plan.lost_shard("ep.round")
                if shard is not None:
                    raise ShardLossError(shard)
            with obs.trace_span("ep", "round"):
                with FinishScope(telemetry):
                    obs.instant("ep", "round_posted")
                    telemetry.record_exchange(posted=1)
                    with obs.trace_span("ep", "launch"):
                        y, stats = ep_dispatch_combine(
                            p, cfg, x, mesh=mesh, use_kernel=use_kernel,
                            impl=impl, return_stats=True,
                            dead_shards=tuple(sorted(dead)))
                    with obs.trace_span("ep", "barrier"):
                        y = jax.block_until_ready(y)
                    stats = {k: (float(v) if k == "dropped_frac"
                                 else int(v))
                             for k, v in stats.items()}
            break
        except ShardLossError as e:
            sh = int(getattr(e, "shard", -1))
            if sh in dead or (S is not None and len(dead) + 1 >= S):
                raise  # same shard twice, or no live shard left
            dead.add(sh)
            telemetry.record_retry("ep.round")
            obs.instant("sched", "retry", args={"site": "ep.round"})
    obs.instant("ep", "round_completed")
    with telemetry.lock:
        telemetry.spawns += stats["spawns"]
    obs.instant("sched", "spawn", n=stats["spawns"])
    telemetry.record_exchange(
        sent=stats["sent"], received=stats["received"],
        reassigned=stats["reassigned"], dropped=stats["dropped"],
        completed=1, degraded=1 if dead else 0)
    obs_metrics.counter("ep.rounds").inc()
    # scalar stats only (benches/tests cast every value): degraded is a
    # 0/1 flag, dead_shards the count of lanes closed this round
    stats["degraded"] = int(bool(dead))
    stats["dead_shards"] = len(dead)
    if dead:
        obs_metrics.counter("ep.degraded_rounds").inc()
        # flight-recorder trigger: the round COMPLETED, but it ran with
        # lanes closed — dump the window while the evidence is fresh
        obs_monitor.on_ep_degraded(dead)
    return y, stats
