"""Unified model: init / param_specs / forward / prefill / decode for all
ten assigned architectures.

Layer stacking: homogeneous layer stacks get a leading (L,) dim and run
under ``jax.lax.scan`` with rematerialisation (compile-time stays flat in
depth; remat bounds activation memory).  Heterogeneous archs decompose
into homogeneous stacks:

* encdec  — encoder stack (bidir) + decoder stack (causal + cross)
* vlm     — groups of (cross_every-1) self layers + 1 cross layer,
            outer scan over groups, inner scan over self layers
* mla_moe — the leading dense layers, then the expert layers
* others  — one stack

The dry-run never materialises params: ``param_shapes()`` returns a
ShapeDtypeStruct pytree consumed by ``jax.jit(...).lower()``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import act_spec, batch_spec, shard, shard_act, shard_logits
from . import blocks as B
from . import layers as L
from . import moe as M


#: families whose caches take a prompt span in one launch (prefill_step)
SPAN_PREFILL_FAMILIES = ("dense", "moe", "mla_moe")


def _dtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def _stack_shapes(shapes: dict, n: int) -> dict:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), shapes)


def _stacked_init(key, cfg, dtype, kind: str, n: int) -> dict:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: B.layer_init(k, cfg, dtype, kind))(keys)


def _plan(cfg: ModelConfig):
    """Stack plan: list of (name, kind, n_layers, nested_inner)."""
    if cfg.family == "dense":
        return [("layers", "dense", cfg.n_layers, 0)]
    if cfg.family == "moe":
        return [("layers", "moe", cfg.n_layers, 0)]
    if cfg.family == "ssm":
        return [("layers", "ssm", cfg.n_layers, 0)]
    if cfg.family == "hybrid":
        return [("layers", "hybrid", cfg.n_layers, 0)]
    if cfg.family == "mla_moe":
        return [("dense_layers", "mla_dense", cfg.first_k_dense, 0),
                ("layers", "mla_moe", cfg.n_layers - cfg.first_k_dense, 0)]
    if cfg.family == "encdec":
        return [("enc_layers", "enc", cfg.enc_layers, 0),
                ("dec_layers", "dec", cfg.n_layers, 0)]
    if cfg.family == "vlm":
        k = cfg.cross_every
        assert cfg.n_layers % k == 0
        g = cfg.n_layers // k
        return [("self_layers", "dense", g, k - 1),  # (g, k-1, ...)
                ("cross_layers", "cross", g, 0)]
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    out = {"embed": jax.ShapeDtypeStruct((cfg.padded_vocab, cfg.d_model), dt),
           "final_norm": L.norm_shapes(cfg.d_model, cfg.norm, dt)}
    if not cfg.tie_embeddings:
        out["lm_head"] = jax.ShapeDtypeStruct((cfg.d_model, cfg.padded_vocab), dt)
    for name, kind, n, inner in _plan(cfg):
        s = B.layer_shapes(cfg, dt, kind)
        s = _stack_shapes(s, inner) if inner else s
        out[name] = _stack_shapes(s, n)
    if cfg.family == "encdec":
        out["enc_norm"] = L.norm_shapes(cfg.d_model, cfg.norm, dt)
    return out


def init_params(cfg: ModelConfig, key) -> dict:
    dt = _dtype(cfg)
    keys = iter(jax.random.split(key, 8))
    out = {
        "embed": (jax.random.normal(next(keys), (cfg.padded_vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "final_norm": L.norm_init(next(keys), cfg.d_model, cfg.norm, dt),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = (jax.random.normal(
            next(keys), (cfg.d_model, cfg.padded_vocab), jnp.float32)
            * cfg.d_model ** -0.5).astype(dt)
    for name, kind, n, inner in _plan(cfg):
        k = next(keys)
        if inner:
            ks = jax.random.split(k, n)
            out[name] = jax.vmap(
                lambda kk: _stacked_init(kk, cfg, dt, kind, inner))(ks)
        else:
            out[name] = _stacked_init(k, cfg, dt, kind, n)
    if cfg.family == "encdec":
        out["enc_norm"] = L.norm_init(next(keys), cfg.d_model, cfg.norm, dt)
    return out


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def _scan_stack(stack_params, x, fn, remat: bool = True):
    body = fn
    if remat:
        body = jax.checkpoint(
            fn, policy=jax.checkpoint_policies.nothing_saveable)

    def step(carry, layer_p):
        return body(carry, layer_p), None

    out, _ = jax.lax.scan(step, x, stack_params)
    return out


def forward(params: dict, cfg: ModelConfig, batch: dict, *,
            schedule: str = "masked", q_chunk: int = 1024,
            k_chunk: int = 1024, ssm_chunk: int = 256,
            remat: bool = True, last_only: bool = False) -> jnp.ndarray:
    """Logits for (B, S) tokens (training / prefill).

    ``last_only`` (prefill): slice to the final position BEFORE the
    lm_head matmul — the full (B, S, V) logits tensor is never built
    (minitron prefill_32k: 66 GB → fits; §Perf iteration 2)."""
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard_act(x)

    kw = dict(schedule=schedule, q_chunk=q_chunk, k_chunk=k_chunk,
              ssm_chunk=ssm_chunk)

    ctx = None
    if cfg.family == "encdec":
        enc = batch["enc_frames"].astype(x.dtype)
        enc = shard_act(enc)
        enc = _scan_stack(
            params["enc_layers"], enc,
            lambda h, p: B.layer_apply(p, cfg, h, "enc", causal=False, **kw),
            remat=remat)
        ctx = L.norm_apply(params["enc_norm"], enc, cfg.norm)
    if cfg.family == "vlm":
        ctx = shard_act(batch["vis_embed"].astype(x.dtype))

    if cfg.family == "vlm":
        k = cfg.cross_every

        def group(h, gp):
            h = _scan_stack(
                gp["self"], h,
                lambda hh, p: B.layer_apply(p, cfg, hh, "dense", **kw),
                remat=remat)
            fn = lambda hh, p: B.layer_apply(p, cfg, hh, "cross", ctx=ctx,
                                             **kw)
            if remat:
                fn = jax.checkpoint(
                    fn, policy=jax.checkpoint_policies.nothing_saveable)
            return fn(h, gp["cross"])

        def gstep(carry, gp):
            return group(carry, gp), None

        x, _ = jax.lax.scan(
            gstep, x,
            {"self": params["self_layers"], "cross": params["cross_layers"]})
    elif cfg.family == "encdec":
        x = _scan_stack(
            params["dec_layers"], x,
            lambda h, p: B.layer_apply(p, cfg, h, "dec", ctx=ctx, **kw),
            remat=remat)
    else:
        for name, kind, _, _ in _plan(cfg):
            x = _scan_stack(
                params[name], x,
                lambda h, p, kind=kind: B.layer_apply(p, cfg, h, kind, **kw),
                remat=remat)

    if last_only:
        x = x[:, -1:]
    x = L.norm_apply(params["final_norm"], x, cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return shard_logits(logits)


def loss_fn(params, cfg, batch, **kw):
    logits = forward(params, cfg, batch, **kw)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab:
        # Mask vocab-padding logits out of the partition function.
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e30, logits)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# Decode path (serve_step)
# ---------------------------------------------------------------------------


#: minor-dim tile of each backend's array layout, in elements (CPU and
#: others: none).  See :func:`cache_shapes`.
_LANE_WIDTH = {"tpu": 128}


def lane_width(device) -> int:
    """The lane width of ``device``'s layouts, for :func:`cache_shapes`."""
    return _LANE_WIDTH.get(device.platform, 1)


def cache_shapes(cfg: ModelConfig, bsz: int, cache_len: int,
                 lane: int) -> dict:
    """The decode cache: each stack's per-layer caches stacked on a
    leading ``(L,)`` dim, plus ``cross_kv`` for encdec/vlm.

    K/V are heads-major, ``(L, bsz, KV, T, h)``: each head's ``(T, h)``
    rows are the minor pair that the attention dots read.  Self-attention
    K/V pad the head dim up to a multiple of ``lane``, the lane width of
    the device that runs the steps (:func:`lane_width`), so the device's
    default layout for the cache keeps ``h`` minor, as the layer body
    writes and reads it, and no launch converts the cache's layout.
    With ``lane=1`` (CPU), or a head dim already a multiple, ``h`` is
    not padded.  ``cross_kv`` is written once and never padded.  The
    lane has no default: a cache compiled for one device and served on
    another would lay out differently from what that device runs.

    The serving steps (``serve.batcher.step_programs``) donate the cache
    they are given and write it in place: a caller keeps the cache each
    launch returns and never reuses the one it passed."""
    dt = _dtype(cfg)
    out = {}
    for name, kind, n, inner in _plan(cfg):
        if kind == "enc":
            continue
        s = B.layer_cache_shapes(cfg, kind, bsz, cache_len, dt, lane)
        s = _stack_shapes(s, inner) if inner else s
        out[name] = _stack_shapes(s, n)
    if cfg.family == "encdec":
        h, KV = cfg.head_dim, cfg.n_kv_heads
        out["cross_kv"] = {
            "k": jax.ShapeDtypeStruct(
                (cfg.n_layers, bsz, KV, cfg.enc_seq, h), dt),
            "v": jax.ShapeDtypeStruct(
                (cfg.n_layers, bsz, KV, cfg.enc_seq, h), dt),
        }
    if cfg.family == "vlm":
        h, KV = cfg.head_dim, cfg.n_kv_heads
        g = cfg.n_layers // cfg.cross_every
        out["cross_kv"] = {
            "k": jax.ShapeDtypeStruct((g, bsz, KV, cfg.vis_seq, h), dt),
            "v": jax.ShapeDtypeStruct((g, bsz, KV, cfg.vis_seq, h), dt),
        }
    return out


def init_cache(cfg: ModelConfig, bsz: int, cache_len: int,
               lane: int) -> dict:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_shapes(cfg, bsz, cache_len, lane))


def _idx(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def _decode_scan(stack_params, cache_stack, x, step_fn, outer: tuple = ()):
    """Scan over layers carrying the FULL stacked cache.

    ``step_fn(x, layer_params, cache_stack, at)`` runs layer ``at`` =
    ``outer + (l,)`` (its leading indices in the stack) and returns
    ``(x, cache_stack, stats)``; the scan returns the same, each of
    ``stats`` stacked over the layers.  Attention K/V are written
    straight into the carried stack at ``[*at, rows, positions]`` and
    read from it there:
    no per-layer slice is built or written back, so a launch that
    donates the cache writes only the positions it produces, in place.
    Recurrent state (ssm/hybrid) is small and keeps a per-layer slice
    and write-back.  A single carried buffer also avoids the xs→ys
    double buffering of multi-GB caches (phi3 decode_32k: 15.5 GB temp
    → §Perf iteration 4)."""
    n = jax.tree.leaves(stack_params)[0].shape[0]

    def body(carry, l):
        x, cache = carry
        x, cache, stats = step_fn(x, _idx(stack_params, l), cache,
                                  outer + (l,))
        return (x, cache), stats

    (x, cache_stack), stats = jax.lax.scan(
        body, (x, cache_stack), jnp.arange(n))
    return x, cache_stack, stats


def _stacks(params, cache, cfg, x, step_fn) -> tuple:
    """Run every stack of the plan in order through :func:`_decode_scan`
    (``step_fn(x, layer_params, cache_stack, at, kind)``).  Returns
    ``(x, new_cache, stats)``: the expert layers' routing counts reduced
    over the layers (``moe.reduce_stats``), or ``{}``."""
    new_cache, stats = dict(cache), {}
    for name, kind, _, _ in _plan(cfg):
        x, new_cache[name], st = _decode_scan(
            params[name], cache[name], x,
            lambda xx, lp, c, at, kind=kind: step_fn(xx, lp, c, at, kind))
        stats.update(st)
    return x, new_cache, M.reduce_stats(stats)


def decode_step(params: dict, cfg: ModelConfig, cache: dict,
                batch: dict, with_stats: bool = False) -> tuple:
    """One token for every sequence in the batch against the cache.

    batch = {"tokens": (B, 1), "cache_index": () or (B,)} — returns
    (logits (B, vocab), new_cache), and with ``with_stats`` also the
    launch's routing counts (``moe.held_experts``, summed over the layers
    with ``expert_max`` the largest; ``{}`` for a family without the
    held-expert layer).  A per-row cache index lets the
    continuous batcher keep each decode slot at its own position.

    Each layer writes one K/V position per row into the stacked cache
    (``[l, row, :, cache_index[row]]``, modulo T for a ring buffer), so a
    caller that donates ``cache`` gets it back updated in place and must
    not reuse the array it passed.
    """
    tokens, cache_index = batch["tokens"], batch["cache_index"]
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard_act(x)
    new_cache, stats = dict(cache), {}

    if cfg.family == "vlm":
        gp_tree = {"self": params["self_layers"],
                   "cross": params["cross_layers"]}
        g = jax.tree.leaves(params["cross_layers"])[0].shape[0]

        def self_step(xx, lp, c, at):
            return B.layer_decode_apply(lp, cfg, xx, c, cache_index,
                                        "dense", at=at)

        def gbody(carry, gi):
            x, self_cache = carry
            gp = _idx(gp_tree, gi)
            x, self_cache, _ = _decode_scan(gp["self"], self_cache, x,
                                            self_step, outer=(gi,))
            x, _, _ = B.layer_decode_apply(
                gp["cross"], cfg, x, {}, cache_index, "cross",
                ctx_kv=_idx(cache["cross_kv"], gi))
            return (x, self_cache), None

        (x, new_self), _ = jax.lax.scan(
            gbody, (x, cache["self_layers"]), jnp.arange(g))
        new_cache["self_layers"] = new_self
    elif cfg.family == "encdec":
        def dec_step(xx, lp, c, at):
            return B.layer_decode_apply(
                lp, cfg, xx, c, cache_index, "dec", at=at,
                ctx_kv=_idx(cache["cross_kv"], at[0]))

        x, new_dec, _ = _decode_scan(params["dec_layers"],
                                     cache["dec_layers"], x, dec_step)
        new_cache["dec_layers"] = new_dec
    else:
        def lyr_step(xx, lp, c, at, kind):
            return B.layer_decode_apply(lp, cfg, xx, c, cache_index, kind,
                                        at=at)

        x, new_cache, stats = _stacks(params, cache, cfg, x, lyr_step)

    x = L.norm_apply(params["final_norm"], x, cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = shard_logits((x @ head)[:, 0])
    return (logits, new_cache, stats) if with_stats else (logits, new_cache)


def prefill_step(params: dict, cfg: ModelConfig, cache: dict,
                 batch: dict, with_stats: bool = False) -> tuple:
    """Write a span of prompt tokens through the model at per-row cache
    indices — the chunked-prefill primitive for the continuous batcher.

    batch = {"tokens": (B, C), "cache_index": (B,), "count": (B,)} —
    row b's ``tokens[b, :count[b]]`` land at cache positions
    ``cache_index[b] .. cache_index[b]+count[b]-1``.  Rows with
    ``count == 0`` are inert: their cache is untouched bit-for-bit
    (padded lanes scatter out of bounds and are dropped), so slots deep
    in decode can share a launch buffer with prefilling neighbours.

    Returns ``(logits (B, vocab), new_cache)`` (and the routing counts
    with ``with_stats``, as :func:`decode_step`) where row b's logits are
    taken at its LAST valid lane (``count[b] - 1``) — the same shape
    contract as :func:`decode_step`, so a slot whose prefill just
    finished can seed decode from these logits.  Rows with ``count == 0``
    return garbage logits that callers must not read.

    Because every chunk runs through the same static ``(B, C)`` buffer
    and each query's attention reduces over the full cache, chunked
    prefill is bitwise identical to whole-prompt prefill (pinned by
    tests/test_prefill.py).

    Like :func:`decode_step`, each layer writes only the span's valid
    positions into the stacked cache, in place when the caller donates
    it (the caller must not reuse the array it passed).

    Only full-cache attention families (:data:`SPAN_PREFILL_FAMILIES`,
    no sliding window) are supported — recurrent and ring-buffer caches
    have no position-indexed span write.
    """
    if cfg.family not in SPAN_PREFILL_FAMILIES:
        raise NotImplementedError(
            f"prefill_step needs a position-indexed KV cache "
            f"{SPAN_PREFILL_FAMILIES}, not family={cfg.family!r}")
    if cfg.sliding_window > 0:
        raise NotImplementedError(
            "prefill_step writes absolute-position spans; ring-buffer "
            "(sliding-window) caches would need modular span writes")
    tokens = batch["tokens"]
    cache_index = jnp.asarray(batch["cache_index"], jnp.int32)
    count = jnp.asarray(batch["count"], jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard_act(x)

    def lyr_step(xx, lp, c, at, kind):
        return B.layer_prefill_apply(lp, cfg, xx, c, cache_index, count,
                                     kind, at=at)

    x, new_cache, stats = _stacks(params, cache, cfg, x, lyr_step)
    x = L.norm_apply(params["final_norm"], x, cfg.norm)
    last = jnp.clip(count - 1, 0, tokens.shape[1] - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = shard_logits((x @ head)[:, 0])
    return (logits, new_cache, stats) if with_stats else (logits, new_cache)
