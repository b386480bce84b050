"""Smoke run of the serving path on a TPU chip.

    python chip_smoke.py [--seed N]      # one chip: phi3-mini-3.8b serving
    python chip_smoke.py --chips 4       # four chips: expert-parallel MoE

With no options it serves phi3-mini-3.8b at full width (random weights
from ``--seed``) through ``ContinuousBatcher``: 8 requests whose prompts
span several prefill chunks.  It then checks chunked ``prefill_step`` plus
one ``decode_step`` against ``forward`` on one prompt.  ``--chips 4`` runs
only granite-moe-1b-a400m's MoE layer with expert-parallel dispatch over
four chips against the same layer on one chip.

Each phase prints its compile and wall seconds, tokens generated, peak
device bytes and the compile-cache directory.  Any failure exits
non-zero.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A machine without a TPU fails before any model work.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.distributed.sharding import (  # noqa: E402
    mesh_context, named_shardings,
)
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.launch.serve import build_batcher  # noqa: E402
from repro.models import model as MDL  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.serve.batcher import Request  # noqa: E402

SERVE_ARCH = "phi3-mini-3.8b"
N_SLOTS, CACHE_LEN = 4, 1024
N_REQUESTS = 8
PROMPT_LEN = (17, 700)
MAX_NEW = (16, 32)
CHECK_PROMPT_LEN = 300
#: prefill+decode vs forward, max |logit difference| over the real vocab,
#: as a fraction of the largest |logit| of the forward pass.  Both paths
#: run in bf16 and round their activations at different points (a
#: chunked span and a masked full-cache read against one causal pass),
#: so they agree to a few bf16 ulps (2^-8 relative) of the logit scale,
#: compounded over 32 layers; 1/16 of the scale is that with margin,
#: and still fails a wrong cache position, mask or chunk boundary,
#: which moves logits by the whole scale.
CHECK_REL_BOUND = 1 / 16

MOE_ARCH = "granite-moe-1b-a400m"
MOE_TOKENS = 8192
#: expert-parallel vs one-chip MoE output, max |difference| as a fraction
#: of the largest |output|: the same bf16 products summed per shard in
#: another order, i.e. a few bf16 ulps.
MOE_REL_BOUND = 1 / 64


class _CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def _tpu_devices() -> list:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU: JAX found platform {dev.platform!r} "
            f"({dev.device_kind}, {len(devices)} devices)")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    return devices


def _peak_bytes(devices) -> int:
    return max(int(d.memory_stats().get("peak_bytes_in_use", 0))
               for d in devices)


def _phase(name, fn, clock, devices, cache_dir):
    """Run one phase and print its line; ``fn`` returns (tokens, extra)."""
    c0, t0 = clock.seconds, time.perf_counter()
    tokens, extra = fn()
    row = {"phase": name,
           "compile_s": round(clock.seconds - c0, 3),
           "wall_s": round(time.perf_counter() - t0, 3),
           "tokens_generated": tokens,
           "peak_bytes_in_use": _peak_bytes(devices),
           "compile_cache": cache_dir, **extra}
    print(json.dumps(row), flush=True)
    return extra


def make_requests(cfg, seed: int) -> list:
    """``N_REQUESTS`` prompts with lengths spread over ``PROMPT_LEN``."""
    rng = np.random.default_rng(seed)
    lens = np.linspace(*PROMPT_LEN, N_REQUESTS).astype(int)
    rng.shuffle(lens)
    return [Request(rid=i,
                    prompt=[int(t) for t in rng.integers(0, cfg.vocab, n)],
                    max_new=int(rng.integers(MAX_NEW[0], MAX_NEW[1] + 1)))
            for i, n in enumerate(lens)]


def serve(batcher, requests) -> tuple:
    """Serve ``requests``; every one must finish whole with in-vocab ids."""
    stats = batcher.run(requests)
    vocab = batcher.cfg.vocab
    generated = [r.tokens[len(r.prompt):] for r in requests]
    unfinished = [r.rid for r in requests if r.done_step is None]
    if unfinished or stats.failed or stats.truncated:
        raise RuntimeError(
            f"serving: unfinished={unfinished} failed={stats.failed} "
            f"truncated={stats.truncated}")
    short = [r.rid for r, g in zip(requests, generated) if len(g) < r.max_new]
    bad = [t for g in generated for t in g if not 0 <= t < vocab]
    if short or bad:
        raise RuntimeError(f"serving: short outputs {short}, "
                           f"out-of-vocab ids {bad[:8]}")
    return sum(len(g) for g in generated), {
        "requests": len(requests), "finished": len(requests) - len(unfinished),
        "failed": stats.failed, "truncated": stats.truncated,
        "steps": stats.steps,
        "prompt_lens": [len(r.prompt) for r in requests]}


def check_prefill_decode(batcher, seed: int,
                         prompt_len: int = CHECK_PROMPT_LEN) -> tuple:
    """Chunked ``prefill_step`` over a prompt's prefix, one
    ``decode_step`` on its last token, against ``forward`` on the whole
    prompt: the logits at the last prompt position must agree."""
    cfg, params = batcher.cfg, batcher.params
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
    n, width = batcher.n_slots, batcher.prefill_chunk
    cache = jax.tree.map(jnp.zeros_like, batcher.cache)
    for pos in range(0, prompt_len - 1, width):
        span = prompt[pos:min(pos + width, prompt_len - 1)]
        tokens = np.zeros((n, width), np.int32)
        tokens[0, :len(span)] = span
        _, cache, _ = batcher.prefill_fn(params, cache, {
            "tokens": jnp.asarray(tokens),
            "cache_index": jnp.asarray([pos] + [0] * (n - 1), jnp.int32),
            "count": jnp.asarray([len(span)] + [0] * (n - 1), jnp.int32)})
    tokens = np.zeros((n, 1), np.int32)
    tokens[0, 0] = prompt[-1]
    logits, _, _ = batcher.decode_fn(params, cache, {
        "tokens": jnp.asarray(tokens),
        "cache_index": jnp.asarray([prompt_len - 1] + [0] * (n - 1),
                                   jnp.int32)})
    ref = jax.jit(lambda p, t: MDL.forward(p, cfg, {"tokens": t},
                                           last_only=True))(
        params, jnp.asarray(prompt[None]))
    got = np.asarray(logits[0, :cfg.vocab], np.float32)
    want = np.asarray(ref[0, 0, :cfg.vocab], np.float32)
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(want))):
        raise RuntimeError("prefill/decode check: non-finite logits")
    delta = float(np.max(np.abs(got - want)))
    bound = CHECK_REL_BOUND * float(np.max(np.abs(want)))
    print(f"prefill+decode vs forward: max|delta|={delta} bound={bound} "
          f"(prompt {prompt_len} tokens, chunks of {width})", flush=True)
    if not delta <= bound:
        raise RuntimeError(f"prefill/decode check: max|delta| {delta} "
                           f"exceeds {bound}")
    return 1, {"max_abs_delta": delta, "bound": bound,
               "argmax_equal": bool(np.argmax(got) == np.argmax(want))}


def check_expert_parallel(cfg, seed: int, n_tokens: int, devices) -> tuple:
    """``moe_apply`` with expert-parallel dispatch over ``devices`` (one
    expert shard each) against ``moe_apply`` on the first device."""
    S = len(devices)
    k_p, k_x = jax.random.split(jax.random.PRNGKey(seed))
    p = MOE.moe_init(k_p, cfg, jnp.bfloat16)
    x = jax.random.normal(k_x, (n_tokens, cfg.d_model), jnp.bfloat16)

    def apply(c):
        return jax.jit(lambda p, x: MOE.moe_apply(p, c, x, return_stats=True))

    host = dataclasses.replace(cfg, expert_parallel=False)
    y_ref, st_ref = apply(host)(p, x)
    ep = dataclasses.replace(cfg, expert_parallel=True)
    with mesh_context(make_test_mesh(data=1, model=1, expert=S)):
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                              {"moe": p})
        p_ep = jax.device_put(p, named_shardings(shapes, ep)["moe"])
        y_ep, st_ep = apply(ep)(p_ep, x)
    placement = {}
    for name in ("w1", "w3", "w2"):
        shards = sorted((s.device.id, s.data.shape)
                        for s in p_ep[name].addressable_shards)
        placement[name] = {"spec": str(p_ep[name].sharding.spec),
                           "shards": [[i, list(sh)] for i, sh in shards]}
        ids = {i for i, _ in shards}
        experts = {sh[0] for _, sh in shards}
        if len(ids) != S or experts != {cfg.n_experts // S}:
            raise RuntimeError(f"expert weights {name} not spread over "
                               f"{S} devices: {placement[name]}")
    print(f"expert weight placement: {json.dumps(placement)}", flush=True)
    got = np.asarray(y_ep, np.float32)
    want = np.asarray(y_ref, np.float32)
    delta = float(np.max(np.abs(got - want)))
    bound = MOE_REL_BOUND * float(np.max(np.abs(want)))
    joins, rounds = int(st_ep["joins"]), int(st_ep["rounds"])
    drop_ep, drop_ref = (float(st_ep["dropped_frac"]),
                         float(st_ref["dropped_frac"]))
    print(f"expert-parallel vs one chip: max|delta|={delta} bound={bound} "
          f"dropped_frac ep={drop_ep} one_chip={drop_ref} "
          f"joins={joins} rounds={rounds}", flush=True)
    if not (np.all(np.isfinite(got)) and delta <= bound):
        raise RuntimeError(f"EP check: max|delta| {delta} exceeds {bound}")
    if joins != 1 or rounds != 1:
        raise RuntimeError(f"EP check: {joins} joins over {rounds} rounds")
    if drop_ep != drop_ref:
        raise RuntimeError(f"EP check: dropped_frac {drop_ep} != "
                           f"one-chip {drop_ref}")
    return 0, {"max_abs_delta": delta, "bound": bound,
               "dropped_frac": drop_ep, "joins_per_round": joins / rounds,
               "tokens": n_tokens}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the expert-parallel MoE phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = _tpu_devices()
    cache_dir = setup_compile_cache()
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"found {len(devices)}")
    devices = devices[:args.chips]
    clock = _CompileClock()
    if args.chips == 4:
        cfg = get_config(MOE_ARCH)
        _phase("expert_parallel_moe",
               lambda: check_expert_parallel(cfg, args.seed, MOE_TOKENS,
                                             devices),
               clock, devices, cache_dir)
    else:
        cfg = get_config(SERVE_ARCH)
        box = {}

        def build():
            box["batcher"] = build_batcher(cfg, seed=args.seed,
                                           n_slots=N_SLOTS,
                                           cache_len=CACHE_LEN, policy="dlbc")
            jax.block_until_ready(box["batcher"].params)
            return 0, {"arch": cfg.name, "n_layers": cfg.n_layers,
                       "d_model": cfg.d_model, "vocab": cfg.vocab}

        _phase("init", build, clock, devices, cache_dir)
        batcher = box["batcher"]
        _phase("serve", lambda: serve(batcher, make_requests(cfg, args.seed)),
               clock, devices, cache_dir)
        _phase("prefill_decode_check",
               lambda: check_prefill_decode(batcher, args.seed),
               clock, devices, cache_dir)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
