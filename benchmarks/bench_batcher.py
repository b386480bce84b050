"""Serving: DLBC continuous batching vs LC fixed batching — latency,
utilisation, and the chunked-prefill SLO surface, routed through the
oracle-first harness (seeded repeats, bootstrap-CI gates, trajectory).

Arms (per-repeat samples = end-to-end p99 latency in steps):

* ``lc``    — fixed batching (oracle/reference arm: the static-chunking
  baseline the paper's DLBC story is measured against);
* ``dlbc``  — continuous batching with DLBC-chunked prefill;
* ``dlbc/decode_cost`` — per-token decode cost p99 (token units: 1 +
  the largest prefill chunk sharing the step), the surface the
  long-prompt-adversary gate in ``bench_tenants`` leans on.

Exact gates (no sampling noise, no CI slack):

* chunked prefill == whole-prompt prefill, max |Δ| == 0.0 per repeat
  (the correctness oracle for the prefill-replay bugfix);
* telemetry joins == completed requests on every run (AFE: prefill
  chunks are never joined individually);
* every per-token decode cost ≤ 1 + prefill_chunk (a chunk cap that
  holds structurally is what makes the SLO bound non-vacuous).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as MDL
from repro.serve.batcher import ContinuousBatcher, Request

from .common import report
from .harness import Bench

PREFILL_CHUNK = 8
CACHE_LEN = 64


def _make_requests(n_requests, vocab, seed):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=list(rng.integers(0, vocab,
                                             size=int(rng.integers(2, 17)))),
                    max_new=int(rng.integers(3, 28)),
                    arrive_step=int(rng.integers(0, 30)))
            for i in range(n_requests)]


def _prefill_equivalence_delta(cfg, params, seed) -> float:
    """Oracle check: decode logits after chunked prefill (sizes 1, 8)
    vs whole-prompt prefill — returns max |Δ| (must be exactly 0.0)."""
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab, size=12).tolist()
    pre = len(prompt) - 1
    buf = 16

    def fill(sizes):
        cache = MDL.init_cache(cfg, 1, 32, MDL.lane_width(jax.devices()[0]))
        pos = 0
        for s in sizes:
            toks = np.zeros((1, buf), np.int32)
            toks[0, :s] = prompt[pos:pos + s]
            _, cache = MDL.prefill_step(
                params, cfg, cache,
                {"tokens": jnp.asarray(toks),
                 "cache_index": jnp.asarray([pos], jnp.int32),
                 "count": jnp.asarray([s], jnp.int32)})
            pos += s
        logits, _ = MDL.decode_step(
            params, cfg, cache,
            {"tokens": jnp.asarray([[prompt[-1]]], jnp.int32),
             "cache_index": jnp.asarray([pre], jnp.int32)})
        return np.asarray(logits)

    ref = fill([pre])
    delta = 0.0
    for sizes in ([1] * pre, [8, pre - 8]):
        delta = max(delta, float(np.abs(ref - fill(sizes)).max()))
    return delta


def run(n_requests: int = 32, slots: int = 4, seed: int = 0,
        repeats: int = 5):
    repeats = max(int(repeats or 5), 5)
    cfg = ModelConfig(name="bench-serve", family="dense", n_layers=2,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab=1024)
    params = MDL.init_params(cfg, jax.random.PRNGKey(seed))

    bench = Bench("batcher", seed=seed, repeats=repeats)
    p99s = {"lc": [], "dlbc": []}
    cost_p99s = []
    records = []
    max_delta = 0.0
    joins_mismatch = 0
    worst_cost = 0
    for rep in range(repeats):
        for policy in ("lc", "dlbc"):
            b = ContinuousBatcher(cfg, params, n_slots=slots,
                                  cache_len=CACHE_LEN, policy=policy,
                                  prefill_chunk=PREFILL_CHUNK)
            st = b.run(_make_requests(n_requests, cfg.vocab, seed + rep))
            sched = b.sched.telemetry.summary()
            # AFE: joins count REQUESTS — chunked prefill must not add
            # joins, and every admitted request must complete
            joins_mismatch += abs(sched["joins"] - len(st.latencies))
            joins_mismatch += abs(sched["spawns"] - sched["joins"])
            p99s[policy].append(st.p99_latency)
            if policy == "dlbc":
                cost_p99s.append(st.p99_decode_cost)
                worst_cost = max(worst_cost,
                                 max(st.decode_step_costs, default=0))
            records.append(dict(
                policy=policy, repeat=rep, steps=st.steps,
                utilization=st.utilization,
                mean_latency=float(np.mean(st.latencies)),
                p99_latency=st.p99_latency,
                p99_decode_cost=st.p99_decode_cost,
                n_done=len(st.latencies), truncated=st.truncated,
                vtime=b.vtime, sched=sched))
        max_delta = max(max_delta,
                        _prefill_equivalence_delta(cfg, params, seed + rep))

    bench.add_samples("lc", p99s["lc"], oracle=True, unit="steps")
    bench.add_samples("dlbc", p99s["dlbc"], unit="steps")
    bench.add_samples("dlbc/decode_cost", cost_p99s, unit="tokens")
    # continuous batching must not lose to fixed batching on tail latency
    bench.gate_ratio("dlbc_vs_lc_p99", "dlbc", "lc", "<=", 1.0, p=50)
    # the prefill-replay bugfix's correctness oracle: exact, every repeat
    bench.gate_exact("prefill_chunked_vs_whole_max_abs_delta",
                     max_delta, "<=", 0.0)
    bench.gate_exact("joins_eq_completed_requests", joins_mismatch, "<=", 0)
    # the chunk cap holds structurally: no decoded token ever paid more
    # than one decode + one full prefill chunk
    bench.gate_exact("decode_cost_le_one_plus_chunk",
                     worst_cost, "<=", 1 + PREFILL_CHUNK)
    bench.check()

    rows = []
    for policy in ("lc", "dlbc"):
        recs = [r for r in records if r["policy"] == policy]
        rows.append([policy,
                     f"{np.mean([r['steps'] for r in recs]):.0f}",
                     f"{np.mean([r['utilization'] for r in recs]):.3f}",
                     f"{np.mean([r['mean_latency'] for r in recs]):.1f}",
                     f"{np.percentile(p99s[policy], 50):.1f}",
                     f"{np.mean([r['p99_decode_cost'] for r in recs]):.1f}",
                     sum(r["truncated"] for r in recs)])
    rows.append(["prefill max|Δ|", "", "", "", f"{max_delta:.1f}", "", ""])
    return report(
        "Serving: DLBC continuous batching vs LC fixed batching "
        f"(chunked prefill, cap={PREFILL_CHUNK}, {repeats} repeats)",
        rows,
        ["policy", "steps", "util", "mean_lat", "p99_lat(med)",
         "decode_cost_p99", "truncated"],
        "batcher", records, harness=bench.payload())


if __name__ == "__main__":
    run()
