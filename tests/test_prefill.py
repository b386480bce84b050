"""Chunked prefill: exactness, isolation, validation, AFE accounting.

The serving-path prefill claims pinned here:

* chunked prefill == whole-prompt prefill, BITWISE (every chunk runs
  through the same static launch buffer and each query's attention
  reduces over the full cache, so chunk boundaries cannot move a single
  bit — the harness gates max |Δ| == 0.0);
* a padded/inert row of the batched prefill launch leaves its cache
  untouched bit-for-bit (neighbour isolation);
* a refill that starts a long prefill next to a slot deep in decode
  leaves the neighbour's tokens exactly as in its solo run;
* `submit()` validates prompts (empty, out-of-vocab, overlong) instead
  of crashing or silently wrapping inside `step()`;
* cache-bound kills are counted as `truncated`, apart from completions;
* telemetry joins count REQUESTS, never prefill chunks (AFE).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import model as MDL
from repro.serve.batcher import ContinuousBatcher, Request


def _cfg(vocab=128):
    return ModelConfig(name="prefill-test", family="dense", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab=vocab)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = MDL.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prefill_in_chunks(cfg, params, prompt, sizes, *, buf=16, bsz=2,
                       cache_len=32, lane=1):
    """Write ``prompt`` through prefill_step in the given chunk sizes
    (row 0 live, row 1 inert), all through one static ``buf``-wide
    launch buffer like the batcher, into a cache padded to ``lane``."""
    assert sum(sizes) == len(prompt) and max(sizes) <= buf
    cache = MDL.init_cache(cfg, bsz, cache_len, lane)
    pos = 0
    for s in sizes:
        toks = np.zeros((bsz, buf), np.int32)
        toks[0, :s] = prompt[pos:pos + s]
        _, cache = MDL.prefill_step(
            params, cfg, cache,
            {"tokens": jnp.asarray(toks),
             "cache_index": jnp.asarray([pos] + [0] * (bsz - 1), jnp.int32),
             "count": jnp.asarray([s] + [0] * (bsz - 1), jnp.int32)})
        pos += s
    return cache


def _decode_logits(cfg, params, cache, token, pos, bsz=2):
    toks = np.zeros((bsz, 1), np.int32)
    toks[0, 0] = token
    logits, _ = MDL.decode_step(
        params, cfg, cache,
        {"tokens": jnp.asarray(toks),
         "cache_index": jnp.asarray([pos] + [0] * (bsz - 1), jnp.int32)})
    return np.asarray(logits)


@pytest.mark.parametrize("lane", [1, 128])
def test_chunked_prefill_is_bitwise_equal_to_whole(setup, lane):
    """Chunk size ∈ {1, 8, prompt_len}: the KV cache and the next-token
    logits are EXACTLY equal — max |Δ| == 0.0, not allclose — on the
    cache as the CPU lays it out and padded to a TPU's 128 lanes."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab, size=12).tolist()
    pre = len(prompt) - 1  # decode consumes the last prompt token
    chunks = lambda sizes: _prefill_in_chunks(  # noqa: E731
        cfg, params, prompt[:-1], sizes, lane=lane)
    whole = chunks([pre])
    assert whole["layers"]["k"].shape[-1] == max(cfg.head_dim, lane)
    by_one = chunks([1] * pre)
    by_eight = chunks([8, pre - 8])
    ref = _decode_logits(cfg, params, whole, prompt[-1], pre)
    for cache in (by_one, by_eight):
        for k in ("k", "v"):
            assert np.array_equal(np.asarray(whole["layers"][k]),
                                  np.asarray(cache["layers"][k]))
        logits = _decode_logits(cfg, params, cache, prompt[-1], pre)
        assert float(np.abs(ref - logits).max()) == 0.0


def test_prefill_first_token_matches_forward(setup):
    """The decode-after-prefill argmax equals the training-path forward
    argmax on the same prompt (numerics differ — online vs full softmax
    — but the picked token must not)."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, size=9).tolist()
    pre = len(prompt) - 1
    cache = _prefill_in_chunks(cfg, params, prompt[:-1], [pre])
    logits = _decode_logits(cfg, params, cache, prompt[-1], pre)
    fwd = np.asarray(MDL.forward(params, cfg,
                                 {"tokens": jnp.asarray([prompt])},
                                 last_only=True))
    assert int(np.argmax(fwd[0].ravel()[:cfg.vocab])) \
        == int(np.argmax(logits[0, :cfg.vocab]))


def test_inert_rows_untouched_bitwise(setup):
    """A row with count == 0 in the batched launch keeps its cache
    bit-for-bit — seeded with garbage first so zeros can't mask a
    spurious write."""
    cfg, params = setup
    cache = MDL.init_cache(cfg, 2, 32, 1)
    k0 = jax.random.normal(jax.random.PRNGKey(1),
                           cache["layers"]["k"].shape,
                           cache["layers"]["k"].dtype)
    cache["layers"]["k"] = k0
    toks = np.zeros((2, 16), np.int32)
    toks[0, :5] = [1, 2, 3, 4, 5]
    _, new_cache = MDL.prefill_step(
        params, cfg, cache,
        {"tokens": jnp.asarray(toks),
         "cache_index": jnp.asarray([0, 0], jnp.int32),
         "count": jnp.asarray([5, 0], jnp.int32)})
    assert np.array_equal(np.asarray(new_cache["layers"]["k"])[:, 1],
                          np.asarray(k0)[:, 1])
    # and the live row's tail (past its span) is untouched too
    assert np.array_equal(np.asarray(new_cache["layers"]["k"])[:, 0, :, 5:],
                          np.asarray(k0)[:, 0, :, 5:])


def _span_reference(cache, new, idx, count):
    """The span write's contract, one position at a time: lane j of row
    b lands at ``[b, :, idx[b] + j]``, zero-padded past the head dim,
    when ``j < count[b]`` and the position is inside the cache; every
    other position keeps its bits."""
    out = np.array(cache)
    T, h = out.shape[2], new.shape[3]
    for b in range(out.shape[0]):
        for j in range(min(int(count[b]), new.shape[1])):
            if int(idx[b]) + j < T:
                out[b, :, int(idx[b]) + j] = 0
                out[b, :, int(idx[b]) + j, :h] = new[b, j]
    return out


@pytest.mark.parametrize("T,C,idx,count", [
    (16, 8, [0, 5], [8, 3]),      # spans inside the cache
    (16, 8, [12, 0], [8, 0]),     # runs past the end; an inert row
    (16, 8, [15, 9], [1, 7]),     # the window clamps to the cache end
    (8, 12, [2, 0], [12, 8]),     # a launch wider than the cache
])
def test_span_write_keeps_every_dropped_position(T, C, idx, count):
    """``kv_cache_update_span`` equals the one-position reference
    bitwise, on a cache seeded with noise and padded past the head dim,
    whether it is one layer or layer 1 of a stack."""
    from repro.models import layers as L

    B, KV, h, dc = 2, 3, 4, 8
    rng = np.random.default_rng(T * C)
    cache = rng.normal(size=(B, KV, T, dc)).astype(np.float32)
    new = rng.normal(size=(B, C, KV, h)).astype(np.float32)
    idx, count = np.asarray(idx, np.int32), np.asarray(count, np.int32)
    want = _span_reference(cache, new, idx, count)
    got = L.kv_cache_update_span(jnp.asarray(cache), jnp.asarray(new),
                                 jnp.asarray(idx), jnp.asarray(count))
    assert np.array_equal(np.asarray(got), want)
    stack = jnp.stack([jnp.zeros_like(cache), jnp.asarray(cache)])
    got = L.kv_cache_update_span(stack, jnp.asarray(new), jnp.asarray(idx),
                                 jnp.asarray(count), at=(jnp.int32(1),))
    assert np.array_equal(np.asarray(got[1]), want)
    assert not np.any(np.asarray(got[0]))


def test_prefill_rejected_for_unsupported_cache_families(setup):
    cfg, params = setup
    windowed = ModelConfig(name="win", family="dense", n_layers=2,
                           d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                           vocab=128, sliding_window=8)
    with pytest.raises(NotImplementedError, match="ring-buffer"):
        MDL.prefill_step(MDL.init_params(windowed, jax.random.PRNGKey(0)),
                         windowed, MDL.init_cache(windowed, 1, 16, 1),
                         {"tokens": jnp.zeros((1, 4), jnp.int32),
                          "cache_index": jnp.zeros(1, jnp.int32),
                          "count": jnp.ones(1, jnp.int32)})


# -- batcher-level ----------------------------------------------------------


def test_refill_mid_prefill_neighbour_decode_unperturbed(setup):
    """A long-prompt request refilled next to a slot deep in decode must
    not perturb the neighbour: its tokens match the solo run exactly.
    And the long request's own tokens match ITS solo run — chunked
    prefill beside a decoder changes nothing either way."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    long_prompt = rng.integers(0, cfg.vocab, size=14).tolist()

    def batcher():
        return ContinuousBatcher(cfg, params, n_slots=2, cache_len=32,
                                 policy="dlbc", prefill_chunk=4)

    def steady():
        return Request(rid=0, prompt=[7, 8, 9], max_new=12, arrive_step=0)

    def adversary():
        # arrives once the steady slot is several tokens deep in decode
        return Request(rid=1, prompt=list(long_prompt), max_new=4,
                       arrive_step=4)

    solo_s = steady()
    batcher().run([solo_s])
    solo_a = adversary()
    batcher().run([solo_a])
    s, a = steady(), adversary()
    both = batcher()
    both.run([s, a])
    # the adversary's 13-token prefix really was chunked (cap 4)
    assert both.sched.telemetry.prefill_chunks >= 4
    assert s.tokens == solo_s.tokens
    assert a.tokens == solo_a.tokens


def test_padded_cache_serves_the_same_tokens(setup, monkeypatch):
    """A batcher whose cache is padded to a TPU's 128 lanes serves
    exactly the tokens of one with the CPU's unpadded cache, through a
    refill mid-decode and a prompt spread over several DLBC chunks —
    and each launch leaves the cache it was given donated."""
    cfg, params = setup
    rng = np.random.default_rng(17)
    long_prompt = rng.integers(0, cfg.vocab, size=19).tolist()

    def serve(lane):
        monkeypatch.setattr(MDL, "lane_width", lambda device: lane)
        b = ContinuousBatcher(cfg, params, n_slots=2, cache_len=48,
                              policy="dlbc", prefill_chunk=4)
        assert b.cache["layers"]["k"].shape[-1] == max(cfg.head_dim, lane)
        first = b.cache["layers"]["k"]
        reqs = [Request(rid=0, prompt=[3, 1, 4], max_new=5, arrive_step=0),
                Request(rid=1, prompt=[5, 9], max_new=14, arrive_step=0),
                # refills the first slot to free while rid 1 decodes
                Request(rid=2, prompt=list(long_prompt), max_new=6,
                        arrive_step=2)]
        b.run(reqs)
        assert first.is_deleted()
        return b, [r.tokens for r in reqs]

    padded, padded_tokens = serve(128)
    plain, plain_tokens = serve(1)
    assert padded.sched.telemetry.prefill_chunks >= 5
    assert [a[0] for a in padded.admissions] == [0, 0, 5]
    assert padded.admissions == plain.admissions
    assert padded_tokens == plain_tokens


def test_submit_rejects_empty_prompt(setup):
    cfg, params = setup
    b = ContinuousBatcher(cfg, params, n_slots=2, cache_len=16)
    with pytest.raises(ValueError, match="empty prompt"):
        b.submit(Request(rid=0, prompt=[], max_new=4))


def test_submit_rejects_out_of_vocab(setup):
    cfg, params = setup
    b = ContinuousBatcher(cfg, params, n_slots=2, cache_len=16)
    with pytest.raises(ValueError, match="outside"):
        b.submit(Request(rid=0, prompt=[1, cfg.vocab], max_new=4))
    with pytest.raises(ValueError, match="outside"):
        b.submit(Request(rid=1, prompt=[-1], max_new=4))


def test_submit_rejects_overlong_prompt(setup):
    cfg, params = setup
    b = ContinuousBatcher(cfg, params, n_slots=2, cache_len=16)
    with pytest.raises(ValueError, match="cannot fit"):
        b.submit(Request(rid=0, prompt=list(range(17)), max_new=4))


def test_submit_rejects_windowed_multi_token_prompt():
    cfg = ModelConfig(name="win", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                      sliding_window=8)
    b = ContinuousBatcher(cfg, params=MDL.init_params(
        cfg, jax.random.PRNGKey(0)), n_slots=2, cache_len=16)
    with pytest.raises(NotImplementedError, match="single-token"):
        b.submit(Request(rid=0, prompt=[1, 2, 3], max_new=2))
    # single-token prompts still serve on windowed configs
    b.submit(Request(rid=1, prompt=[1], max_new=2))


def test_truncated_counter_separates_cache_kills(setup):
    """A request that hits the cache bound before max_new is counted in
    `truncated`, not silently folded into normal completions."""
    cfg, params = setup
    b = ContinuousBatcher(cfg, params, n_slots=2, cache_len=16,
                          policy="dlbc")
    b.run([Request(rid=0, prompt=[1, 2], max_new=500, arrive_step=0),
           Request(rid=1, prompt=[3], max_new=2, arrive_step=0)])
    assert b.stats.truncated == 1
    assert len(b.stats.latencies) == 2  # both still complete + record
    assert "truncated" in b.stats.summary()
    assert b.stats.summary()["truncated"] == 1


def test_joins_count_requests_not_chunks(setup):
    """AFE over the serving path: a request whose prefill ran in many
    chunks still joins exactly once — spawns == joins == requests, with
    chunk work in its own counters."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=13).tolist()
               for _ in range(3)]
    b = ContinuousBatcher(cfg, params, n_slots=2, cache_len=32,
                          policy="dlbc", prefill_chunk=4)
    b.run([Request(rid=i, prompt=p, max_new=3, arrive_step=2 * i)
           for i, p in enumerate(prompts)])
    tele = b.sched.telemetry
    assert tele.spawns == tele.joins == 3
    assert tele.prefill_chunks >= 3 * 2  # 12-token prefixes, chunk cap 4
    assert tele.prefill_tokens == 3 * 12
    assert b.stats.summary()["n_done"] == 3


def test_decode_cost_accounting_charges_shared_prefill(setup):
    """Per-token decode costs: steps shared with prefill chunks cost
    1 + chunk, and the whole-prefill baseline's worst token cost is
    strictly larger than chunked's (the SLO mechanism the adversary
    bench gates)."""
    cfg, params = setup
    rng = np.random.default_rng(13)
    long_prompt = rng.integers(0, cfg.vocab, size=25).tolist()

    def run(mode):
        reqs = [Request(rid=0, prompt=[5, 6], max_new=30, arrive_step=0),
                Request(rid=1, prompt=list(long_prompt), max_new=2,
                        arrive_step=3)]
        b = ContinuousBatcher(cfg, params, n_slots=2, cache_len=48,
                              policy="dlbc", prefill_chunk=6,
                              prefill_mode=mode)
        b.run(reqs)
        return b, reqs
    chunked, creqs = run("chunked")
    whole, wreqs = run("whole")
    assert max(chunked.stats.decode_step_costs) \
        <= 1 + chunked.prefill_chunk
    assert max(whole.stats.decode_step_costs) \
        > max(chunked.stats.decode_step_costs)
    # chunking changes scheduling, never tokens (bitwise prefill)
    assert [r.tokens for r in creqs] == [r.tokens for r in wreqs]
