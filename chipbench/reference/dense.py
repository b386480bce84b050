"""Plain reference for a dense decoder (pre-norm RMSNorm blocks, rotary
attention with grouped key/value heads, a gated SiLU MLP, untied output
head), in float32 at the highest matmul precision.

It imports nothing of the program.  It reads the benchmark's own weights
by their names in the tree and the sizes from the configuration file, and
runs teacher-forced: every sequence is a prompt followed by the tokens
the program served, so each served token is judged against the
reference's logits at the position it was produced for.

Sequences go through in groups of ``GROUP`` padded to the cache length,
one block at a time and the output head in vocabulary blocks, so neither
a float32 copy of the model nor a full logits tensor is ever built.

``float8`` is the control: every matrix product takes its operands
through float8 (e4m3, scaled per row of the activations and per output
column of the weights), the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
GROUP = 4
VOCAB_BLOCK = 16384
F8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def _q8(x, axis):
    """Round ``x`` through float8 e4m3 with one scale per slice along
    ``axis`` (the slice's largest magnitude maps to float8's largest)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, prec: str):
    """``x @ w`` for activations ``x`` (..., k) and weights ``w`` (k, n)."""
    w = w.astype(jnp.float32)
    if prec == "float8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """Rotary embedding on (G, T, H, dh) at positions 0..T-1, rotating the
    first half of each head against the second."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims", "prec"))
def _block(layers, index, x, dims, prec):
    n_heads, n_kv, dh, eps, theta = dims
    p = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False),
        layers)
    G, T, _ = x.shape
    h = _rms(x, p["ln1"]["scale"], eps)
    a = p["attn"]
    q = _mm(h, a["wq"]["w"], prec).reshape(G, T, n_heads, dh)
    k = _mm(h, a["wk"]["w"], prec).reshape(G, T, n_kv, dh)
    v = _mm(h, a["wv"]["w"], prec).reshape(G, T, n_kv, dh)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv          # query head j reads key head j // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("gqhd,gkhd->ghqk", q, k, precision=HIGHEST) / math.sqrt(dh)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("ghqk,gkhd->gqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(G, T, n_heads * dh)
    x = x + _mm(o, a["wo"]["w"], prec)
    h = _rms(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    f = jax.nn.silu(_mm(h, m["w1"], prec)) * _mm(h, m["w3"], prec)
    return x + _mm(f, m["w2"], prec)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, scale, eps):
    return _rms(x, scale, eps)


@functools.partial(jax.jit, static_argnames=("size", "vocab"))
def _head_block(h, low_h, head, start, served, best, served_logit, low_best,
                pick, *, size, vocab):
    """Fold one vocabulary block into the running float32 best logit and
    the float32 logit of each served token; with ``low_h`` (the control's
    hidden states), also into the token that float8 ranks first and its
    float32 logit."""
    w = jax.lax.dynamic_slice_in_dim(head, start, size, axis=1)
    ids = start + jnp.arange(size)
    valid = ids < vocab
    ref = jnp.where(valid, _mm(h, w, "float32"), -jnp.inf)
    best = jnp.maximum(best, jnp.max(ref, axis=-1))
    hit = ids == served[..., None]
    served_logit = served_logit + jnp.sum(jnp.where(hit, ref, 0.0), axis=-1)
    if low_h is not None:
        low = jnp.where(valid, _mm(low_h, w, "float8"), -jnp.inf)
        j = jnp.argmax(low, axis=-1)[..., None]
        low_max = jnp.take_along_axis(low, j, -1)[..., 0]
        better = low_max > low_best
        pick = jnp.where(better, jnp.take_along_axis(ref, j, -1)[..., 0], pick)
        low_best = jnp.maximum(low_best, low_max)
    return best, served_logit, low_best, pick


def _hidden(cfg, params, toks, precision):
    dims = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
            cfg["norm_eps"], cfg["rope_theta"])
    x = jnp.take(params["embed"], jnp.asarray(toks), axis=0).astype(
        jnp.float32)
    for layer in range(cfg["n_layers"]):
        x = _block(params["layers"], layer, x, dims, precision)
    return _final(x, params["final_norm"]["scale"], cfg["norm_eps"])


def logit_gaps(cfg: dict, params: dict, sequences, precision="float32"):
    """For each ``(tokens, first, served)`` in ``sequences`` -- the prompt
    and served tokens run through the model, the position that produced
    the first served token, and the served tokens -- the gap by which each
    served token's float32 logit lies below the float32 best at its
    position (infinite for an id outside the vocabulary).  With
    ``precision="float8"``, the gap of the token that the float8 path
    ranks first instead.  Returns one array per sequence."""
    T, vocab = cfg["cache_len"], cfg["vocab"]
    head = params["lm_head"]
    padded = head.shape[1]
    out = []
    for g0 in range(0, len(sequences), GROUP):
        group = sequences[g0:g0 + GROUP]
        toks = np.zeros((GROUP, T), np.int32)
        target = np.full((GROUP, T), -1, np.int64)
        for i, (tokens, first, served) in enumerate(group):
            toks[i, :len(tokens)] = tokens
            target[i, first:first + len(served)] = served
        h = _hidden(cfg, params, toks, "float32")
        low_h = (None if precision == "float32"
                 else _hidden(cfg, params, toks, precision))
        served = jnp.asarray(np.clip(target, 0, vocab - 1), jnp.int32)
        best = jnp.full((GROUP, T), -jnp.inf)
        got = jnp.zeros((GROUP, T))
        low_best = jnp.full((GROUP, T), -jnp.inf)
        pick = jnp.zeros((GROUP, T))
        for start in range(0, padded, VOCAB_BLOCK):
            best, got, low_best, pick = _head_block(
                h, low_h, head, jnp.int32(start), served, best, got,
                low_best, pick, size=min(VOCAB_BLOCK, padded - start),
                vocab=vocab)
        gap = np.array(best - (got if low_h is None else pick))
        gap[(target >= vocab)] = np.inf
        for i, (tokens, first, served_i) in enumerate(group):
            out.append(gap[i, first:first + len(served_i)])
    return out
