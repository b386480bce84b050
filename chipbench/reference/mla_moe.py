"""Plain reference for a DeepSeek-V3-style decoder with latent attention
and an expert layer that holds a share of the experts (Moonlight-16B-A3B),
in float32 at the highest matmul precision.

It imports nothing of the program.  It reads the benchmark's own weights
by their names in the tree (``dense_layers``, then ``layers``) and the
sizes from the configuration file, and runs teacher-forced, one sequence
at a time, as ``dense.py`` does; the output head and the float8 control
are ``dense.py``'s.

Each layer is the textbook form:

* attention: ``q = Wq h`` split into ``q_nope`` and ``q_rope`` per head;
  ``[c, k_rope] = Wkv_a h`` with ``c`` RMS-normalised; ``[k_nope, v] =
  Wkv_b c`` per head, built for every position (the expanded form, not
  the program's absorbed one); rotary on ``q_rope`` and on the one
  ``k_rope`` all heads share; softmax scale ``1 / sqrt(nope + rope)``;
* the leading layers' MLP, or the expert layer: sigmoid scores over all
  experts, each token's exact top-k by score plus the correction bias,
  the chosen scores normalised to sum 1 and scaled; every held expert
  computed for every token and weighted by its gate where chosen (no
  capacity); the experts held elsewhere left out, as the chip leaves
  them; the shared experts added.

Attention runs in blocks of queries so that no full score matrix is
built.

``float8`` is the control, as in ``dense.py``, except that the two
attention products (queries against keys, probabilities against values)
take their operands through float8 too: the program computes them in the
configuration's bfloat16 against its latent cache, so the precision below
it rounds them as well, each operand scaled per slice along the summed
axis.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import dense as D

HIGHEST = D.HIGHEST
#: queries per attention block
Q_BLOCK = 512
#: sequences are padded to a multiple of this (at most the cache
#: length), so few shapes compile
PAD = 2048
VOCAB_BLOCK = 8192


def _dot(spec, a, b, axis, prec):
    """``einsum(spec, a, b)`` of two activations, summed over the last
    axis of ``a`` and over ``axis`` of ``b``; with ``float8``, each operand
    is rounded through float8 with one scale per slice along that axis."""
    if prec == "float8":
        a, b = D._q8(a, -1), D._q8(b, axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _swiglu(h, w1, w3, w2, prec):
    return D._mm(jax.nn.silu(D._mm(h, w1, prec)) * D._mm(h, w3, prec), w2,
                 prec)


def _attention(p, h, dims, prec):
    """Latent attention of one sequence ``h`` (1, T, d), expanded."""
    heads, rank, nope, rope, dv, eps, theta = dims
    _, T, _ = h.shape
    q = D._mm(h, p["wq"]["w"], prec).reshape(1, T, heads, nope + rope)
    kv = D._mm(h, p["wkv_a"]["w"], prec)
    c = D._rms(kv[..., :rank], p["kv_norm"]["scale"], eps)
    k_rope = D._rope(kv[..., rank:].reshape(1, T, 1, rope), theta)
    q = jnp.concatenate([q[..., :nope], D._rope(q[..., nope:], theta)], -1)
    kvb = D._mm(c, p["wkv_b"]["w"], prec).reshape(1, T, heads, nope + dv)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_rope, (1, T, heads, rope))], -1)
    v = kvb[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)
    kpos = jnp.arange(T)
    size = min(Q_BLOCK, T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, size, axis=1)
        s = _dot("gqhd,gkhd->ghqk", qb, k, -1, prec) * scale
        causal = (start + jnp.arange(size))[:, None] >= kpos[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        return _dot("ghqk,gkhd->gqhd", jax.nn.softmax(s, axis=-1), v, 1,
                    prec)

    o = jax.lax.map(block, jnp.arange(0, T, size))      # (nb, 1, qb, H, dv)
    o = jnp.moveaxis(o, 0, 1).reshape(1, T, heads * dv)
    return D._mm(o, p["wo"]["w"], prec)


def _experts(m, h, moe, prec):
    """The expert layer for ``h`` (1, T, d): the held experts' part for
    each token's exact top-k, plus the shared experts."""
    top_k, routed_scale, first = moe
    scores = jax.nn.sigmoid(D._mm(h, m["router"]["w"], prec))
    _, ids = jax.lax.top_k(scores + m["router"]["bias"], top_k)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20) * routed_scale
    n_held = m["w1"].shape[0]

    def expert(out, e):
        w = jnp.sum(jnp.where(ids == first + e, gates, 0.0), axis=-1)
        y = _swiglu(h, m["w1"][e], m["w3"][e], m["w2"][e], prec)
        return out + w[..., None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(n_held))
    s = m["shared"]
    return out + _swiglu(h, s["w1"], s["w3"], s["w2"], prec)


@functools.partial(jax.jit, static_argnames=("dims", "moe", "prec"))
def _block(layers, index, x, dims, moe, prec):
    p = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False),
        layers)
    eps = dims[5]
    x = x + _attention(p["attn"], D._rms(x, p["ln1"]["scale"], eps), dims,
                       prec)
    h = D._rms(x, p["ln2"]["scale"], eps)
    if moe is None:
        m = p["mlp"]
        return x + _swiglu(h, m["w1"], m["w3"], m["w2"], prec)
    return x + _experts(p["moe"], h, moe, prec)


def _hidden(cfg, params, toks, precision):
    dims = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["rms_norm_eps"], float(cfg["rope_theta"]))
    moe = (cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
           cfg["expert_first"])
    x = jnp.take(params["embed"], jnp.asarray(toks), axis=0).astype(
        jnp.float32)
    for name, kind in (("dense_layers", None), ("layers", moe)):
        for layer in range(params[name]["ln1"]["scale"].shape[0]):
            x = _block(params[name], layer, x, dims, kind, precision)
    return D._final(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def logit_gaps(cfg: dict, params: dict, sequences, precision="float32"):
    """As ``dense.logit_gaps``: for each ``(tokens, first, served)``, the
    gap by which each served token's float32 logit lies below the float32
    best at its position (with ``precision="float8"``, the gap of the
    token that the float8 path ranks first).  One array per sequence."""
    vocab = cfg["vocab"]
    head = params["lm_head"]
    padded = head.shape[1]
    out = []
    for tokens, first, served in sequences:
        T = min(-(-len(tokens) // PAD) * PAD, cfg["cache_len"])
        toks = np.zeros((1, T), np.int32)
        toks[0, :len(tokens)] = tokens
        target = np.full((1, T), -1, np.int64)
        target[0, first:first + len(served)] = served
        h = _hidden(cfg, params, toks, "float32")
        low_h = (None if precision == "float32"
                 else _hidden(cfg, params, toks, precision))
        tgt = jnp.asarray(np.clip(target, 0, vocab - 1), jnp.int32)
        best = jnp.full((1, T), -jnp.inf)
        got = jnp.zeros((1, T))
        low_best = jnp.full((1, T), -jnp.inf)
        pick = jnp.zeros((1, T))
        for start in range(0, padded, VOCAB_BLOCK):
            best, got, low_best, pick = D._head_block(
                h, low_h, head, jnp.int32(start), tgt, best, got, low_best,
                pick, size=min(VOCAB_BLOCK, padded - start), vocab=vocab)
        gap = np.array(best - (got if low_h is None else pick))
        gap[target >= vocab] = np.inf
        out.append(gap[0, first:first + len(served)])
    return out
