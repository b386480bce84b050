"""What the algorithm needs, in bytes and FLOPs, worked out from the
configuration's sizes and the positions served (never from the program's
buffers: a full-length cache read or a padded vocabulary is the
implementation's cost, not the work's).

``cfg`` is a configuration file's dict: ``n_layers``, ``d_model``,
``n_heads``, ``n_kv_heads``, ``head_dim``, ``d_ff``, ``vocab``, ``act``
and ``dtype``.
"""

from __future__ import annotations

import numpy as np

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def bytes_per_value(cfg: dict) -> int:
    return _BYTES[cfg["dtype"]]


def layer_matmul_params(cfg: dict) -> int:
    """Matrix parameters of one dense block: q, k, v, o and the MLP."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    attn = d * hd * (2 * cfg["n_heads"] + 2 * cfg["n_kv_heads"])
    mlp = (3 if cfg["act"] == "swiglu" else 2) * d * cfg["d_ff"]
    return attn + mlp


def head_params(cfg: dict) -> int:
    """The output projection over the real vocabulary."""
    return cfg["d_model"] * cfg["vocab"]


def decode_weight_bytes(cfg: dict) -> int:
    """Weights one decode step reads: every block's matrices and norms,
    the final norm and the output head; not the embedding table, of
    which a step reads one row per slot."""
    d = cfg["d_model"]
    per_layer = layer_matmul_params(cfg) + 2 * d
    n = cfg["n_layers"] * per_layer + d + head_params(cfg)
    return n * bytes_per_value(cfg)


def kv_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position over every layer."""
    return (2 * cfg["n_layers"] * cfg["n_kv_heads"] * cfg["head_dim"]
            * bytes_per_value(cfg))


def decode_step_bytes(cfg: dict, positions) -> int:
    """Least bytes of one decode step whose slots write at ``positions``:
    the weights, each slot's valid keys and values (``position + 1`` of
    them, the new one included), and the new ones written."""
    pos = np.asarray(list(positions), np.int64)
    kv = kv_bytes_per_position(cfg)
    return int(decode_weight_bytes(cfg) + kv * int(np.sum(pos + 2)))


def token_flops(cfg: dict, position: int, with_head: bool) -> float:
    """FLOPs one token at ``position`` needs: two per matrix parameter,
    attention over the ``position + 1`` valid positions (scores and
    weighted sum, two FLOPs per multiply-add each), and the output head
    where the token's logits are needed."""
    attn = (4 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"]
            * (position + 1))
    head = 2 * head_params(cfg) if with_head else 0
    return 2.0 * cfg["n_layers"] * layer_matmul_params(cfg) + attn + head


def request_flops(cfg: dict, prompt_len: int, first_generated: int,
                  n_generated: int, prefill: bool) -> float:
    """FLOPs of a request's work: its prompt's first ``prompt_len - 1``
    tokens through the blocks (when ``prefill``), then one decode per
    generated token ``first_generated .. first_generated + n_generated
    - 1``, each with the output head.  Generated token ``i`` is decoded
    at position ``prompt_len - 1 + i``."""
    total = 0.0
    if prefill:
        total += sum(token_flops(cfg, p, False)
                     for p in range(prompt_len - 1))
    for i in range(first_generated, first_generated + n_generated):
        total += token_flops(cfg, prompt_len - 1 + i, True)
    return total
