"""What a latent-attention expert model (configuration family
``mla_moe``, DeepSeek-V3-style) needs, in bytes and FLOPs, worked out
from the configuration file's sizes, the positions served and the experts
a launch hit, as ``costs.py`` does for a dense block.

``cfg`` is the configuration file's dict; the sizes are its published
keys (``hidden_size``, ``num_attention_heads``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``n_shared_experts``,
``first_k_dense_replace``, ``num_experts_per_tok``, ``vocab_size``),
with ``n_routed_experts`` the experts held here and
``n_routed_experts_published`` the router's width.

FLOPs count the textbook form, whatever the implementation computes:
two per matrix parameter a token touches (the projections with the whole
of ``Wkv_b``, the router, the shared experts, the held experts it is
routed to, the dense layers, the output head where logits are needed),
and ``2 x heads x (nope + rope + v)`` per valid (query, key) pair.  A
token is routed to ``num_experts_per_tok x held / published`` held
experts per expert layer, the share of a uniform router, so every
implementation reads the same work.
"""

from __future__ import annotations

import numpy as np

from chipbench.costs import bytes_per_value


def attn_params(cfg: dict) -> int:
    """One layer's latent attention: q, kv_a, its norm, kv_b and o."""
    d, H, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    return (d * H * (nope + rope) + d * (r + rope) + r
            + r * H * (nope + dv) + H * dv * d)


def expert_params(cfg: dict) -> int:
    """One routed expert (a SwiGLU of ``moe_intermediate_size``)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layers(cfg: dict) -> tuple:
    """(layers, leading dense layers, expert layers)."""
    n, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return n, k, n - k


def _shared_and_router(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (cfg["n_shared_experts"] * expert_params(cfg)
            + d * cfg["n_routed_experts_published"]
            + cfg["n_routed_experts_published"])


def decode_weight_bytes(cfg: dict) -> int:
    """Weights every decode step reads, the held experts aside: each
    layer's attention and norms, the dense layers' MLP, the expert
    layers' router and shared experts, the final norm and the output
    head; not the embedding table, of which a step reads a row per slot."""
    d = cfg["hidden_size"]
    n, k, m = _layers(cfg)
    total = (n * (attn_params(cfg) + 2 * d)
             + k * 3 * d * cfg["intermediate_size"]
             + m * _shared_and_router(cfg) + d
             + d * cfg["vocab_size"])
    return total * bytes_per_value(cfg)


def entry_bytes(cfg: dict) -> int:
    """The latent cache's entry of one position over every layer."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * bytes_per_value(cfg))


def decode_step_bytes(cfg: dict, positions, experts_hit: float) -> float:
    """Least bytes of one decode step whose slots write at ``positions``
    and whose tokens hit ``experts_hit`` held experts (summed over the
    expert layers): the weights above, each hit expert once, and each
    slot's valid entries (``position + 1``, the new one included) and
    the one written."""
    pos = np.asarray(list(positions), np.int64)
    return float(decode_weight_bytes(cfg)
                 + experts_hit * expert_params(cfg) * bytes_per_value(cfg)
                 + entry_bytes(cfg) * int(np.sum(pos + 2)))


def token_flops(cfg: dict, position: int, with_head: bool) -> float:
    """FLOPs one token at ``position`` needs (module docstring)."""
    d = cfg["hidden_size"]
    n, k, m = _layers(cfg)
    held = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_published"])
    params = (n * attn_params(cfg) + k * 3 * d * cfg["intermediate_size"]
              + m * (_shared_and_router(cfg) + held * expert_params(cfg)))
    per_pair = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    head = 2 * d * cfg["vocab_size"] if with_head else 0
    return 2.0 * params + n * per_pair * (position + 1) + head


def request_flops(cfg: dict, prompt_len: int, n_generated: int) -> float:
    """FLOPs of a request's prompt (its first ``prompt_len - 1`` tokens
    through the layers) and of its first ``n_generated`` tokens, each
    decoded at ``prompt_len - 1 + i`` with the output head."""
    total = sum(token_flops(cfg, p, False) for p in range(prompt_len - 1))
    return total + sum(token_flops(cfg, prompt_len - 1 + i, True)
                       for i in range(n_generated))
