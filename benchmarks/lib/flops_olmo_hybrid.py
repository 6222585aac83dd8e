"""Required FLOPs per token of Olmo-Hybrid's decoder as one pipeline stage
holds it, from the source's own keys.

6 x the matmul parameters a token passes through: each kept linear layer's
Gated DeltaNet mixer (q and k at ``linear_num_key_heads`` x
``linear_key_head_dim``, v, the output gate and o at ``linear_num_value_heads``
x ``linear_value_head_dim``, the two maps a and b of one value a head); each
kept full layer's q and o at ``num_attention_heads``, k and v at
``num_key_value_heads``; every layer's SwiGLU of ``intermediate_size``; the
head over the held vocabulary; no embedding gather. Plus the causal attention
of the full layers and the recurrence of the linear layers
(``flops_kda.recurrence_per_token`` at dk != dv). The short convolutions, the
norms and the gates' products are no matmuls and count for nothing."""
from __future__ import annotations

from .flops_kda import recurrence_per_token


def layer_kinds(cfg: dict) -> list:
    """"gdn" or "attn" for each layer kept (the source counts them from 0)."""
    kinds = {"linear_attention": "gdn", "full_attention": "attn"}
    return [kinds[t] for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def gdn_matmul_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    qk = 2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    v = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return h * (qk + 3 * v) + h * (cfg["linear_num_key_heads"] + cfg["linear_num_value_heads"])


def full_matmul_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * d * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def olmo_hybrid_decoder(cfg: dict, seq: int) -> float:
    kinds = layer_kinds(cfg)
    n_gdn = kinds.count("gdn")
    n_full = len(kinds) - n_gdn
    h = cfg["hidden_size"]
    params = (
        n_gdn * gdn_matmul_params(cfg) + n_full * full_matmul_params(cfg)
        + len(kinds) * 3 * h * cfg["intermediate_size"] + h * cfg["vocab_size"]
    )
    # Scores and weighted values, the causal half, forward and backward.
    attention = 6.0 * n_full * seq * cfg["num_attention_heads"] * cfg["head_dim"]
    recurrence = n_gdn * cfg["linear_num_key_heads"] * recurrence_per_token(
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])
    return 6.0 * params + attention + recurrence
