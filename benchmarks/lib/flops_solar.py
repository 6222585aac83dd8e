"""Required FLOPs per token of Solar-Open2's decoder as one expert-parallel
rank holds it, from the source's own keys.

6 x the matmul parameters a token passes through: each kept KDA layer's
mixer (``flops_kimi.kda_matmul_params``: q, k, v, o, the two low-rank gates
of the head dim's width, beta, at this model's 64 heads); each kept GQA
layer's q and o at ``num_attention_heads``, k and v at
``num_key_value_heads`` and, under ``use_gqa_gate``, the gate at q's width;
in every expert layer the router at its published width, the shared expert,
and the routed experts a token meets *here*: of its ``num_experts_per_tok``
choices among ``n_routed_experts_published`` the share ``n_routed_experts /
n_routed_experts_published`` in expectation (a fifth of an expert at 8 of
320, top-8). The head over the held vocabulary; no embedding gather. Plus
the causal attention of the GQA layers and the recurrence of the KDA layers
(``flops_kda.recurrence_per_token``). The short convolutions, the norms and
the gates' products are no matmuls and count for nothing, and neither do the
rows that pad a tile-aligned dispatch to its static bound."""
from __future__ import annotations

from .flops_kda import recurrence_per_token
from .flops_kimi import kda_matmul_params  # the same mixer at other widths


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] of the layers kept (the source counts them from 0)."""
    return [
        ("attn" if i in cfg["gqa_layers"] else "kda",
         "mlp" if i < cfg["first_k_dense_replace"] else "moe")
        for i in range(cfg["num_hidden_layers"])
    ]


def gqa_matmul_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q = h * cfg["num_attention_heads"] * d
    return (2 + bool(cfg["use_gqa_gate"])) * q + 2 * h * cfg["num_key_value_heads"] * d


def expert_layer_matmul_params(cfg: dict) -> float:
    """Router, the shared expert and the routed experts a token meets here."""
    h, expert = cfg["hidden_size"], 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    here = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_published"])
    return (h * cfg["n_routed_experts_published"]
            + cfg["n_shared_experts"] * expert + here * expert)


def solar_open2_decoder(cfg: dict, seq: int) -> float:
    h, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    kinds = layer_kinds(cfg)
    n_kda = sum(mixer == "kda" for mixer, _ in kinds)
    n_gqa = len(kinds) - n_kda
    n_moe = sum(ffn == "moe" for _, ffn in kinds)
    params = (
        n_kda * kda_matmul_params(cfg) + n_gqa * gqa_matmul_params(cfg)
        + (len(kinds) - n_moe) * 3 * h * cfg["intermediate_size"]
        + n_moe * expert_layer_matmul_params(cfg)
        + h * cfg["vocab_size"]
    )
    # Scores and weighted values, the causal half, forward and backward.
    attention = 6.0 * n_gqa * seq * cfg["num_attention_heads"] * cfg["head_dim"]
    recurrence = n_kda * lin["num_heads"] * recurrence_per_token(
        lin["head_dim"], lin["head_dim"]
    )
    return 6.0 * params + attention + recurrence
