"""Required FLOPs per token of Kimi-Linear's decoder as one expert-parallel
rank holds it, from the source's own keys.

6 x the matmul parameters a token passes through: each kept layer's mixer
(KDA: q, k, v, o, the two low-rank gates of the head dim's width, beta; MLA:
q, the latent's down- and up-projection, o), the dense SwiGLU of the leading
layers, and in an expert layer the router at its published width, the shared
expert, and the routed experts a token meets *here*: of its
``num_experts_per_token`` choices among ``num_experts_published`` the share
``num_experts / num_experts_published`` in expectation (0.5 of an expert at
16 of 256, top-8). The head over the held vocabulary; no embedding gather.
Plus the causal attention of the MLA layers at q/k heads of nope + pe and v
heads of ``v_head_dim``, and the recurrence of the KDA layers
(``flops_kda.recurrence_per_token``). The short convolutions (4 taps a
channel), norms and gates are no matmuls and count for nothing, and neither
do the rows that pad a tile-aligned dispatch to its static bound."""
from __future__ import annotations

from .flops_kda import recurrence_per_token


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] of the layers kept (the source counts them from 1)."""
    lin = cfg["linear_attn_config"]
    return [
        ("kda" if i + 1 in lin["kda_layers"] else "mla",
         "moe" if i >= cfg["first_k_dense_replace"]
         and i % cfg["moe_layer_freq"] == 0 else "mlp")
        for i in range(cfg["num_hidden_layers"])
    ]


def kda_matmul_params(cfg: dict) -> int:
    h, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    low_rank = h * d + d * heads * d  # the decay's, and the output gate's
    return 4 * h * heads * d + 2 * low_rank + h * heads


def mla_matmul_params(cfg: dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, pe, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    return (h * heads * (nope + pe) + h * (rank + pe)
            + rank * heads * (nope + dv) + heads * dv * h)


def expert_layer_matmul_params(cfg: dict) -> float:
    """Router, shared experts and the routed experts a token meets here."""
    h, expert = cfg["hidden_size"], 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    here = (cfg["num_experts_per_token"] * cfg["num_experts"]
            / cfg["num_experts_published"])
    return (h * cfg["num_experts_published"]
            + cfg["num_shared_experts"] * expert + here * expert)


def kimi_linear_decoder(cfg: dict, seq: int) -> float:
    h, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    kinds = layer_kinds(cfg)
    n_kda = sum(mixer == "kda" for mixer, _ in kinds)
    n_mla = len(kinds) - n_kda
    n_moe = sum(ffn == "moe" for _, ffn in kinds)
    params = (
        n_kda * kda_matmul_params(cfg) + n_mla * mla_matmul_params(cfg)
        + (len(kinds) - n_moe) * 3 * h * cfg["intermediate_size"]
        + n_moe * expert_layer_matmul_params(cfg)
        + h * cfg["vocab_size"]
    )
    # Scores and weighted values, the causal half, forward and backward.
    attention = 3.0 * n_mla * seq * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    )
    recurrence = n_kda * lin["num_heads"] * recurrence_per_token(
        lin["head_dim"], lin["head_dim"]
    )
    return 6.0 * params + attention + recurrence
