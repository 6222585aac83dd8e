"""Required FLOPs per token of sarvam-105b's decoder as one expert-parallel
rank holds it, from the source's own keys.

6 x the matmul parameters a token passes through: each kept layer's MLA
mixer (q at heads x (nope + pe), the latent's down- and up-projection, o),
the dense SwiGLU of the leading layers, and in an expert layer the router at
its published width, the shared expert, and the routed experts a token meets
*here*: of its ``num_experts_per_tok`` choices among
``num_experts_published`` the share ``num_experts / num_experts_published``
in expectation (half an expert at 8 of 128, top-8). The head over the held
vocabulary; no embedding gather. Plus the causal attention of every layer at
q/k heads of nope + pe and v heads of ``v_head_dim``. The rotation, the
norms and the gates are no matmuls and count for nothing, and neither do the
rows that pad a tile-aligned dispatch to its static bound."""
from __future__ import annotations

from .flops_kimi import mla_matmul_params  # the same mixer at other widths


def expert_layer_matmul_params(cfg: dict) -> float:
    """Router, shared experts and the routed experts a token meets here."""
    h, expert = cfg["hidden_size"], 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    here = (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["num_experts_published"])
    return (h * cfg["num_experts_published"]
            + cfg["num_shared_experts"] * expert + here * expert)


def attention_per_token(cfg: dict, seq: int) -> float:
    """Scores and weighted values of every layer, the causal half, forward
    and backward."""
    return 3.0 * cfg["num_hidden_layers"] * seq * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    )


def sarvam_mla_decoder(cfg: dict, seq: int) -> float:
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    n_dense = min(cfg["first_k_dense_replace"], layers)
    params = (
        layers * mla_matmul_params(cfg)
        + n_dense * 3 * h * cfg["intermediate_size"]
        + (layers - n_dense) * expert_layer_matmul_params(cfg)
        + h * cfg["vocab_size"]
    )
    return 6.0 * params + attention_per_token(cfg, seq)
