"""Required FLOPs per token of Xing4.0-29B-A4B's decoder as one
expert-parallel rank holds it, from the source's own keys.

6 x the matmul parameters a token passes through. A layer: the MLA mixer (q
through its latent: down to ``q_lora_rank``, up to heads x (nope + pe); the
kv latent's down- and up-projection; o) and the two hyper-connections' maps
(Phi, hc_mult x hidden by 2 hc_mult + hc_mult^2, each); the dense SwiGLU of
the leading layers or, in an expert layer, the router at its published width
(``n_routed_experts``), the shared expert, and the routed experts a token
meets *here*: of its ``num_experts_per_tok`` choices the share ``num_experts /
n_routed_experts`` in expectation (one expert at 16 of 64, top-4). The
multi-token-prediction module is required work, the training objective's: its
2 hidden x hidden projection, one more expert layer, and a second pass of the
head. The head over the held vocabulary, once for each of the 1 +
``num_nextn_predict_layers`` predictions; no embedding gather. Plus the causal
attention of every layer, the module's included, at q/k heads of nope + pe and
v heads of ``v_head_dim``. The rotation, the norms, Sinkhorn's iterations and
the streams' weighted sums are no matmuls and count for nothing here
(``flops_hc.py`` counts the bytes they need)."""
from __future__ import annotations


def mla_matmul_params(cfg: dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, pe, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * q_rank + q_rank * heads * (nope + pe) + h * (kv_rank + pe)
            + kv_rank * heads * (nope + dv) + heads * dv * h)


def connection_matmul_params(cfg: dict) -> int:
    """One hyper-connection's Phi."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (2 * n + n * n)


def expert_layer_matmul_params(cfg: dict) -> float:
    """Router, shared experts and the routed experts a token meets here."""
    h, expert = cfg["hidden_size"], 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    here = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["n_routed_experts"]
    return h * cfg["n_routed_experts"] + cfg["n_shared_experts"] * expert + here * expert


def layer_counts(cfg: dict) -> tuple:
    """(layers with a mixer, dense among them, expert layers), the module's
    one among the first and the last."""
    layers, further = cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"]
    n_dense = min(cfg["first_k_dense_replace"], layers)
    return layers + further, n_dense, layers - n_dense + further


def matmul_params(cfg: dict) -> float:
    h = cfg["hidden_size"]
    mixers, n_dense, n_moe = layer_counts(cfg)
    further = cfg["num_nextn_predict_layers"]
    return (
        mixers * (mla_matmul_params(cfg) + 2 * connection_matmul_params(cfg))
        + n_dense * 3 * h * cfg["intermediate_size"]
        + n_moe * expert_layer_matmul_params(cfg)
        + further * 2 * h * h
        + (1 + further) * h * cfg["vocab_size"]
    )


def attention_per_token(cfg: dict, seq: int) -> float:
    """Scores and weighted values of every layer, the causal half, forward
    and backward."""
    mixers, _, _ = layer_counts(cfg)
    return 3.0 * mixers * seq * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    )


def xing4_decoder(cfg: dict, seq: int) -> float:
    return 6.0 * matmul_params(cfg) + attention_per_token(cfg, seq)
