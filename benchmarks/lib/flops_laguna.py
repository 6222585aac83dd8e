"""Required FLOPs per token of Laguna's decoder as one expert-parallel rank
holds it, and what one call of a windowed flash kernel needs, from the
source's own keys.

6 x the matmul parameters a token passes through: each kept layer's q and o
at its own head count (``num_attention_heads_per_layer``), k and v at the
shared K/V heads, the gate's [hidden, heads]; the dense SwiGLU of a
``dense`` layer; in a ``sparse`` layer the router at its published width,
the shared expert, and the routed experts a token meets *here*: of its
``num_experts_per_tok`` choices among ``num_experts_published`` the share
``num_experts / num_experts_published`` in expectation (one expert at 32 of
256, top-8). The head over the held vocabulary; no embedding gather. Plus
each layer's attention at its own head count: a full layer's causal half, a
sliding layer's row i at min(i + 1, ``sliding_window``) keys. The rotation,
the norms and the gates' products are no matmuls and count for nothing, and
neither do the rows that pad a tile-aligned dispatch to its static bound."""
from __future__ import annotations

from .flops import FLASH_MATMULS

# The windowed kernels of ``ray_tpu/ops/attention.py`` and, of each, the
# causal kernel whose [T, T] matmuls it has (``flops.FLASH_MATMULS``).
WINDOW_KERNELS = {"_fwd_window_kernel": "_fwd_kernel",
                  "_bwd_dkv_window_kernel": "_bwd_dkv_kernel",
                  "_bwd_dq_window_kernel": "_bwd_dq_kernel"}


def band_pairs(seq: int, window: int) -> float:
    """(row, key) pairs of one head under a window: row i sees
    min(i + 1, window) keys."""
    w = min(window, seq)
    return seq * w - w * (w - 1) / 2


def window_call(kernel: str, bh: int, bkv: int, seq: int, window: int,
                d: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one call of a windowed flash kernel needs over
    ``bh`` q heads and ``bkv`` K/V heads of ``seq`` rows: its matmuls over
    the band's pairs and no other, every operand and result moved once (K
    and V at their own heads; the float32 log-sum-exp written by the forward,
    it and the rows' delta read by each backward kernel)."""
    n_qk, n_v = FLASH_MATMULS[WINDOW_KERNELS[kernel]]
    flops = 2.0 * bh * band_pairs(seq, window) * (n_qk + n_v) * d
    q_rows, kv_rows, f32_rows = {
        "_fwd_window_kernel": (2, 2, 1),      # q in, o out; k, v in; lse out
        "_bwd_dkv_window_kernel": (2, 4, 2),  # q, do in; k, v in, dk, dv out
        "_bwd_dq_window_kernel": (3, 2, 2),   # q, do in, dq out; k, v in
    }[kernel]
    return flops, float(
        (bh * q_rows + bkv * kv_rows) * seq * d * itemsize + bh * f32_rows * seq * 4
    )


def layers_of(cfg: dict) -> list:
    """[(layer kind, heads, ffn kind)] of the layers kept."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def expert_layer_matmul_params(cfg: dict) -> float:
    """Router, the shared expert and the routed experts a token meets here."""
    h = cfg["hidden_size"]
    here = (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["num_experts_published"])
    return (h * cfg["num_experts_published"]
            + 3 * h * cfg["shared_expert_intermediate_size"]
            + here * 3 * h * cfg["moe_intermediate_size"])


def laguna_decoder(cfg: dict, seq: int) -> float:
    h, d, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    params, attention = h * cfg["vocab_size"], 0.0
    for kind, heads, ffn in layers_of(cfg):
        params += 2 * h * d * (heads + kv) + (h * heads if cfg["gating"] else 0)
        params += (3 * h * cfg["intermediate_size"] if ffn == "dense"
                   else expert_layer_matmul_params(cfg))
        # Scores and weighted values, forward and backward: 2 matmuls x 2
        # FLOPs x 3 a (row, key) pair and channel; pairs a token.
        pairs = (seq / 2 if kind == "full_attention"
                 else band_pairs(seq, cfg["sliding_window"]) / seq)
        attention += 12.0 * pairs * heads * d
    return 6.0 * params + attention
