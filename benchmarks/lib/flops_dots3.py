"""Required FLOPs per token of dots3-note-prev's decoder as one rank holds it,
and what one call of its new kernels needs, from the source's own keys.

6 x the matmul parameters a token passes through: each kept layer's latent
mixer at the heads held of its kind (q's down-projection and its
up-projection to the held heads, the kv latent's down- and up-projection, the
gate's [hidden, heads], o at the held heads); the dense SwiGLU of the leading
layers; in an expert layer the router at its published width, the shared
expert, and the routed experts a token meets *here*: of its
``num_experts_per_tok`` choices among ``n_routed_experts_published`` the share
``n_routed_experts / n_routed_experts_published`` in expectation (a quarter of
an expert at 8 of 256, top-8). The head over the held vocabulary; no embedding
gather. The indexer of a full layer is forward only (nothing of it is
differentiated): 2 x its three projections, whole on every rank, and 2 FLOPs
an index head, channel and (row, key <= row) pair: it has to score every key
to choose. Plus each layer's attention at its held heads over the pairs it
attends and no other: a full layer's row t at min(t + 1, ``index_topk``) keys
(the chosen pairs only: what a masked kernel computes and discards is not
required), a sliding layer's at min(t + 1, ``sliding_window_size``). The
rotation, the norms, the gates' products and the threshold are no matmuls and
count for nothing, and neither do the rows that pad a tile-aligned dispatch."""
from __future__ import annotations

from .flops import FLASH_MATMULS

INDEX_KERNEL = "_index_kernel"
# The selection's and the band's kernels of ``ray_tpu/ops/attention.py`` and,
# of each, the causal kernel whose [T, T] matmuls it has.
SELECT_KERNELS = {"_fwd_select_kernel": "_fwd_kernel",
                  "_bwd_dkv_select_kernel": "_bwd_dkv_kernel",
                  "_bwd_dq_select_kernel": "_bwd_dq_kernel"}
WINDOW_KERNELS = {"_fwd_window_kernel": "_fwd_kernel",
                  "_bwd_dkv_window_kernel": "_bwd_dkv_kernel",
                  "_bwd_dq_window_kernel": "_bwd_dq_kernel"}


def kept_pairs(seq: int, kept: int) -> float:
    """(row, key) pairs of one head where row t attends min(t + 1, kept)
    keys: a band's, and a top-``kept`` selection's."""
    w = min(kept, seq)
    return seq * w - w * (w - 1) / 2


def kind_of(cfg: dict, layer_type: str) -> dict:
    """The widths of a layer's mixer: the plain keys for a full layer, those
    with ``swa_`` for a sliding one."""
    pre = "" if layer_type == "full_attention" else "swa_"
    out = {k: cfg[pre + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")}
    out["kept"] = cfg["sliding_window_size"] if pre else cfg["index_topk"]
    out["gate"] = cfg[pre + "attention_gate_type"] is not None
    return out


def layer_types(cfg: dict) -> list:
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def mixer_matmul_params(cfg: dict, kind: dict) -> int:
    h, heads = cfg["hidden_size"], kind["num_attention_heads"]
    nope, pe, dv = kind["qk_nope_head_dim"], kind["qk_rope_head_dim"], kind["v_head_dim"]
    q_rank, rank = kind["q_lora_rank"], kind["kv_lora_rank"]
    return (h * q_rank + q_rank * heads * (nope + pe) + h * (rank + pe)
            + rank * heads * (nope + dv) + heads * dv * h
            + (h * heads if kind["gate"] else 0))


def indexer_matmul_params(cfg: dict) -> int:
    heads, d = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * heads * d + cfg["hidden_size"] * (d + heads)


def expert_layer_matmul_params(cfg: dict) -> float:
    """Router, the shared expert and the routed experts a token meets here."""
    h, expert = cfg["hidden_size"], 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    here = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_published"])
    return (h * cfg["n_routed_experts_published"]
            + cfg["n_shared_experts"] * expert + here * expert)


def dots3_note_decoder(cfg: dict, seq: int) -> float:
    h = cfg["hidden_size"]
    params, forward_only, attention = h * cfg["vocab_size"], 0.0, 0.0
    for i, layer_type in enumerate(layer_types(cfg)):
        kind = kind_of(cfg, layer_type)
        params += mixer_matmul_params(cfg, kind)
        params += (3 * h * cfg["intermediate_size"]
                   if i < cfg["first_k_dense_replace"]
                   else expert_layer_matmul_params(cfg))
        # Scores and weighted values, forward and backward: 2 FLOPs x 3 a
        # (row, key) pair and channel of q/k and of v; pairs a token.
        pairs = kept_pairs(seq, kind["kept"]) / seq
        attention += 6.0 * pairs * kind["num_attention_heads"] * (
            kind["qk_nope_head_dim"] + kind["qk_rope_head_dim"] + kind["v_head_dim"])
        if layer_type == "full_attention":
            forward_only += 2.0 * indexer_matmul_params(cfg) + (
                2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]
                * kept_pairs(seq, seq) / seq)
    return 6.0 * params + forward_only + attention


def index_call(batch: int, seq: int, heads: int, d: int,
               itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one call of the indexer kernel needs: every index
    head's products over the (row, key <= row) pairs; the index queries, the
    one index key a token and the float32 weights in, a bit a pair out."""
    flops = 2.0 * batch * heads * d * kept_pairs(seq, seq)
    return flops, float(batch * seq * ((heads + 1) * d * itemsize + heads * 4)
                        + batch * seq * seq / 8)


def masked_call(kernel: str, causal: str, bh: int, batch: int, seq: int,
                kept: int, d: int, d_v: int, bits: bool,
                itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one call of a flash kernel under a mask that keeps
    min(t + 1, ``kept``) keys a row needs over ``bh`` heads (K and V at q's
    heads) of ``seq`` rows: its matmuls over the kept pairs and no other, q
    and k at ``d`` channels and v at ``d_v``; every operand and result moved
    once, the float32 log-sum-exp written by the forward, it and the rows'
    delta read by each backward kernel, and where the mask is ``bits`` a bit
    a (row, key) pair read once a batch row."""
    n_qk, n_v = FLASH_MATMULS[causal]
    flops = 2.0 * bh * kept_pairs(seq, kept) * (n_qk * d + n_v * d_v)
    qk_rows, v_rows, f32_rows = {
        "_fwd_kernel": (2, 2, 1),      # q, k in; v in, o out; lse out
        "_bwd_dkv_kernel": (3, 3, 2),  # q, k in, dk out; v, do in, dv out
        "_bwd_dq_kernel": (3, 2, 2),   # q, k in, dq out; v, do in
    }[causal]
    return flops, float(
        bh * seq * ((qk_rows * d + v_rows * d_v) * itemsize + f32_rows * 4)
        + (batch * seq * seq / 8 if bits else 0))
