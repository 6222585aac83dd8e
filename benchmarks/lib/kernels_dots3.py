"""The Pallas kernels in dots3-note-prev's step, from the source's own keys:
the indexer kernel and the selection's three flash kernels once for each full
layer; the windowed flash kernels once for each sliding layer, at q/k heads of
256 and v heads of 128; the grouped matmuls of each expert layer over the
pairs this rank holds. (The latent kernels of ``ops/rotary.py`` that a full
layer's q and k pass are not stated: no reader prices them.)"""
from __future__ import annotations

from .flops_dots3 import (
    INDEX_KERNEL, SELECT_KERNELS, WINDOW_KERNELS, index_call, kind_of,
    layer_types, masked_call,
)
from .flops_gmm import gmm_call
from .kernels_olmoe import GMM_CALLS_A_LAYER


def dots3_note_decoder(config: dict, traffic: dict) -> dict:
    """One device, no mesh axis splits a layer. A remat replay of a forward
    kernel is the compiler's to keep or drop, so it is not asked for (the
    policy keeps what the forward kernels wrote, the chosen keys' words among
    it, so none is expected).

    A selection call is a full layer's: its held heads, K and V at them, the
    chosen pairs only (row t's min(t + 1, index_topk)): the tiles a masked
    kernel computes and discards are work the floor does not have, so the
    share reads under the chosen pairs' part of the causal half (44% at 8k).
    A windowed call is a sliding layer's at its held heads, the band's pairs.

    The grouped matmuls are counted at the pairs this rank holds in
    expectation: batch x seq x experts per token x held / published (2,048 a
    layer at 8,192 tokens). How many it really holds follows the routing, so
    no roofline is read from it in this cell."""
    kinds = layer_types(config)
    n_full = sum(kind == "full_attention" for kind in kinds)
    n_moe = len(kinds) - min(config["first_k_dense_replace"], len(kinds))
    batch, seq = traffic["batch"], traffic["seq"]
    stated = {INDEX_KERNEL: {
        "least": n_full,
        "call": index_call(batch, seq, config["index_n_heads"],
                           config["index_head_dim"]),
    }}
    for kernels, layer_type, least, bits in (
            (SELECT_KERNELS, "full_attention", n_full, True),
            (WINDOW_KERNELS, "sliding_attention", len(kinds) - n_full, False)):
        kind = kind_of(config, layer_type)
        for kernel, causal in kernels.items():
            stated[kernel] = {
                "least": least,
                "call": masked_call(
                    kernel, causal, batch * kind["num_attention_heads"], batch,
                    seq, kind["kept"],
                    kind["qk_nope_head_dim"] + kind["qk_rope_head_dim"],
                    kind["v_head_dim"], bits),
            }
    pairs = (batch * seq * config["num_experts_per_tok"]
             * config["n_routed_experts"] // config["n_routed_experts_published"])
    for kernel, calls in GMM_CALLS_A_LAYER.items():
        stated[kernel] = {
            "least": calls * n_moe,
            "call": gmm_call(kernel, pairs, config["hidden_size"],
                             config["moe_intermediate_size"],
                             config["n_routed_experts"]),
        }
    return stated
