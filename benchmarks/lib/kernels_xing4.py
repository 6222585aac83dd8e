"""The Pallas kernels in Xing4.0-29B-A4B's step, from the source's own keys:
the flash kernels once for each layer, the multi-token-prediction module's
included, at q/k heads of nope + pe and v heads of ``v_head_dim``; the grouped
matmuls of each expert layer over the pairs this rank holds. The
hyper-connections are XLA operations and state no kernel."""
from __future__ import annotations

from .flops import FLASH_MATMULS, flash_call
from .flops_gmm import gmm_call
from .flops_xing4 import layer_counts
from .kernels_olmoe import GMM_CALLS_A_LAYER


def xing4_decoder(config: dict, traffic: dict) -> dict:
    """One device, no mesh axis splits a layer. A remat replay of a forward
    kernel is the compiler's to keep or drop, so it is not asked for.

    The grouped matmuls are counted at the pairs this rank holds in
    expectation: batch x seq x experts per token x held / published (4,096 a
    layer at 4,096 tokens). How many it really holds follows the routing, so
    no roofline is read from it in this cell; the static layout bounds at
    every pair."""
    mixers, _, n_moe = layer_counts(config)
    batch, seq = traffic["batch"], traffic["seq"]
    stated = {
        kernel: {
            "least": mixers,
            "call": flash_call(
                kernel, batch * config["num_attention_heads"], seq, seq,
                config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
                causal=True, d_v=config["v_head_dim"]),
        }
        for kernel in FLASH_MATMULS
    }
    pairs = (batch * seq * config["num_experts_per_tok"]
             * config["num_experts"] // config["n_routed_experts"])
    for kernel, calls in GMM_CALLS_A_LAYER.items():
        stated[kernel] = {
            "least": calls * n_moe,
            "call": gmm_call(kernel, pairs, config["hidden_size"],
                             config["moe_intermediate_size"],
                             config["num_experts"]),
        }
    return stated
