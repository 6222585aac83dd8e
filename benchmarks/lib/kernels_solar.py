"""The Pallas kernels in Solar-Open2's step, from the source's own keys: the
two scan kernels of ``ray_tpu/ops/kda.py`` once for each KDA layer at its 64
heads of 128; the causal flash kernels once for each GQA layer at 64 q heads,
K and V repeated to them from 8; the grouped matmuls of each expert layer
over the pairs this rank holds."""
from __future__ import annotations

from .flops import FLASH_MATMULS, flash_call
from .flops_gmm import gmm_call
from .flops_kda import KDA_KERNELS, kda_call
from .flops_solar import layer_kinds
from .kernels_olmoe import GMM_CALLS_A_LAYER


def solar_open2_decoder(config: dict, traffic: dict) -> dict:
    """One device, no mesh axis splits a layer. A remat replay of a forward
    kernel is the compiler's to keep or drop, so it is not asked for.

    The grouped matmuls are counted at the pairs this rank holds in
    expectation: batch x seq x experts per token x held / published (819 a
    layer at 4,096 tokens). How many it really holds follows the routing, so
    no roofline is read from it in this cell (PERF.md, Open questions); the
    static layout bounds at every pair."""
    kinds = layer_kinds(config)
    n_kda = sum(mixer == "kda" for mixer, _ in kinds)
    n_moe = sum(ffn == "moe" for _, ffn in kinds)
    lin = config["linear_attn_config"]
    batch, seq = traffic["batch"], traffic["seq"]
    stated = {
        kernel: {
            "least": len(kinds) - n_kda,
            "call": flash_call(kernel, batch * config["num_attention_heads"],
                               seq, seq, config["head_dim"], causal=True),
        }
        for kernel in FLASH_MATMULS
    }
    for kernel in KDA_KERNELS:
        stated[kernel] = {
            "least": n_kda,
            "call": kda_call(kernel, batch * lin["num_heads"], seq,
                             lin["head_dim"], lin["head_dim"]),
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
