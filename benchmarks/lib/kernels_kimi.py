"""The Pallas kernels in Kimi-Linear's step, from the source's own keys: the
flash kernels once for each MLA layer at q/k heads of nope + pe and v heads
of ``v_head_dim``; the two scan kernels of ``ray_tpu/ops/kda.py`` once for
each KDA layer; the grouped matmuls of each expert layer over the pairs this
rank holds."""
from __future__ import annotations

from .flops import FLASH_MATMULS, flash_call
from .flops_gmm import gmm_call
from .flops_kda import KDA_KERNELS, kda_call
from .flops_kimi import layer_kinds
from .kernels_olmoe import GMM_CALLS_A_LAYER


def kimi_linear_decoder(config: dict, traffic: dict) -> dict:
    """One device, no mesh axis splits a layer. A remat replay of a forward
    kernel is the compiler's to keep or drop, so it is not asked for.

    The grouped matmuls are counted at the pairs this rank holds in
    expectation: batch x seq x experts per token x held / published. How many
    it really holds follows the routing (one frequent token sends all its
    copies to the same experts), so a floor stated from this can read over
    100% and no roofline is read from it in this cell (PERF.md, Open
    questions); the static layout bounds at every pair."""
    kinds = layer_kinds(config)
    n_kda = sum(mixer == "kda" for mixer, _ in kinds)
    n_moe = sum(ffn == "moe" for _, ffn in kinds)
    lin = config["linear_attn_config"]
    batch, seq = traffic["batch"], traffic["seq"]
    stated = {
        kernel: {
            "least": len(kinds) - n_kda,
            "call": flash_call(
                kernel, batch * config["num_attention_heads"], seq, seq,
                config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
                causal=True, d_v=config["v_head_dim"]),
        }
        for kernel in FLASH_MATMULS
    }
    for kernel in KDA_KERNELS:
        stated[kernel] = {
            "least": n_kda,
            "call": kda_call(kernel, batch * lin["num_heads"], seq,
                             lin["head_dim"], lin["head_dim"]),
        }
    pairs = (batch * seq * config["num_experts_per_token"]
             * config["num_experts"] // config["num_experts_published"])
    for kernel, calls in GMM_CALLS_A_LAYER.items():
        stated[kernel] = {
            "least": calls * n_moe,
            "call": gmm_call(kernel, pairs, config["hidden_size"],
                             config["moe_intermediate_size"],
                             config["num_experts"]),
        }
    return stated
