"""The Pallas kernels in LFM2-MoE's step, from the source's own keys: the two
gated-convolution kernels of ``ray_tpu/ops/kda.py`` ``gated_conv`` behind
their jitted entries, which a ``conv`` layer calls once; the causal flash
kernels once for each ``full_attention`` layer at ``num_attention_heads`` of
``head_dim``, K and V repeated to them; the grouped matmuls of each expert
layer over every (token, expert) pair (``ray_tpu/ops/gmm.py``). A head of 64
lanes turns by XLA's lines (``ops/rotary.py`` takes its kernel at whole vregs
of 128), so no rotation kernel is stated."""
from __future__ import annotations

from .flops import FLASH_MATMULS, flash_call
from .flops_gmm import gmm_call
from .flops_lfm2 import GATED_CONV_KERNELS, gated_conv_call, head_dim, layer_kinds
from .kernels_olmoe import GMM_CALLS_A_LAYER


def lfm2_decoder(config: dict, traffic: dict) -> dict:
    """One device holds every expert and computes every pair of its batch, as
    OLMoE's does: batch x seq x experts per token rows, not the rows that pad
    an expert's segment to whole tiles. A remat replay of a forward kernel is
    the compiler's to keep or drop, so it is not asked for. The convolution
    kernels sit behind jitted entries and every layer's call has one shape, so
    the lowered text holds a body once whatever the number of layers: one of
    each at least."""
    kinds = layer_kinds(config)
    n_attn = sum(mixer == "attn" for mixer, _ in kinds)
    n_moe = sum(ffn == "moe" for _, ffn in kinds)
    batch, seq = traffic["batch"], traffic["seq"]
    stated = {
        kernel: {
            "least": n_attn,
            "call": flash_call(kernel, batch * config["num_attention_heads"],
                               seq, seq, head_dim(config), causal=True),
        }
        for kernel in FLASH_MATMULS
    }
    pairs = batch * seq * config["num_experts_per_tok"]
    for kernel, calls in GMM_CALLS_A_LAYER.items():
        stated[kernel] = {
            "least": calls * n_moe,
            "call": gmm_call(kernel, pairs, config["hidden_size"],
                             config["moe_intermediate_size"], config["num_experts"]),
        }
    for kernel in GATED_CONV_KERNELS:
        stated[kernel] = {
            "least": 1 if len(kinds) > n_attn else 0,
            "call": gated_conv_call(kernel, batch * seq, config["hidden_size"],
                                    config["conv_L_cache"]),
        }
    return stated
