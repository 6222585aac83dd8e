"""The Pallas kernels in Granite 4.0-H's step, from the source's own keys: the
causal flash kernels once for each ``attention`` layer at
``num_attention_heads`` of ``head_dim``; the two Mamba-2 scan kernels of
``ray_tpu/ops/kda.py`` ``chunk_ssd`` once for each ``mamba`` layer at
``mamba_n_heads`` heads of ``mamba_d_head`` over a state of ``mamba_d_state``;
the convolution kernels behind their jitted entries, which a mamba layer calls
once, over x, B and C together."""
from __future__ import annotations

from .flops import FLASH_MATMULS, flash_call
from .flops_granite_hybrid import (
    SSD_KERNELS, head_dim, layer_kinds, mamba_channels, ssd_call,
)
from .kernels_olmo_hybrid import CONV_KERNELS, conv_call


def granite_hybrid_decoder(config: dict, traffic: dict) -> dict:
    """One device, no mesh axis splits a layer. A remat replay of a forward
    kernel is the compiler's to keep or drop, so it is not asked for. The
    convolution kernels sit behind jitted entries and every layer's call has
    one shape and one output dtype, so the lowered text holds a body once
    whatever the number of layers: one of each at least. A convolution call is
    counted as the sibling files count theirs (float32 in, two bytes out; the
    bias's add is not counted)."""
    kinds = layer_kinds(config)
    n_mamba = kinds.count("mamba")
    batch, seq = traffic["batch"], traffic["seq"]
    stated = {
        kernel: {
            "least": len(kinds) - n_mamba,
            "call": flash_call(kernel, batch * config["num_attention_heads"],
                               seq, seq, head_dim(config), causal=True),
        }
        for kernel in FLASH_MATMULS
    }
    for kernel in SSD_KERNELS:
        stated[kernel] = {
            "least": n_mamba,
            "call": ssd_call(kernel, batch, seq, config["mamba_n_heads"],
                             config["mamba_d_head"], config["mamba_d_state"]),
        }
    channels = mamba_channels(config) + 2 * config["mamba_d_state"]
    for kernel in CONV_KERNELS:
        stated[kernel] = {
            "least": 1 if n_mamba else 0,
            "call": conv_call(kernel, batch * seq, channels, config["mamba_d_conv"]),
        }
    return stated
