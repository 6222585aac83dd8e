"""The Pallas kernels in Olmo-Hybrid's step, from the source's own keys: the
causal flash kernels once for each full layer at ``num_attention_heads`` of
``head_dim``; the two scalar-decay scan kernels of ``ray_tpu/ops/kda.py``
once for each linear layer at its key heads of ``linear_key_head_dim`` and
value heads of ``linear_value_head_dim``; the convolution kernels behind
their jitted entries, which a linear layer calls twice (q with k, and v)."""
from __future__ import annotations

from .flops import FLASH_MATMULS, flash_call
from .flops_gdn import GDN_KERNELS, gdn_call
from .flops_olmo_hybrid import layer_kinds

CONV_KERNELS = ("_conv_fwd_kernel", "_conv_bwd_kernel")


def conv_call(kernel: str, rows: int, channels: int, taps: int) -> tuple:
    """(FLOPs, HBM bytes) of the smaller of a layer's two passes (v's, whose
    output is two bytes an element): a multiply and an add a tap forward; the
    backward makes the pre-activation again, then a tap's product for the
    projection's cotangent and one for the filter's. The float32 projection is
    read once and, backward, its cotangent written once."""
    if kernel == "_conv_fwd_kernel":
        return 2.0 * taps * rows * channels, float(rows * channels * (4 + 2))
    return 6.0 * taps * rows * channels, float(rows * channels * (4 + 2 + 4))


def olmo_hybrid_decoder(config: dict, traffic: dict) -> dict:
    """One device, no mesh axis splits a layer. A remat replay of a forward
    kernel is the compiler's to keep or drop, so it is not asked for. The
    convolution kernels sit behind jitted entries, so the lowered text holds
    a body once an output dtype (q with k float32, v bfloat16) whatever the
    number of layers: two of each at least."""
    kinds = layer_kinds(config)
    n_gdn = kinds.count("gdn")
    batch, seq = traffic["batch"], traffic["seq"]
    stated = {
        kernel: {
            "least": len(kinds) - n_gdn,
            "call": flash_call(kernel, batch * config["num_attention_heads"],
                               seq, seq, config["head_dim"], causal=True),
        }
        for kernel in FLASH_MATMULS
    }
    for kernel in GDN_KERNELS:
        stated[kernel] = {
            "least": n_gdn,
            "call": gdn_call(kernel, batch * config["linear_num_key_heads"], seq,
                             config["linear_key_head_dim"],
                             config["linear_value_head_dim"]),
        }
    channels = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    for kernel in CONV_KERNELS:
        stated[kernel] = {
            "least": 2 if n_gdn else 0,
            "call": conv_call(kernel, batch * seq, channels,
                              config["linear_conv_kernel_dim"]),
        }
    return stated
