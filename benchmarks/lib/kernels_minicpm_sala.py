"""The Pallas kernels in MiniCPM-SALA's step, from the source's own keys:
for each ``minicpm4`` layer the three sparse kernels of
``ray_tpu/ops/attention.py`` at ``num_attention_heads`` over
``num_key_value_heads`` K/V heads where the sequence is longer than
``dense_len``, the causal flash kernels where it is not; for each
``lightning-attn`` layer the two scan kernels of ``ray_tpu/ops/kda.py``
``chunk_lightning`` at ``lightning_nh`` heads of ``lightning_head_dim``."""
from __future__ import annotations

from .flops import FLASH_MATMULS, flash_call
from .flops_minicpm_sala import (
    LIGHTNING_KERNELS, SPARSE_KERNELS, is_dense, layer_kinds, lightning_call,
    sparse_call,
)


def minicpm_sala_decoder(config: dict, traffic: dict) -> dict:
    """One device, no mesh axis splits a layer. A remat replay of a forward
    kernel is the compiler's to keep or drop, so it is not asked for. What a
    sparse call needs is counted at the attended pairs, K and V at their own
    heads, whatever the kernels do with a tile."""
    kinds = layer_kinds(config)
    n_sparse = kinds.count("sparse")
    batch, seq, d = traffic["batch"], traffic["seq"], config["head_dim"]
    heads, sel = config["num_attention_heads"], config["sparse_config"]
    if is_dense(config, seq):
        stated = {
            kernel: {"least": n_sparse,
                     "call": flash_call(kernel, batch * heads, seq, seq, d, causal=True)}
            for kernel in FLASH_MATMULS
        }
    else:
        stated = {
            kernel: {"least": n_sparse,
                     "call": sparse_call(
                         kernel, batch * heads, batch * config["num_key_value_heads"],
                         seq, sel["topk"], sel["block_size"], d)}
            for kernel in SPARSE_KERNELS
        }
    for kernel in LIGHTNING_KERNELS:
        stated[kernel] = {
            "least": len(kinds) - n_sparse,
            "call": lightning_call(kernel, batch * config["lightning_nh"], seq,
                                   config["lightning_head_dim"],
                                   config["lightning_head_dim"]),
        }
    return stated
