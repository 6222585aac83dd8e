"""The Pallas kernels in Laguna's step, from the source's own keys: the
causal flash kernels once for each full-attention layer at its head count;
the windowed flash kernels (``_fwd_window_kernel`` and its two backward
kernels, ``ray_tpu/ops/attention.py``) once for each sliding layer at its
own; the grouped matmuls of each sparse layer over the pairs this rank
holds."""
from __future__ import annotations

from .flops import FLASH_MATMULS, flash_call
from .flops_gmm import gmm_call
from .flops_laguna import WINDOW_KERNELS, layers_of, window_call
from .kernels_olmoe import GMM_CALLS_A_LAYER


def _heads(layers: list, kind: str) -> int:
    """The one head count of the kept layers of ``kind``."""
    (heads,) = {h for k, h, _ in layers if k == kind}
    return heads


def laguna_decoder(config: dict, traffic: dict) -> dict:
    """One device, no mesh axis splits a layer. A remat replay of a forward
    kernel is the compiler's to keep or drop, so it is not asked for.

    A causal call is a full layer's: its q heads, K and V repeated to them.
    A windowed call is a sliding layer's: its q heads, K and V at their own
    ``num_key_value_heads``, the band's pairs T w - w (w - 1) / 2 a head.

    The grouped matmuls are counted at the pairs this rank holds in
    expectation: batch x seq x experts per token x held / published (16,384
    a layer at 16,384 tokens). How many it really holds follows the routing,
    so no roofline is read from it in this cell (PERF.md, Open questions)."""
    layers = layers_of(config)
    n_full = sum(kind == "full_attention" for kind, _, _ in layers)
    n_moe = sum(ffn == "sparse" for _, _, ffn in layers)
    batch, seq, d = traffic["batch"], traffic["seq"], config["head_dim"]
    stated = {
        kernel: {
            "least": n_full,
            "call": flash_call(kernel, batch * _heads(layers, "full_attention"),
                               seq, seq, d, causal=True),
        }
        for kernel in FLASH_MATMULS
    }
    for kernel in WINDOW_KERNELS:
        stated[kernel] = {
            "least": len(layers) - n_full,
            "call": window_call(
                kernel, batch * _heads(layers, "sliding_attention"),
                batch * config["num_key_value_heads"], seq,
                config["sliding_window"], d),
        }
    pairs = (batch * seq * config["num_experts_per_tok"]
             * config["num_experts"] // config["num_experts_published"])
    for kernel, calls in GMM_CALLS_A_LAYER.items():
        stated[kernel] = {
            "least": calls * n_moe,
            "call": gmm_call(kernel, pairs, config["hidden_size"],
                             config["moe_intermediate_size"],
                             config["num_experts"]),
        }
    return stated
