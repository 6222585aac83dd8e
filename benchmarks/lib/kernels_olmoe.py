"""The Pallas kernels in OLMoE's step, from the source's own keys: the flash
kernels of every layer (``kernels_flash.flash_every_layer``) and the grouped
matmuls of its drop-free expert layer (``ray_tpu/ops/gmm.py``)."""
from __future__ import annotations

from .flops_gmm import gmm_call
from .kernels_flash import flash_every_layer

# Grouped-matmul calls of one layer: the gate, up and down projections and
# the gradients of their inputs; the gradients of their weights.
GMM_CALLS_A_LAYER = {"_gmm_kernel": 6, "_tgmm_kernel": 3}


def olmoe_decoder(config: dict, traffic: dict) -> dict:
    """One device holds every expert and computes every (token, expert) pair
    of its batch: batch x seq x experts per token rows, not the rows that pad
    an expert's segment to whole tiles. Every call of a layer has the hidden
    size and one expert's width as its two matrix dimensions, in either
    order, so all count alike."""
    pairs = traffic["batch"] * traffic["seq"] * config["num_experts_per_tok"]
    stated = flash_every_layer(config, traffic)
    for kernel, calls in GMM_CALLS_A_LAYER.items():
        stated[kernel] = {
            "least": calls * config["num_hidden_layers"],
            "call": gmm_call(kernel, pairs, config["hidden_size"],
                             config["intermediate_size"], config["num_experts"]),
        }
    return stated
