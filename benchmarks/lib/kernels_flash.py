"""The Pallas kernels in the step of a decoder whose every layer is softmax
attention with one head dim, through ``ray_tpu/ops/attention.py``.

A configuration names its function as ``"kernels":
"benchmarks.lib.kernels_<family>:<name>"``. ``name(config, traffic)`` takes
the configuration file's own keys and the traffic file's (its ``mesh`` among
them) and returns ``{kernel name: {"least": n, "call": (FLOPs, HBM bytes)}}``:
the lowered step must hold each named kernel ``n`` times at least
(``checks.decide``'s ``pallas_kernels``), and one executed call of it needs
that much (the ``kernel.*_roofline`` readers). The harness and the readers
read no model key; a new architecture brings a file of its own.
"""
from __future__ import annotations

from .flops import FLASH_MATMULS, flash_call


def flash_every_layer(config: dict, traffic: dict) -> dict:
    """The flash forward and both backward kernels once a layer (the remat
    replay of the forward is the compiler's to keep or drop, so it is not
    asked for). Every call takes a device's rows x heads at the one head dim;
    k and v arrive repeated to q's heads. On a mesh with a seq axis a call is
    one [T/seq, T/seq] block of the ring and is counted as a causal one:
    exact for the diagonal block, and the average over the devices for the
    others, which half of the devices need whole and the other half not at
    all."""
    mesh = traffic.get("mesh", {})
    rows = traffic["batch"] // (mesh.get("data", 1) * mesh.get("fsdp", 1))
    heads = config["num_attention_heads"] // mesh.get("tensor", 1)
    block = traffic["seq"] // mesh.get("seq", 1)
    return {
        kernel: {
            "least": config["num_hidden_layers"],
            "call": flash_call(kernel, rows * heads, block, block,
                               config["head_dim"], causal=True),
        }
        for kernel in FLASH_MATMULS
    }
