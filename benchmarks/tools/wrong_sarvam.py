"""sarvam-105b's wrong programs, for ``reference_readings_of.py --wrong
benchmarks.tools.wrong_sarvam``: each has to read far from the reference.

- ``system_no_mscale``: the program with YaRN's table and the softmax scale
  without ``mscale`` squared; ``system_no_qk_norm``: without the per-head
  norm of q and k; ``system_no_rotation``: nothing rotated;
  ``system_no_shared_expert``, ``system_no_scaling``: without the shared
  expert, and with the gates not multiplied by 2.5;
- ``reference_unrotated_key``: the reference with q's 64-wide part rotated
  and the shared key's not; ``reference_router_bf16``: the reference with
  the router's matmul and sigmoid rounded to bfloat16."""
from __future__ import annotations

import dataclasses


def programs(cfg) -> dict:
    """name -> (the program's config, the mixer's parameters it lacks)."""
    without_mscale = dataclasses.replace(cfg, rope_scaling=dataclasses.replace(
        cfg.rope_scaling, mscale=0.0, mscale_all_dim=0.0))
    return {
        "system_no_mscale": (without_mscale,),
        "system_no_qk_norm": (
            dataclasses.replace(cfg, qk_head_norm=False), ("q_norm", "k_norm")),
        "system_no_rotation": (dataclasses.replace(cfg, mla_rope=False),),
        "system_no_shared_expert": (dataclasses.replace(cfg, num_shared_experts=0),),
        "system_no_scaling": (dataclasses.replace(cfg, routed_scaling_factor=1.0),),
    }


def references(bf16) -> dict:
    """name -> (one of the reference's functions, what replaces it given the
    plain one). ``bf16`` rounds an array to bfloat16's values."""
    import jax
    import jax.numpy as jnp

    def unrotated_key(plain):
        calls = []

        def rotate(x, c):  # qkv rotates q's part, then k's
            calls.append(x)
            return plain(x, c) if len(calls) % 2 else x

        return rotate

    def router_bf16(plain):
        def router_gates(p, x, c):
            sigmoid = jax.nn.sigmoid
            jax.nn.sigmoid = lambda logits: bf16(sigmoid(bf16(logits)))
            try:
                rounded = {**p, "router": {"kernel": bf16(p["router"]["kernel"].astype(jnp.float32))}}
                return plain(rounded, bf16(x), c)
            finally:
                jax.nn.sigmoid = sigmoid

        return router_gates

    return {
        "reference_unrotated_key": ("rotate", unrotated_key),
        "reference_router_bf16": ("router_gates", router_bf16),
    }
