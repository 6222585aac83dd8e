"""Laguna's wrong programs, for ``reference_readings_of.py --wrong
benchmarks.tools.wrong_laguna``: each has to read far from the reference.

- ``system_no_window``: the program with the sliding layers' window dropped
  (every key up to the row's own, through the causal kernels);
  ``system_no_attention_factor``: the full layers' cos and sin not times
  YaRN's ``attention_factor``; ``system_no_gate``: the attention's output
  not gated;
- ``reference_wrong_half``: the reference with the second half of a full
  layer's head turned and the first passed (a sliding layer's whole head
  turns either way); ``reference_window_plus_one``:
  the reference with a window of one key more; ``reference_router_bf16``:
  the reference with the router's matmul and sigmoid rounded to bfloat16."""
from __future__ import annotations

import dataclasses


def programs(cfg) -> dict:
    """name -> (the program's config, the mixer's parameters it lacks)."""
    def every_kind(**changes):
        return dataclasses.replace(cfg, attentions=tuple(
            (name, dataclasses.replace(kind, **changes))
            for name, kind in cfg.attentions))

    return {
        "system_no_window": (every_kind(window=None),),
        "system_no_attention_factor": (every_kind(attention_factor=1.0),),
        "system_no_gate": (dataclasses.replace(cfg, gating=False), ("g_proj",)),
    }


def references(bf16) -> dict:
    """name -> (one of the reference's functions, what replaces it given the
    plain one). ``bf16`` rounds an array to bfloat16's values."""
    import jax
    import jax.numpy as jnp

    def wrong_half(plain):
        def rotate(x, rope):  # the halves swapped, turned, swapped back
            half = x.shape[-1] // 2
            return jnp.roll(plain(jnp.roll(x, half, axis=-1), rope), half, axis=-1)

        return rotate

    def window_plus_one(plain):
        def window_of(c, layer):
            window = plain(c, layer)
            return None if window is None else window + 1

        return window_of

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
        "reference_wrong_half": ("rotate", wrong_half),
        "reference_window_plus_one": ("window_of", window_plus_one),
        "reference_router_bf16": ("router_gates", router_bf16),
    }
