"""Plain reference of a dense pre-norm decoder (Mistral-7B's layer): RMSNorm,
rotary grouped-query causal attention, SwiGLU MLP, untied output head.

``forward`` takes the system's parameter tree (flax names) and the
configuration file's own keys, and returns float32 logits of the last
``last`` positions of one sequence."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, attention, gated_mlp, rms_norm

# Per-position error ||system - reference|| / ||reference|| over the
# vocabulary. The system holds weights and activations in bfloat16 (8
# mantissa bits, 2**-9 = 0.002 a rounding) and rounds a few dozen times on
# the way through four layers; the reference keeps float32. It depends on the
# seed's weights: over 108 chip runs with different seeds the median position
# read 0.003 to 0.014 and the worst 0.005 to 0.018 (PERF.md, Findings,
# PR 22), so this is 1.6 times the worst seen. An 8-bit float or int8 path
# (2**-4 to 2**-7 a rounding, four to thirty times bfloat16's) puts a typical
# seed's worst position at 0.04 or more. Every position has to pass: a dense
# model has no discontinuity.
TOLERANCE = {"per_position_rel_err": 0.03, "min_share_within": 1.0}


def forward(params, ids, cfg: dict, last: int):
    p = params["params"]
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = p["embed_tokens"]["embedding"].astype(F32)[ids]  # [T, hidden]
        for i in range(cfg["num_hidden_layers"]):
            layer = p[f"layers_{i}"]
            x = x + attention(
                layer["attn"], rms_norm(x, layer["input_norm"]["scale"], eps), cfg
            )
            mlp = layer["mlp"]
            x = x + gated_mlp(
                rms_norm(x, layer["post_attn_norm"]["scale"], eps),
                mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                mlp["down_proj"]["kernel"],
            )
        x = rms_norm(x[-last:], p["final_norm"]["scale"], eps)
        return x @ p["lm_head"]["kernel"].astype(F32)
