"""Granite 4.0-H's wrong programs, for ``reference_readings_of.py --wrong
benchmarks.tools.wrong_granite_hybrid``: each is another function than the
model, and the readings say which of them ``correct`` refuses on the chip.
``tests/test_granite_hybrid_model.py``
(``test_a_wrong_program_or_reference_is_refused``) holds every one of them
but the rounded state far from the model in float32 on the CPU, which is the
named test for what the chip's check cannot see; the rounded state, which
float32 logits at 256 tokens do not see either, is
``tests/test_kda_op.py``'s (``test_ssd_state_is_float32``: the kernels
against the recurrence where a bfloat16 state is 100 times further).
PERF.md §6 (PR 58) has the table of which sees which.

- ``system_default_scale``: the attention layer's scores times head_dim^-1/2 =
  1/8 where the model has ``attention_multiplier`` = 1/64;
  ``system_no_filter_bias``: the convolution without its bias;
  ``system_residual_one``: each sublayer's output added as it is, not times
  0.22; ``system_embedding_one``: the embedding not times 12;
  ``system_logits_undivided``: the final norm's output not divided by 8;
- ``reference_no_softplus``: the reference with the step dt + dt_bias, no
  softplus; ``reference_decay_without_a``: the decay exp(-dl), the head's rate
  left out; ``reference_input_unscaled``: the state written with u B^T, not
  (dl u) B^T; ``reference_no_skip``: y without D u;
  ``reference_norm_before_gate``: RMSNorm(y) SiLU(z) where the model norms the
  gated y; ``reference_norm_a_head``: the gated y normed over each head's 64
  channels, not over all 4,096; ``reference_bc_a_head``: B and C of head h their
  channels weighted by a pattern of h's own, not one pair for all heads; ``reference_rotated``: q
  and k of the attention layer turned by the plain table at ``rope_theta``;
  ``reference_untied_head``: another table than the embedding as the head
  (its rows reversed); ``reference_state_bf16``: the recurrence's state
  rounded to bfloat16 after every token."""
from __future__ import annotations

import dataclasses


def programs(cfg) -> dict:
    """name -> (the program's config, the mixer's parameters it lacks)."""
    return {
        "system_default_scale": (dataclasses.replace(cfg, attention_scale=None),),
        "system_no_filter_bias": (
            dataclasses.replace(cfg, mamba_conv_bias=False), ("conv_bias",)),
        "system_residual_one": (dataclasses.replace(cfg, residual_scale=1.0),),
        "system_embedding_one": (dataclasses.replace(cfg, embed_scale=1.0),),
        "system_logits_undivided": (dataclasses.replace(cfg, logit_divisor=1.0),),
    }


def references(bf16) -> dict:
    """name -> (one of the reference's functions, what replaces it given the
    plain one). ``bf16`` rounds an array to bfloat16's values."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference.common import F32, rms_norm, rotary

    def no_softplus(_):
        return lambda p, dt: dt + p["dt_bias"].astype(F32)

    def decay_without_a(_):
        return lambda p, dl: -dl

    def input_unscaled(_):
        return lambda dl, u: u

    def no_skip(_):
        return lambda p, u: jnp.zeros_like(u)

    def norm_before_gate(_):
        return lambda p, y, z, cfg: rms_norm(
            y, p["norm"]["scale"], cfg["rms_norm_eps"]) * jax.nn.silu(z)

    def norm_a_head(_):
        def gated_norm(p, y, z, cfg):
            heads = (y * jax.nn.silu(z)).reshape(y.shape[0], cfg["mamba_n_heads"], -1)
            normed = rms_norm(heads, jnp.ones((), F32), cfg["rms_norm_eps"])
            return normed.reshape(y.shape) * p["norm"]["scale"].astype(F32)

        return gated_norm

    def bc_a_head(_):
        # Not a permutation of the channels: B and C moved alike keep B . C.
        return lambda b, heads: jnp.stack(
            [b * (1.0 + 0.5 * jnp.cos((h + 1.0) * jnp.arange(b.shape[-1])))
             for h in range(heads)], axis=1)

    def rotated(_):
        return lambda q, k, cfg: (rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"]))

    def untied_head(plain):
        return lambda p: plain(p)[:, ::-1]

    def state_bf16(_):
        return bf16

    return {
        "reference_no_softplus": ("step", no_softplus),
        "reference_decay_without_a": ("log_decay", decay_without_a),
        "reference_input_unscaled": ("written", input_unscaled),
        "reference_no_skip": ("skipped", no_skip),
        "reference_norm_before_gate": ("gated_norm", norm_before_gate),
        "reference_norm_a_head": ("gated_norm", norm_a_head),
        "reference_bc_a_head": ("shared", bc_a_head),
        "reference_rotated": ("turned", rotated),
        "reference_untied_head": ("head", untied_head),
        "reference_state_bf16": ("state", state_bf16),
    }
