"""Olmo-Hybrid's wrong programs, for ``reference_readings_of.py --wrong
benchmarks.tools.wrong_olmo_hybrid``: each is another function than the
model, and the readings say which of them ``correct`` refuses on the chip.

- ``system_beta_undoubled``: the program with the write strength sigmoid(W_b
  x), in (0, 1), where the model doubles it; ``system_rotated``: q and k of
  the full layer rotated (the plain table at theta 10,000 over the whole
  head), where the model turns nothing; ``system_no_qk_norm``: the full
  layer's q and k not normed;
- ``reference_sigmoid_gate``: the reference with a sigmoid where the gated
  RMSNorm has SiLU; ``reference_no_decay``: the reference with the decay left
  out (g = 0: a delta rule that forgets nothing); ``reference_prenorm``: the
  reference with each norm before its sublayer (h = x + mixer(RMSNorm(x))),
  the same weights; ``reference_state_bf16``: the reference with the
  recurrence's state rounded to bfloat16 after every token."""
from __future__ import annotations

import dataclasses


def programs(cfg) -> dict:
    """name -> (the program's config, the mixer's parameters it lacks)."""
    return {
        "system_beta_undoubled": (
            dataclasses.replace(cfg, linear_allow_neg_eigval=False),),
        "system_rotated": (dataclasses.replace(cfg, rope_theta=10000.0),),
        "system_no_qk_norm": (
            dataclasses.replace(cfg, qk_norm=False), ("q_norm", "k_norm")),
    }


def references(bf16) -> dict:
    """name -> (one of the reference's functions, what replaces it given the
    plain one). ``bf16`` rounds an array to bfloat16's values."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference.common import gated_mlp, rms_norm

    def sigmoid_gate(_):
        return jax.nn.sigmoid

    def no_decay(plain):
        return lambda p, x: jnp.zeros_like(plain(p, x))

    def prenorm(_):
        from benchmarks.reference import olmo_hybrid_decoder as reference

        def decoder_layer(layer, x, c, i):
            eps = c["rms_norm_eps"]
            fed = rms_norm(x, layer["post_mixer_norm"]["scale"], eps)
            h = x + (reference.full_attention(layer["attn"], fed, c)
                     if reference.is_full(c, i) else reference.gdn(layer["gdn"], fed, c))
            m = layer["mlp"]
            return h + gated_mlp(
                rms_norm(h, layer["post_ffn_norm"]["scale"], eps),
                m["gate_proj"]["kernel"], m["up_proj"]["kernel"], m["down_proj"]["kernel"])

        return decoder_layer

    def state_bf16(_):
        def gated_delta_rule(q, k, v, g, beta):
            def token(S, x):  # the reference's step, its state rounded
                q, k, v, g, beta = x
                S = jnp.exp(g)[:, None, None] * S
                S = S + beta[:, None, None] * jnp.einsum(
                    "hi,hv->hiv", k, v - jnp.einsum("hjv,hj->hv", S, k))
                S = bf16(S)
                return S, jnp.einsum("hiv,hi->hv", S, q)

            zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
            S, o = jax.lax.scan(token, zero, (q, k, v, g, beta))
            return o, S

        return gated_delta_rule

    return {
        "reference_sigmoid_gate": ("out_gate", sigmoid_gate),
        "reference_no_decay": ("log_decay", no_decay),
        "reference_prenorm": ("decoder_layer", prenorm),
        "reference_state_bf16": ("gated_delta_rule", state_bf16),
    }
