"""Solar-Open2's wrong programs, for ``reference_readings_of.py --wrong
benchmarks.tools.wrong_solar``: each is another function than the model, and
the readings say which of them ``correct`` refuses on the chip.

- ``system_beta_undoubled``: the program with KDA's write strength
  sigmoid(W_b x), in (0, 1), where the model doubles it;
  ``system_no_gate``: the GQA layer's output not gated;
  ``system_rotated``: q and k of the GQA layer rotated (the plain table at
  ``rope_theta`` over the whole head), where the model turns nothing;
- ``reference_gate_per_head``: the reference with one gate value a head, the
  mean over the head's channels of W_g's columns, where the model has one a
  channel; ``reference_router_of_held``: the reference with a router that
  scores the 8 held experts alone (the first 8 of its 320 columns) and so
  sends every pair here; ``reference_state_bf16``: the reference with KDA's
  state rounded to bfloat16 after every token."""
from __future__ import annotations

import dataclasses


def programs(cfg) -> dict:
    """name -> (the program's config, the mixer's parameters it lacks)."""
    return {
        "system_beta_undoubled": (
            dataclasses.replace(cfg, kda_allow_neg_eigval=False),),
        "system_no_gate": (
            dataclasses.replace(cfg, use_gqa_gate=False), ("g_proj",)),
        "system_rotated": (dataclasses.replace(cfg, use_rope=True),),
    }


def references(bf16) -> dict:
    """name -> (one of the reference's functions, what replaces it given the
    plain one). ``bf16`` rounds an array to bfloat16's values."""
    import jax
    import jax.numpy as jnp

    def gate_per_head(plain):
        def gqa_gate(p, x, o):
            kernel = p["g_proj"]["kernel"].astype(jnp.float32)
            a_head = jnp.broadcast_to(kernel.mean(-1, keepdims=True), kernel.shape)
            return plain({**p, "g_proj": {"kernel": a_head}}, x, o)

        return gqa_gate

    def router_of_held(plain):
        def router_gates(p, x, c):
            held = p["router"]["kernel"][:, :c["n_routed_experts"]]
            return plain({**p, "router": {"kernel": held}}, x, c)

        return router_gates

    def state_bf16(_):
        def gated_delta_rule(q, k, v, a, beta):
            def token(S, x):  # the reference's step, its state rounded
                q, k, v, a, beta = x
                S = a[:, :, None] * S
                S = S + beta[:, None, None] * jnp.einsum(
                    "hi,hv->hiv", k, v - jnp.einsum("hjv,hj->hv", S, k))
                S = bf16(S)
                return S, jnp.einsum("hiv,hi->hv", S, q)

            zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
            S, o = jax.lax.scan(token, zero, (q, k, v, a, beta))
            return o, S

        return gated_delta_rule

    return {
        "reference_gate_per_head": ("gqa_gate", gate_per_head),
        "reference_router_of_held": ("router_gates", router_of_held),
        "reference_state_bf16": ("gated_delta_rule", state_bf16),
    }
