"""LFM2-MoE's wrong programs, for ``reference_readings_of.py --workload
lfm2-8b-a1b-l5.dropfree-4k --wrong benchmarks.tools.wrong_lfm2``: each is
another function than the model, and the readings say which of them
``correct`` refuses on the chip. ``tests/test_lfm2_model.py``
(``test_a_wrong_program_or_reference_is_refused``) holds every one of them far
from the model in float32 on the CPU, on a selection bias that is not zero and
projections of unit size: the named test for what the chip's check cannot see.
PERF.md §6 (PR 65) has the table of which sees which.

- ``system_gates_not_renormalised``: the program with the chosen experts'
  scores as the gates, not divided by their sum;
  ``system_softmax_scores``: the program with a soft-max over the router's 32
  logits where the model takes a sigmoid of each;
- ``reference_no_gate_b``: the reference convolving x~ itself, B's gate
  absent; ``reference_no_gate_c``: the convolution's output not gated by C;
  ``reference_filter_reversed``: the filter's taps reversed in time (tap 0 on
  the current token); ``reference_silu_after_conv``: a SiLU on the
  convolution's output, as ``conv_silu``'s callers have it;
  ``reference_top4_without_bias``: the 4 largest scores chosen without the
  selection bias (the same function while the bias is zero, as it is in every
  run of the cell: the chip cannot see it); ``reference_qk_norm_whole``: q and
  k normed over the whole projection's channels (OLMoE's way) where the model
  norms a head's 64; ``reference_no_rotation``: q and k not turned."""
from __future__ import annotations

import dataclasses


def programs(cfg) -> dict:
    """name -> (the program's config, the mixer's parameters it lacks)."""
    return {
        "system_gates_not_renormalised": (
            dataclasses.replace(cfg, norm_topk_prob=False),),
        "system_softmax_scores": (dataclasses.replace(cfg, router_score="softmax"),),
    }


def references(bf16) -> dict:
    """name -> (one of the reference's functions, what replaces it given the
    plain one). ``bf16`` rounds an array to bfloat16's values (not used: no
    state outlives two tokens here)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference.common import F32

    def no_gate_b(_):
        return lambda b, x: x

    def no_gate_c(_):
        return lambda c, y: y

    def filter_reversed(plain):
        return lambda w: plain(w)[::-1]

    def silu_after_conv(_):
        return jax.nn.silu

    def top4_without_bias(_):
        return lambda s, bias, k: jax.lax.top_k(s, k)

    def qk_norm_whole(_):
        def qk_normed(p, q, k, cfg):
            def whole(x, scale):
                flat = x.reshape(x.shape[0], -1)
                var = jnp.mean(flat * flat, axis=-1, keepdims=True)
                return (flat * jax.lax.rsqrt(var + cfg["norm_eps"])).reshape(
                    x.shape) * scale.astype(F32)

            return whole(q, p["q_norm"]["scale"]), whole(k, p["k_norm"]["scale"])

        return qk_normed

    def no_rotation(_):
        return lambda q, k, cfg: (q, k)

    return {
        "reference_no_gate_b": ("gate_in", no_gate_b),
        "reference_no_gate_c": ("gate_out", no_gate_c),
        "reference_filter_reversed": ("taps", filter_reversed),
        "reference_silu_after_conv": ("activation", silu_after_conv),
        "reference_top4_without_bias": ("chosen", top4_without_bias),
        "reference_qk_norm_whole": ("qk_normed", qk_norm_whole),
        "reference_no_rotation": ("turned", no_rotation),
    }
