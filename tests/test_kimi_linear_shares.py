"""Kimi-Linear's architecture through the program's models, on the CPU: one
rank's share of an expert layer: the ranks' shares add up to the uncut layer,
and a share is the whole layer's bounds at the cost of its pairs
(``tests/test_kimi_linear_model.py`` has the model against its reference and
says what the reference is; ``tests/kimi_linear_cases.py`` what the files
share).
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import kimi_linear_decoder as reference
from ray_tpu.models.kimi_linear import KimiLinearConfig

from kimi_linear_cases import (  # noqa: F401 - fixtures
    expert_layer, interpret, whole_layer,
)


def layer_config(cfg: KimiLinearConfig) -> dict:
    """The reference's keys for one expert layer of ``cfg``."""
    lo, hi = cfg.experts_held or (0, cfg.num_experts)
    return {
        "num_experts_published": cfg.num_experts, "num_experts": hi - lo,
        "expert_rank": lo // (hi - lo),
        "num_experts_per_token": cfg.num_experts_per_tok,
        "moe_renormalize": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "num_shared_experts": cfg.num_shared_experts,
    }


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(whole_layer):
    """Four ranks of four experts each: the routed parts they give, with
    the shared expert (which every rank computes alike) counted once, are the
    uncut reference's expert layer."""
    cfg, params, x = whole_layer
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(params, tokens, layer_config(cfg))
        shared = reference.shared_expert(params, tokens)
    total = 0.0
    for rank in range(4):
        held = (4 * rank, 4 * rank + 4)
        layer, _ = expert_layer(held)
        mine = {**params, **{k: params[k][held[0]:held[1]]
                             for k in ("w_gate", "w_up", "w_down")}}
        out = layer.apply({"params": mine}, x).reshape(-1, 32)
        # the program's share is the reference's, given the same share
        with jax.default_matmul_precision("highest"):
            want = reference.moe(mine, tokens, layer_config(layer.cfg))
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        total = total + (out - shared)
    np.testing.assert_allclose(total + shared, uncut, rtol=1e-4, atol=1e-5)
    # and the uncut layer through the program is the reference's too
    whole = expert_layer(None)[0].apply({"params": params}, x).reshape(-1, 32)
    np.testing.assert_allclose(whole, uncut, rtol=1e-4, atol=1e-5)


def test_a_routing_that_sends_every_pair_here_loses_none(whole_layer):
    """A selection bias that puts the held experts first for every token:
    all T x K pairs arrive here, the static layout holds them, and the
    share is the whole routed result."""
    cfg, params, x = whole_layer
    held = (4, 8)
    bias = np.zeros(16, np.float32)
    bias[held[0]:held[1]] = 10.0
    params = {**params, "router_bias": jnp.asarray(bias)}
    layer, _ = expert_layer(held)
    mine = {**params, **{k: params[k][held[0]:held[1]]
                         for k in ("w_gate", "w_up", "w_down")}}
    out = layer.apply({"params": mine}, x).reshape(-1, 32)
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        gates = reference.router_gates(params, tokens, layer_config(cfg))
        uncut = reference.moe(params, tokens, layer_config(cfg))
    assert (np.asarray(gates[:, held[0]:held[1]]) > 0).all()  # every pair is here
    np.testing.assert_allclose(out, uncut, rtol=1e-4, atol=1e-5)
    # gradients reach every held expert and are finite
    grads = jax.grad(lambda p: (layer.apply({"params": p}, x) ** 2).sum())(mine)
    for name in ("w_gate", "w_up", "w_down"):
        g = np.asarray(grads[name])
        assert np.isfinite(g).all() and (np.abs(g).reshape(4, -1).max(1) > 0).all()


def test_a_rank_that_no_pair_reaches_gives_the_shared_expert_alone(whole_layer):
    cfg, params, x = whole_layer
    held = (12, 16)
    bias = np.zeros(16, np.float32)
    bias[:4] = 10.0  # every token's four choices are experts 0-3
    params = {**params, "router_bias": jnp.asarray(bias)}
    layer, _ = expert_layer(held)
    mine = {**params, **{k: params[k][held[0]:held[1]]
                         for k in ("w_gate", "w_up", "w_down")}}
    out, grads = jax.value_and_grad(
        lambda p: (layer.apply({"params": p}, x) ** 2).sum())(mine)
    with jax.default_matmul_precision("highest"):
        shared = reference.shared_expert(params, x.reshape(-1, 32))
    np.testing.assert_allclose(out, (shared ** 2).sum(), rtol=1e-4)
    for name in ("w_gate", "w_up", "w_down"):
        assert not np.asarray(grads[name]).any(), name


def test_the_bias_moves_the_selection_and_not_the_gates(whole_layer):
    """Gates are the chosen experts' sigmoids renormalised, times 2.446,
    whatever the bias; which experts are chosen follows score + bias."""
    cfg, params, x = whole_layer
    tokens = x.reshape(-1, 32)
    lc = layer_config(cfg)
    gates = np.asarray(reference.router_gates(params, tokens, lc))
    np.testing.assert_allclose(gates.sum(-1), 2.446, rtol=1e-5)
    assert ((gates > 0).sum(-1) == 4).all()
    scores = np.asarray(jax.nn.sigmoid(tokens @ params["router"]["kernel"]))
    unbiased = np.asarray(reference.router_gates(
        {**params, "router_bias": jnp.zeros(16)}, tokens, lc))
    moved = (gates > 0) != (unbiased > 0)
    assert moved.any()  # the bias changed some token's experts
    chosen = gates > 0
    want = np.where(chosen, scores, 0.0)
    want = want / want.sum(-1, keepdims=True) * 2.446
    np.testing.assert_allclose(gates, want, rtol=1e-5, atol=1e-7)
    # and the program routes as the reference does
    out = expert_layer(None)[0].apply({"params": params}, x).reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            out, reference.moe(params, tokens, lc), rtol=1e-4, atol=1e-5)
