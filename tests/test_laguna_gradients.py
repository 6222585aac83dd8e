"""Laguna's architecture through the program's models, on the CPU: the chunked
loss and every gradient against the reference's, and the four ranks' shares of
an expert layer (``tests/test_laguna_model.py`` has the model against its
reference and says what the reference is; ``tests/laguna_cases.py`` what the
files share).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna_decoder as reference
from ray_tpu.models.laguna import LagunaConfig
from ray_tpu.models.llama import chunked_causal_lm_loss
from ray_tpu.models.mixtral import MoELayer

from laguna_cases import interpret, laguna  # noqa: F401 - fixtures


@pytest.fixture(scope="module")
def both_gradients(laguna):
    config, model, params, ids = laguna
    targets = np.roll(ids, -1)
    system = jax.jit(jax.value_and_grad(
        lambda p: chunked_causal_lm_loss(
            model, p, ids[None], targets[None], chunk_size=64)
    ))(params)
    wanted = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, targets, config)
    ))(params)
    return system, wanted


def test_the_chunked_loss_agrees_with_the_reference(both_gradients):
    (loss, _), (wanted, _) = both_gradients
    assert float(loss) == pytest.approx(float(wanted), rel=1e-5)


def test_every_gradient_agrees_with_the_references(both_gradients):
    (_, grads), (_, wanted) = both_gradients
    flat = dict(jax.tree_util.tree_leaves_with_path(grads["params"]))
    checked = 0
    for path, want in jax.tree_util.tree_leaves_with_path(wanted["params"]):
        got, want = np.asarray(flat[path]), np.asarray(want)
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            assert not got.any() and not want.any()  # no gradient reaches it
            continue
        assert got.shape == want.shape and np.abs(want).max() > 0, name
        np.testing.assert_allclose(
            got, want, rtol=5e-3, atol=5e-5 * np.abs(want).max(), err_msg=name)
        checked += 1
    # 2 norms a layer, 5 mixer weights, 3 dense or 7 expert-layer weights;
    # embedding, final norm, head
    assert checked == 8 * 7 + 3 + 7 * 7 + 3


# ------------------------------------------------- the expert layer alone


def expert_layer(held):
    """One expert layer at Laguna's routing: 16 experts scored, top-2,
    sigmoid, renormalised, x 2.5, one shared expert; ``held`` of them here."""
    cfg = LagunaConfig(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_experts=16, num_experts_per_tok=2, num_shared_experts=1,
        routed_scaling_factor=2.5, experts_held=held, initializer_range=0.5,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return MoELayer(cfg)


def layer_config(held) -> dict:
    """The reference's keys for that layer."""
    lo, hi = held or (0, 16)
    return {"num_experts_published": 16, "num_experts": hi - lo,
            "expert_rank": lo // (hi - lo), "num_experts_per_tok": 2,
            "moe_routed_scaling_factor": 2.5}


def test_the_four_ranks_shares_add_up_to_the_uncut_layer():
    """Four ranks of four experts each: the routed parts they give, with the
    shared expert (which every rank computes alike) counted once, are the
    uncut reference's expert layer."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 48, 32)), jnp.float32)
    params = expert_layer(None).init(jax.random.PRNGKey(1), x)["params"]
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(params, tokens, layer_config(None))
        shared = reference.swiglu(params["shared"], tokens)
    total, pairs = 0.0, 0
    for rank in range(4):
        held = (4 * rank, 4 * rank + 4)
        mine = {**params, **{k: params[k][held[0]:held[1]]
                             for k in ("w_gate", "w_up", "w_down")}}
        out = expert_layer(held).apply({"params": mine}, x).reshape(-1, 32)
        with jax.default_matmul_precision("highest"):
            want = reference.moe(mine, tokens, layer_config(held))
            gates = reference.router_gates(params, tokens, layer_config(held))
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        pairs += int((np.asarray(gates)[:, held[0]:held[1]] > 0).sum())
        total = total + (out - shared)
    assert pairs == 96 * 2  # every pair is held by exactly one rank
    np.testing.assert_allclose(total + shared, uncut, rtol=1e-4, atol=2e-5)
    # gates: two a token, renormalised, times 2.5
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)
    assert ((np.asarray(gates) > 0).sum(-1) == 2).all()
