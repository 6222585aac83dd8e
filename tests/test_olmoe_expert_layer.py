"""OLMoE's expert layer alone, on the CPU: an expert that no token chose has a
zero gradient, and unnormalised gates scale each token by its top-k mass
(``tests/test_olmoe_model.py`` has the model against its reference).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.mixtral import (
    CONFIGS as MOE_CONFIGS, MixtralConfig, MoELayer,
)


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def test_an_expert_that_no_token_chose_has_a_zero_gradient():
    """The weight-gradient kernel writes an expert's block when it leaves
    the expert's tiles: an empty expert keeps a tile of padding so that it
    is written, with zeros."""
    cfg = dataclasses.replace(
        MOE_CONFIGS["mixtral-tiny"], dtype=jnp.float32, moe_dispatch="gmm",
        num_experts=8, num_experts_per_tok=2,
    )
    layer = MoELayer(cfg)
    # Positive inputs and a router that prefers expert 0, then 1, for all.
    x = jnp.asarray(
        np.abs(np.random.RandomState(0).randn(1, 16, cfg.hidden_size)) + 1.0,
        jnp.float32,
    )
    params = layer.init(jax.random.PRNGKey(0), x)
    router = np.full((cfg.hidden_size, 8), -1.0, np.float32)
    router[:, 0], router[:, 1] = 1.0, 0.5
    params = {"params": {**params["params"],
                         "router": {"kernel": jnp.asarray(router)}}}
    grads = jax.grad(lambda p: (layer.apply(p, x) ** 2).sum())(params)["params"]
    for name in ("w_gate", "w_up", "w_down"):
        g = np.asarray(grads[name])
        assert np.isfinite(g).all(), name
        assert np.abs(g[:2]).max() > 0 and not g[2:].any(), name


@pytest.mark.parametrize("dispatch", ["gmm", "capacity", "ragged"])
def test_unnormalised_gates_scale_each_token_by_its_top_k_mass(dispatch):
    """norm_topk_prob false: a token's gates are its top-k probabilities as
    they are, so its output is the renormalised one times their sum, which
    is under one. True is the default and Mixtral's."""
    assert MixtralConfig().norm_topk_prob is True
    base = dataclasses.replace(
        MOE_CONFIGS["mixtral-tiny"], dtype=jnp.float32, moe_dispatch=dispatch,
        capacity_factor=2.0,  # experts / top-k: no pair is dropped
    )
    assert base.norm_topk_prob is True
    x = jnp.asarray(np.random.RandomState(1).randn(2, 32, base.hidden_size),
                    jnp.float32)
    renormalised = MoELayer(base)
    params = renormalised.init(jax.random.PRNGKey(2), x)
    as_they_are = MoELayer(dataclasses.replace(base, norm_topk_prob=False))
    probs = jax.nn.softmax(x @ params["params"]["router"]["kernel"], axis=-1)
    mass = jax.lax.top_k(probs, base.num_experts_per_tok)[0].sum(-1)
    assert float(mass.max()) < 0.999  # the gates do not sum to one
    np.testing.assert_allclose(
        np.asarray(as_they_are.apply(params, x)),
        np.asarray(renormalised.apply(params, x) * mass[..., None]),
        rtol=1e-5, atol=1e-6,
    )
