"""Xing4's architecture through the program's models, on the CPU: the loss's two
terms and every gradient against the reference's
(``tests/test_xing4_model.py`` has the model against its reference and says
what the reference is; ``tests/xing4_cases.py`` what the files share).
"""
import functools

import jax
import numpy as np
import pytest

from benchmarks.reference import xing4_decoder as reference
from ray_tpu.models.xing4 import mtp_chunked_lm_loss

from xing4_cases import interpret, xing4_f32  # noqa: F401 - fixtures


@pytest.fixture(scope="module")
def system_loss_and_gradients(xing4_f32):
    """The program's loss and its gradients at a weight of the module's term:
    one jitted program, the weight its operand (the loss multiplies by it and
    nothing else), which the gradients' cases and the loss terms' all read."""
    _, model, params, ids = xing4_f32
    targets = np.roll(ids, -1)
    program = jax.jit(jax.value_and_grad(
        lambda p, weight: mtp_chunked_lm_loss(
            model, p, ids[None], targets[None], chunk_size=64, mtp_weight=weight)))
    return functools.cache(lambda weight: program(params, weight))


@pytest.fixture(scope="module")
def both_gradients(xing4_f32, system_loss_and_gradients):
    config, model, params, ids = xing4_f32
    targets = np.roll(ids, -1)
    system = system_loss_and_gradients(0.3)
    expected = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, targets, config)
    ))(params)
    return system, expected


@pytest.fixture(scope="module")
def reference_terms(xing4_f32):
    config, _, params, ids = xing4_f32
    return tuple(float(v) for v in reference.loss_terms(
        params, ids, np.roll(ids, -1), config))


@pytest.fixture(scope="module")
def loss(system_loss_and_gradients):
    """The program's loss at a weight, a weight's value made once."""
    return lambda weight: float(system_loss_and_gradients(weight)[0])


@pytest.mark.parametrize("term", ["main", "mtp", "sum"])
def test_each_loss_term_agrees_with_the_references(
        xing4_f32, reference_terms, loss, both_gradients, term):
    config = xing4_f32[0]
    main, mtp = reference_terms
    assert config["mtp_loss_weight"] == 0.3 and abs(main - mtp) > 1e-3
    if term == "main":
        assert loss(0.0) == pytest.approx(main, rel=1e-5)
    elif term == "mtp":
        assert loss(1.0) - loss(0.0) == pytest.approx(mtp, rel=1e-4)
    else:
        (value, _), (expected, _) = both_gradients
        assert float(value) == pytest.approx(main + 0.3 * mtp, rel=1e-5)
        assert float(expected) == pytest.approx(main + 0.3 * mtp, rel=1e-6)


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return np.asarray(tree)


@pytest.mark.parametrize("path", [
    ("layers_0", "mixer_hc", "phi"),
    ("layers_0", "mixer_hc", "alpha"),
    ("layers_0", "mixer_hc", "b_pre"),
    ("layers_0", "mixer_hc", "b_post"),
    ("layers_0", "mixer_hc", "b_res"),
    ("layers_0", "ffn_hc", "phi"),
    ("layers_0", "ffn_hc", "b_res"),
    ("layers_0", "mla", "q_a_proj", "kernel"),
    ("layers_0", "mla", "q_a_norm", "scale"),
    ("layers_0", "mla", "q_b_proj", "kernel"),
    ("layers_0", "mla", "kv_a_proj", "kernel"),
    ("layers_0", "mla", "kv_b_proj", "kernel"),
    ("layers_0", "mla", "o_proj", "kernel"),
    ("layers_0", "mlp", "down_proj", "kernel"),
    ("layers_0", "input_norm", "scale"),
    ("layers_1", "mixer_hc", "alpha"),
    ("layers_1", "ffn_hc", "phi"),
    ("layers_1", "moe", "w_gate"),
    ("layers_1", "moe", "shared", "up_proj", "kernel"),
    ("layers_1", "moe", "router", "kernel"),
    ("layers_3", "mla", "q_b_proj", "kernel"),
    ("layers_3", "ffn_hc", "b_post"),
    ("layers_4", "mixer_hc", "b_res"),
    ("layers_4", "moe", "w_down"),
    ("mtp_hidden_norm", "scale"),
    ("mtp_embed_norm", "scale"),
    ("mtp_proj", "kernel"),
    ("mtp_layer", "mixer_hc", "phi"),
    ("mtp_layer", "mla", "q_a_proj", "kernel"),
    ("mtp_layer", "moe", "w_up"),
    ("mtp_layer", "ffn_hc", "alpha"),
    ("mtp_norm", "scale"),
    ("final_norm", "scale"),
    ("lm_head", "kernel"),
    ("embed_tokens", "embedding"),
], ids="/".join)
def test_gradients_agree_with_the_references(both_gradients, path):
    (_, grads), (_, expected) = both_gradients
    got, want = leaf(grads["params"], path), leaf(expected["params"], path)
    assert got.shape == want.shape and np.abs(want).max() > 0
    # b_res's gradient is what is left after Sinkhorn has projected the shifts
    # of whole rows and columns away: differences of nearly equal numbers
    loose = 2e-2 if path[-1] == "b_res" else 5e-5
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=loose * np.abs(want).max())


def test_no_gradient_reaches_the_selection_bias_and_the_router_learns(both_gradients):
    (_, grads), (_, expected) = both_gradients
    for name in ("layers_1", "layers_4", "mtp_layer"):
        for tree in (grads, expected):
            moe = tree["params"][name]["moe"]
            assert not np.asarray(moe["router_bias"]).any()
            assert np.asarray(moe["router"]["kernel"]).any()
