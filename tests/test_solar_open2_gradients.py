"""Solar-Open2's architecture through the program's models, on the CPU: the
chunked loss and every gradient against the reference's
(``tests/test_solar_open2_model.py`` has the model against its reference and
says what the reference is; ``tests/solar_open2_cases.py`` what the files
share).
"""
import jax
import numpy as np
import pytest

from benchmarks.reference import solar_open2_decoder as reference
from ray_tpu.models.llama import chunked_causal_lm_loss

from solar_open2_cases import interpret, solar  # noqa: F401 - fixtures


@pytest.fixture(scope="module")
def both_gradients(solar):
    config, model, params, ids = solar
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
    # a layer: 2 norms and 7 expert-layer weights; the GQA mixer's 5 weights,
    # a KDA mixer's 16 (g_b_proj has a bias); embedding, final norm, head
    assert checked == 4 * 9 + 5 + 3 * 16 + 3
