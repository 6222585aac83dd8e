"""Granite's architecture through the program's models, on the CPU: the chunked
loss and the gradients against the reference's, the quarter's logits, the
model at an odd size, and the parameter counts
(``tests/test_granite_hybrid_model.py`` has the model against its reference
and says what the reference is; ``tests/granite_hybrid_cases.py`` what the
files share).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import granite_hybrid_decoder as reference
from ray_tpu.models.granite_hybrid import GraniteHybridForCausalLM
from ray_tpu.models.llama import chunked_causal_lm_loss

from granite_hybrid_cases import (  # noqa: F401 - fixtures
    NEAR, SEQ, build, granite, in_float32, interpret, leaves,
)


# 3 state-space heads of 24 over a state of 40, 6 query heads of 24 over 3 K/V
# heads: nothing a power of two, no head count a multiple of a group of 8.
ODD = {"hidden_size": 72, "intermediate_size": 160, "shared_intermediate_size": 160,
       "num_attention_heads": 6, "num_key_value_heads": 3, "head_dim": 24,
       "vocab_size": 384, "mamba_n_heads": 3, "mamba_d_head": 24,
       "mamba_d_state": 40, "mamba_expand": 1, "attention_multiplier": 0.03}
ODD_SEQ = 200


@pytest.fixture(scope="module")
def odd():
    return build(ODD, ODD_SEQ, 1)


@pytest.mark.parametrize("which", ["granite", "odd"])
def test_num_params_is_the_tree_at_the_small_sizes(request, which):
    _, model, params, _ = request.getfixturevalue(which)
    assert leaves(params) == model.cfg.num_params()


def test_logits_agree_at_an_odd_size(odd):
    config, model, params, ids = odd
    system = jax.jit(model.apply)(params, ids[None])[0]
    result = logits_agreement(
        system, reference.forward(params, ids, config, ODD_SEQ), NEAR)
    assert result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(granite):
    config, model, params, ids = granite
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
    errors = {}
    for path, want in jax.tree_util.tree_leaves_with_path(wanted["params"]):
        got, want = np.asarray(flat[path]), np.asarray(want)
        assert got.shape == want.shape and np.abs(want).max() > 0, path
        errors[jax.tree_util.keystr(path)] = np.abs(got - want).max() / np.abs(want).max()
    assert max(errors.values()) < 1e-4, max(errors.items(), key=lambda e: e[1])
    # a layer: 2 norms and the MLP's 3 weights; a mamba mixer's 10 leaves, the
    # attention mixer's 4; the tied embedding and the final norm
    assert len(errors) == 10 * 5 + 9 * 10 + 4 + 2


def test_the_quarters_logits_are_the_first_columns_of_the_whole_tables(granite):
    """The held slice against the whole: a model with four times the table
    whose first quarter is this one's gives, on ids of the slice, logits whose
    first columns are the slice's (a tied table is read by rows going in and
    by rows coming out, and no row looks at another)."""
    config, model, params, ids = granite
    held = config["vocab_size"]
    rest = jax.random.normal(jax.random.PRNGKey(7), (3 * held, config["hidden_size"])) * 0.02
    table = params["params"]["embed_tokens"]["embedding"]
    whole_params = {"params": {**params["params"], "embed_tokens": {
        "embedding": jnp.concatenate([table, rest])}}}
    whole = GraniteHybridForCausalLM(cells.program_config(in_float32(
        {**config, "vocab_size": 4 * held})))
    logits = jax.jit(whole.apply)(whole_params, ids[None])[0]
    assert logits.shape == (SEQ, 4 * held)
    mine = jax.jit(model.apply)(params, ids[None])[0]
    np.testing.assert_allclose(np.asarray(logits[:, :held]), np.asarray(mine),
                               rtol=1e-5, atol=1e-6)
    whole_reference = reference.forward(
        whole_params, ids, {**config, "vocab_size": 4 * held}, SEQ)
    result = logits_agreement(logits, whole_reference, NEAR)
    assert result["ok"], result
