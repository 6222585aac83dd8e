"""OLMoE-1B-7B's architecture through the program's models, on the CPU.

``MixtralForCausalLM`` with QK-norm, unnormalised top-k gates and an untied
head, through the drop-free ``gmm`` dispatch (the Pallas kernels in interpret
mode), against the benchmark's plain reference
(``benchmarks/reference/olmoe_decoder.py``) at the configuration file's own
``rehearsal`` size on seeded random weights: logits, loss and gradients. And
each of the four facts of the architecture (the fourth is its draw of the
weights) leaves the models that lack it as they were.

This file holds the model against its reference. The expert layer alone
(``tests/test_olmoe_expert_layer.py``) and the switches the architecture added
(``tests/test_olmoe_switches.py``) are beside it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import olmoe_decoder
from ray_tpu.models.mixtral import MixtralForCausalLM, moe_lm_loss


SEQ = 128


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def olmoe(dtype: str):
    """(configuration dict at its rehearsal size, model, params, ids)."""
    config = cells.load_json(f"{cells.BENCH_DIR}/configs/olmoe-1b-7b-1chip.json")
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": dtype, "param_dtype": dtype},
    }
    model = MixtralForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    return config, model, params, ids


@pytest.fixture(scope="module")
def olmoe_f32():
    return olmoe("float32")


@pytest.fixture(scope="module")
def olmoe_bf16():
    return olmoe("bfloat16")


def test_the_configuration_builds_olmoes_program(olmoe_f32):
    config, model, params, _ = olmoe_f32
    cfg = model.cfg
    assert (cfg.qk_norm, cfg.norm_topk_prob, cfg.tie_embeddings) == (
        True, False, False)
    assert cfg.moe_dispatch == "gmm" and cfg.router_aux_loss_coef == 0.01
    assert cfg.initializer_range == 0.02
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (8, 2)
    attn = params["params"]["layers_0"]["attn"]
    width = cfg.num_heads * cfg.head_dim_
    assert attn["q_norm"]["scale"].shape == (width,)
    assert attn["k_norm"]["scale"].shape == (cfg.num_kv_heads * cfg.head_dim_,)
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(
        cells.load_json(f"{cells.BENCH_DIR}/configs/olmoe-1b-7b-1chip.json")
    )
    assert (full.hidden_size, full.num_heads, full.num_kv_heads,
            full.head_dim_, full.intermediate_size, full.num_experts,
            full.num_experts_per_tok, full.vocab_size, full.max_seq_len) == (
        2048, 16, 16, 128, 1024, 64, 8, 50304, 4096)
    assert (full.qk_norm, full.norm_topk_prob, full.tie_embeddings) == (
        True, False, False)


def test_logits_agree_with_the_reference_in_float32(olmoe_f32):
    config, model, params, ids = olmoe_f32
    system = jax.jit(model.apply)(params, ids[None])[0]
    expected = olmoe_decoder.forward(params, ids, config, SEQ)
    assert system.dtype == jnp.float32
    # No routing flip is expected in 128 positions: a flip needs two router
    # probabilities within float32 rounding of each other.
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 1e-4, "min_share_within": 1.0}
    )
    assert result["ok"], result


@pytest.fixture(scope="module")
def expected_of_bf16(olmoe_bf16):
    """The reference's logits of the bfloat16 parameters, which the bfloat16
    program and each program of another function are held to."""
    config, _, params, ids = olmoe_bf16
    return olmoe_decoder.forward(params, ids, config, SEQ)


def test_logits_agree_in_bfloat16_within_the_references_tolerance(
        olmoe_bf16, expected_of_bf16):
    config, model, params, ids = olmoe_bf16
    system = jax.jit(model.apply)(params, ids[None])[0]
    expected = expected_of_bf16
    result = logits_agreement(system, expected, olmoe_decoder.TOLERANCE)
    assert result["ok"], result
    assert result["rel_err_median"] > 1e-4  # the system is not the reference


@pytest.mark.parametrize("wrong", [
    {"norm_topk_prob": True},  # Mixtral's gates
    {"moe_dispatch": "capacity", "capacity_factor": 1.25},  # drops pairs
    {"qk_norm": False},  # the tree keeps q_norm and k_norm; nothing reads them
], ids=lambda w: "-".join(w))
def test_a_program_of_another_function_is_refused(olmoe_bf16, expected_of_bf16, wrong):
    """The tolerance the chip runs are held to refuses them here as it does
    there (the reference's file has the chip's readings)."""
    config, model, params, ids = olmoe_bf16
    other = MixtralForCausalLM(dataclasses.replace(model.cfg, **wrong))
    result = logits_agreement(
        jax.jit(other.apply)(params, ids[None])[0], expected_of_bf16,
        olmoe_decoder.TOLERANCE,
    )
    assert not result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(olmoe_f32):
    config, model, params, ids = olmoe_f32
    targets = np.roll(ids, -1)
    system = jax.jit(jax.value_and_grad(
        lambda p: moe_lm_loss(model, p, ids[None], targets[None])
    ))(params)
    expected = jax.jit(jax.value_and_grad(
        lambda p: olmoe_decoder.loss(p, ids, targets, config)
    ))(params)
    return system, expected


def test_loss_agrees_with_the_reference(both_gradients):
    (loss, _), (expected, _) = both_gradients
    assert float(loss) == pytest.approx(float(expected), rel=1e-5)
    # the auxiliary term is in both: the cross-entropy alone is smaller
    assert float(loss) > np.log(512) * 0.9


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return np.asarray(tree)


@pytest.mark.parametrize("path", [
    ("layers_0", "moe", "router", "kernel"),
    ("layers_1", "moe", "router", "kernel"),
    ("layers_0", "moe", "w_gate"),
    ("layers_0", "moe", "w_up"),
    ("layers_0", "moe", "w_down"),
    ("layers_0", "attn", "q_norm", "scale"),
    ("layers_0", "attn", "k_norm", "scale"),
    ("layers_1", "attn", "q_norm", "scale"),
    ("lm_head", "kernel"),
    ("embed_tokens", "embedding"),
], ids="/".join)
def test_gradients_agree_with_the_references(both_gradients, path):
    (_, grads), (_, expected) = both_gradients
    got, want = leaf(grads["params"], path), leaf(expected["params"], path)
    assert got.shape == want.shape and np.abs(want).max() > 0
    if path[-1] in ("w_gate", "w_up", "w_down"):
        # one expert's matrices, the busiest's
        e = int(np.argmax(np.abs(want).reshape(want.shape[0], -1).sum(1)))
        got, want = got[e], want[e]
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5 * np.abs(want).max())
