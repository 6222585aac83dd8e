"""Kimi-Linear's architecture through the program's models, on the CPU.

``KimiLinearForCausalLM`` (a leading dense layer under KDA, KDA + experts,
MLA + experts; sigmoid routing with a selection bias, a shared expert, one
expert-parallel rank's share of the routed experts through the ``gmm``
dispatch, the Pallas kernels in interpret mode) against the benchmark's
plain reference (``benchmarks/reference/kimi_linear_decoder.py``) at the
configuration file's own ``rehearsal`` size on seeded random weights:
logits, loss and gradients. And the test that ties the share to the model:
the routed parts that all the ranks give, with the shared expert counted
once, add up to the uncut reference's expert layer.

This file holds the model against its reference (logits, loss and gradients).
The programs of another function
(``tests/test_kimi_linear_wrong_programs.py``) and one rank's share of an
expert layer (``test_kimi_linear_shares.py``) are beside it, over
``tests/kimi_linear_cases.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import kimi_linear_decoder as reference
from ray_tpu.models.llama import chunked_causal_lm_loss

from kimi_linear_cases import (  # noqa: F401 - fixtures
    CONFIG, SEQ, expected, interpret, kimi, kimi_f32,
)


@pytest.fixture(scope="module")
def kimi_bf16():
    return kimi("bfloat16")


def test_the_configuration_builds_kimi_linears_program(kimi_f32):
    config, model, params, _ = kimi_f32
    cfg = model.cfg
    # each kind of layer is present: leading dense, KDA + experts, MLA + experts
    assert cfg.layers == (("kda", "mlp"), ("kda", "moe"), ("kda", "moe"),
                          ("mla", "moe"), ("kda", "moe"))
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch) == (
        "sigmoid", True, 2.446, 1, "gmm")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (
        16, (0, 4), 4)
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "kda", "post_attn_norm", "mlp"}
    assert set(p["layers_3"]) == {"input_norm", "mla", "post_attn_norm", "moe"}
    moe = p["layers_1"]["moe"]
    assert moe["router"]["kernel"].shape == (128, 16)  # the router's width
    assert moe["w_gate"].shape == (4, 128, 64)  # the experts held
    assert moe["router_bias"].shape == (16,) and not np.asarray(moe["router_bias"]).any()
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(cells.load_json(CONFIG))
    assert (full.hidden_size, full.intermediate_size, full.expert_width,
            full.num_heads, full.kda_num_heads, full.kda_head_dim,
            full.short_conv_kernel_size, full.kv_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim) == (
        2304, 9216, 1024, 32, 32, 128, 4, 512, 128, 64, 128)
    assert (full.num_experts, full.experts_held, full.num_experts_per_tok,
            full.vocab_size, full.num_layers) == (256, (0, 16), 8, 20480, 5)
    assert full.layers == cfg.layers


def test_logits_agree_with_the_reference_in_float32(kimi_f32, expected):
    config, model, params, ids = kimi_f32
    system = jax.jit(model.apply)(params, ids[None])[0]
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-4, "min_share_within": 1.0}
    )
    assert result["ok"], result


def test_logits_in_bfloat16_are_near_the_reference_and_not_it(kimi_bf16):
    config, model, params, ids = kimi_bf16
    system = jax.jit(model.apply)(params, ids[None])[0]
    expected = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 0.1, "min_share_within": 0.9}
    )
    assert result["ok"], result
    assert result["rel_err_median"] > 1e-4  # the system is not the reference


@pytest.fixture(scope="module")
def both_gradients(kimi_f32):
    config, model, params, ids = kimi_f32
    targets = np.roll(ids, -1)
    system = jax.jit(jax.value_and_grad(
        lambda p: chunked_causal_lm_loss(
            model, p, ids[None], targets[None], chunk_size=64)
    ))(params)
    expected = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, targets, config)
    ))(params)
    return system, expected


def test_the_chunked_loss_takes_the_model_and_agrees_with_the_reference(both_gradients):
    (loss, _), (expected, _) = both_gradients
    assert float(loss) == pytest.approx(float(expected), rel=1e-5)


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return np.asarray(tree)


@pytest.mark.parametrize("path", [
    ("layers_0", "kda", "q_proj", "kernel"),
    ("layers_0", "kda", "k_conv"),
    ("layers_0", "kda", "A_log"),
    ("layers_0", "kda", "dt_bias"),
    ("layers_0", "kda", "f_a_proj", "kernel"),
    ("layers_0", "kda", "b_proj", "kernel"),
    ("layers_0", "kda", "g_b_proj", "bias"),
    ("layers_0", "kda", "o_norm", "scale"),
    ("layers_0", "mlp", "down_proj", "kernel"),
    ("layers_1", "kda", "v_proj", "kernel"),
    ("layers_1", "kda", "k_proj", "kernel"),
    ("layers_2", "kda", "g_a_proj", "kernel"),
    ("layers_1", "moe", "router", "kernel"),
    ("layers_1", "moe", "w_gate"),
    ("layers_1", "moe", "w_down"),
    ("layers_1", "moe", "shared", "up_proj", "kernel"),
    ("layers_3", "mla", "q_proj", "kernel"),
    ("layers_3", "mla", "kv_a_proj", "kernel"),
    ("layers_3", "mla", "kv_a_norm", "scale"),
    ("layers_3", "mla", "kv_b_proj", "kernel"),
    ("layers_3", "moe", "w_up"),
    ("layers_4", "kda", "o_proj", "kernel"),
    ("lm_head", "kernel"),
    ("embed_tokens", "embedding"),
], ids="/".join)
def test_gradients_agree_with_the_references(both_gradients, path):
    (_, grads), (_, expected) = both_gradients
    got, want = leaf(grads["params"], path), leaf(expected["params"], path)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-5 * np.abs(want).max())


def test_no_gradient_reaches_the_selection_bias(both_gradients):
    (_, grads), _ = both_gradients
    for i in range(1, 5):
        assert not leaf(grads["params"], (f"layers_{i}", "moe", "router_bias")).any()
