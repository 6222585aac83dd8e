"""sarvam-105b's architecture through the program's models, on the CPU.

``SarvamMLAForCausalLM`` (a leading dense layer and expert layers, each under
latent attention whose 64-wide parts are rotated under YaRN, with a per-head
QK norm; sigmoid routing with a selection bias, a shared expert, one
expert-parallel rank's share of the routed experts through the ``gmm``
dispatch in interpret mode) against the benchmark's plain reference
(``benchmarks/reference/sarvam_mla_decoder.py``) at the configuration file's
own ``rehearsal`` size on seeded random weights: logits, loss and gradients.
The YaRN table against its closed form at the published numbers, what the
rotation turns and what it leaves, the sixteen ranks' shares of one expert
layer against the uncut reference, and ``MLAMixer`` with its new fields off
against the mixer Kimi-Linear had.

This file holds the float32 model against its reference (logits, two programs
made wrong in place, loss and gradients). The programs of another function
(``tests/test_sarvam_mla_wrong_programs.py``) and what needs no float32 model
(``test_sarvam_mla_layers.py``: the YaRN table, the rotation, the bfloat16
program, the ranks' shares, the mixer Kimi-Linear had) are beside it, over
``tests/sarvam_cases.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import sarvam_mla_decoder as reference
from ray_tpu.models.llama import chunked_causal_lm_loss

from sarvam_cases import (  # noqa: F401 - fixtures
    CONFIG, PUBLISHED_YARN, expected, interpret, sarvam_f32,
)


def test_the_configuration_builds_sarvams_program(sarvam_f32):
    config, model, params, _ = sarvam_f32
    cfg = model.cfg
    assert cfg.layers == (("mla", "mlp"),) + (("mla", "moe"),) * 4
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch) == (
        "sigmoid", True, 2.5, 1, "gmm")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (
        16, (0, 4), 4)
    assert (cfg.mla_rope, cfg.qk_head_norm, cfg.rope_scaling) == (
        True, True, PUBLISHED_YARN)
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "mla", "post_attn_norm", "mlp"}
    assert set(p["layers_4"]) == {"input_norm", "mla", "post_attn_norm", "moe"}
    mla = p["layers_0"]["mla"]
    assert set(mla) == {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                        "q_norm", "k_norm", "o_proj"}
    # one weight over a head's nope + pe channels, shared by the heads
    assert mla["q_norm"]["scale"].shape == mla["k_norm"]["scale"].shape == (32,)
    moe = p["layers_1"]["moe"]
    assert moe["router"]["kernel"].shape == (128, 16)  # the router's width
    assert moe["w_gate"].shape == (4, 128, 64)  # the experts held
    assert not np.asarray(moe["router_bias"]).any()
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(cells.load_json(CONFIG))
    assert (full.hidden_size, full.intermediate_size, full.expert_width,
            full.num_heads, full.kv_lora_rank, full.qk_nope_head_dim,
            full.qk_rope_head_dim, full.v_head_dim, full.rms_eps,
            full.rope_theta) == (4096, 16384, 2048, 64, 512, 128, 64, 128, 1e-6, 10000)
    assert (full.num_experts, full.experts_held, full.num_experts_per_tok,
            full.vocab_size, full.num_layers, full.tie_embeddings) == (
        128, (0, 8), 8, 32768, 5, False)
    assert full.layers == cfg.layers and full.rope_scaling == PUBLISHED_YARN


def test_logits_agree_with_the_reference_in_float32(sarvam_f32, expected):
    config, model, params, ids = sarvam_f32
    system = jax.jit(model.apply)(params, ids[None])[0]
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-4, "min_share_within": 1.0}
    )
    assert result["ok"], result


def test_an_unrotated_key_part_is_far_from_the_reference(
        sarvam_f32, expected, monkeypatch):
    """q's 64-wide part rotated and the shared key's not."""
    config, model, params, ids = sarvam_f32
    from ray_tpu.models import mla

    turn, calls = mla._rope, []

    def q_only(t, positions, freqs):
        calls.append(t.shape)
        return turn(t, positions, freqs) if len(calls) % 2 else t

    monkeypatch.setattr(mla, "_rope", q_only)
    system = model.apply(params, ids[None])[0]
    assert len(calls) == 10  # q and k of five layers
    result = logits_agreement(
        system, expected,
        {"per_position_rel_err": 1e-3, "min_share_within": 0.5},
    )
    assert not result["ok"], result


def test_routing_in_bfloat16_is_far_from_the_reference(
        sarvam_f32, expected, monkeypatch):
    """The router's matmul and sigmoid in bfloat16 pick other experts for
    some tokens and give others' gates: the float32 comparison refuses it."""
    config, model, params, ids = sarvam_f32
    sigmoid = jax.nn.sigmoid
    monkeypatch.setattr(
        jax.nn, "sigmoid",
        lambda x: sigmoid(x.astype(jnp.bfloat16)).astype(x.dtype)
        if x.ndim == 3 and x.shape[-1] == 16 else sigmoid(x))
    system = model.apply(params, ids[None])[0]
    result = logits_agreement(
        system, expected,
        {"per_position_rel_err": 1e-3, "min_share_within": 0.5},
    )
    assert not result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(sarvam_f32):
    config, model, params, ids = sarvam_f32
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
    ("layers_0", "mla", "q_proj", "kernel"),
    ("layers_0", "mla", "kv_a_proj", "kernel"),
    ("layers_0", "mla", "kv_a_norm", "scale"),
    ("layers_0", "mla", "kv_b_proj", "kernel"),
    ("layers_0", "mla", "q_norm", "scale"),
    ("layers_0", "mla", "k_norm", "scale"),
    ("layers_0", "mla", "o_proj", "kernel"),
    ("layers_0", "mlp", "down_proj", "kernel"),
    ("layers_0", "input_norm", "scale"),
    ("layers_1", "mla", "q_proj", "kernel"),
    ("layers_1", "moe", "w_gate"),
    ("layers_1", "moe", "w_down"),
    ("layers_1", "moe", "shared", "up_proj", "kernel"),
    ("layers_1", "moe", "router", "kernel"),
    ("layers_2", "mla", "k_norm", "scale"),
    ("layers_3", "mla", "kv_a_proj", "kernel"),
    ("layers_3", "moe", "w_up"),
    ("layers_3", "moe", "router", "kernel"),
    ("layers_4", "mla", "o_proj", "kernel"),
    ("layers_4", "moe", "shared", "down_proj", "kernel"),
    ("final_norm", "scale"),
    ("lm_head", "kernel"),
    ("embed_tokens", "embedding"),
], ids="/".join)
def test_gradients_agree_with_the_references(both_gradients, path):
    (_, grads), (_, expected) = both_gradients
    got, want = leaf(grads["params"], path), leaf(expected["params"], path)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-5 * np.abs(want).max())


def test_no_gradient_reaches_the_selection_bias_and_the_router_learns(both_gradients):
    """The bias stays where it is (the stated departure); the router's
    weights are trained, through the held experts' gates, in the program and
    in the reference's loss alike."""
    (_, grads), (_, expected) = both_gradients
    for i in range(1, 5):
        for tree in (grads, expected):
            moe = tree["params"][f"layers_{i}"]["moe"]
            assert not np.asarray(moe["router_bias"]).any()
            assert np.asarray(moe["router"]["kernel"]).any()
