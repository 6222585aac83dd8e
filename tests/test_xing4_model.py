"""Xing4.0-29B-A4B's architecture through the program's models, on the CPU.

``Xing4ForCausalLM`` (a leading dense layer and expert layers, each under
latent attention with a q latent, on four hyper-connected residual streams
whose stream-to-stream map is Sinkhorn-projected; a multi-token-prediction
module beside the final norm; one expert-parallel rank's share of the routed
experts through the ``gmm`` dispatch in interpret mode) against the
benchmark's plain reference (``benchmarks/reference/xing4_decoder.py``) at the
configuration file's own ``rehearsal`` size on seeded random weights: both
heads' logits, both loss terms and gradients. Sinkhorn's projection and its
hand-written backward against the plain loop, the four ranks' shares of one
expert layer against the uncut reference, and ``MLAMixer`` with the q latent
off against what it was.

This file holds the float32 model against its reference (both heads' logits,
the wrong references). The rest of what is listed above is beside it, a file
each so that none is a run's tail: the bfloat16 program
(``tests/test_xing4_model_bf16.py``), the wrong programs
(``test_xing4_wrong_programs.py``), the loss terms and gradients
(``test_xing4_gradients.py``), what needs no model (``test_xing4_layers.py``),
the gathered rows (``test_xing4_gathered_rows.py``,
``_rank1.py``, ``_rank3.py``) and the tool
(``test_xing4_tool.py``), over ``tests/xing4_cases.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import xing4_decoder as reference
from benchmarks.tools import wrong_xing4
from ray_tpu.models.hyper_connections import HyperConnections
from ray_tpu.models.xing4 import Xing4Config

from xing4_cases import (  # noqa: F401 - fixtures
    CONFIG, LOOSE, PUBLISHED_YARN, SEQ, expected_logits, interpret, xing4_f32,
)


@pytest.fixture(scope="module")
def system_logits(xing4_f32):
    """The unchanged program's float32 logits: one jitted program, one value."""
    _, model, params, ids = xing4_f32
    return jax.jit(model.apply)(params, ids[None])[0]


def test_the_configuration_builds_xing4s_program(xing4_f32):
    config, model, params, _ = xing4_f32
    cfg = model.cfg
    assert cfg.layers == (("mla", "mlp"),) + (("mla", "moe"),) * 4
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch) == ("sigmoid", True, 2, 1, "gmm")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (16, (0, 4), 4)
    # a held quarter moves its rows by gathers; the siblings' sixteenths walk
    assert cfg.held_rows == "gather" and Xing4Config().held_rows == "walk"
    assert (cfg.mla_rope, cfg.qk_head_norm, cfg.rope_scaling, cfg.q_lora_rank) == (
        True, False, PUBLISHED_YARN, 48)
    assert cfg.hyper_connections == HyperConnections(
        mult=4, sinkhorn_iters=20, eps=1e-6, clamp=(-30, 30), alpha_init=1.0,
        res_diagonal_init=2.0)
    assert cfg.remat and cfg.remat_prevent_cse and cfg.remat_policy == "nothing"
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "mla", "post_attn_norm", "mlp",
                                  "mixer_hc", "ffn_hc"}
    assert set(p["layers_4"]) == set(p["mtp_layer"]) == {
        "input_norm", "mla", "post_attn_norm", "moe", "mixer_hc", "ffn_hc"}
    assert {k for k in p if k.startswith("mtp")} == {
        "mtp_hidden_norm", "mtp_embed_norm", "mtp_proj", "mtp_layer", "mtp_norm"}
    assert p["mtp_proj"]["kernel"].shape == (256, 128)
    mla = p["layers_0"]["mla"]
    assert set(mla) == {"q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj",
                        "kv_a_norm", "kv_b_proj", "o_proj"}
    assert mla["q_a_proj"]["kernel"].shape == (128, 48)
    assert mla["q_b_proj"]["kernel"].shape == (48, 4, 32)
    hc = p["layers_2"]["ffn_hc"]
    assert {k: v.shape for k, v in hc.items()} == {
        "phi": (512, 24), "alpha": (3,), "b_pre": (4,), "b_post": (4,), "b_res": (4, 4)}
    moe = p["layers_1"]["moe"]
    assert moe["router"]["kernel"].shape == (128, 16)  # the router's width
    assert moe["w_gate"].shape == (4, 128, 64)  # the experts held
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(cells.load_json(CONFIG))
    assert (full.hidden_size, full.intermediate_size, full.expert_width,
            full.num_heads, full.kv_lora_rank, full.q_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim,
            full.rms_eps, full.rope_theta) == (
        3584, 9216, 1024, 32, 512, 768, 128, 64, 128, 1e-6, 10000)
    assert (full.num_experts, full.experts_held, full.num_experts_per_tok,
            full.vocab_size, full.num_layers, full.tie_embeddings,
            full.num_nextn_predict_layers) == (64, (0, 16), 4, 32768, 5, False, 1)
    assert full.layers == cfg.layers and full.rope_scaling == PUBLISHED_YARN
    assert full.hyper_connections == cfg.hyper_connections
    assert full.param_dtype == full.dtype == jnp.bfloat16


# ----------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(system_logits, expected_logits):
    system, expected = system_logits, expected_logits
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-4, "min_share_within": 1.0})
    assert result["ok"], result


def further_logits(model, params, ids, targets):
    _, predicted = model.apply(params, ids[None], return_hidden=True, next_ids=targets[None])
    return predicted[0].astype(jnp.float32) @ params["params"]["lm_head"]["kernel"].astype(jnp.float32)


def test_the_modules_logits_agree_with_the_reference_in_float32(xing4_f32, expected_logits):
    config, model, params, ids = xing4_f32
    targets = np.roll(ids, -1)
    with jax.default_matmul_precision("highest"):
        system = further_logits(model, params, ids, targets)
    expected = reference.mtp_logits(params, ids, targets, config, SEQ)
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-4, "min_share_within": 1.0})
    assert result["ok"], result
    # and they are not the main head's
    assert not logits_agreement(system, expected_logits, LOOSE)["ok"]


@pytest.mark.parametrize("wrong", sorted(wrong_xing4.references(None)))
def test_a_wrong_reference_is_far_from_the_program(
        xing4_f32, system_logits, wrong, monkeypatch):
    """``wrong_xing4.py``'s references of another function: Sinkhorn on rows
    alone, H_post without its 2, constant maps, an un-normed q latent."""
    config, model, params, ids = xing4_f32
    name, replacement = wrong_xing4.references(None)[wrong]
    monkeypatch.setattr(reference, name, replacement(getattr(reference, name)))
    result = logits_agreement(
        system_logits, reference.forward(params, ids, config, SEQ), LOOSE)
    assert not result["ok"], result
