"""The in-graph scopes of dense Llama's three (plain, with a QK norm, with tied
embeddings) and the full-and-sliding pair (Laguna), which is held against
plain Llama's: the ``op_name`` of every instruction of a tiny model's compiled
train step, on the CPU (``tests/program_paths.py`` has the reading and the
cases every family passes).
"""
import dataclasses
import os

import jax.numpy as jnp
import pytest

from ray_tpu.util import tracing

from program_paths import (
    a_step_shows_the_names_it_is_listed_for, compiled_step,
    every_instruction_path_names_a_part_of_the_program, pass_of, paths_in,
    paths_of, the_loss_and_the_chunked_head_carry_their_scopes,
)


@pytest.fixture(scope="module")
def llama_paths():
    from ray_tpu.models import CONFIGS, LlamaForCausalLM
    from ray_tpu.models.llama import causal_lm_loss

    cfg = dataclasses.replace(CONFIGS["llama-tiny"], remat=True)
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    return paths_of(compiled_step(
        model, lambda p, i, t: causal_lm_loss(model.apply(p, i), t), ids
    ))


@pytest.fixture(scope="module")
def qk_norm_paths():
    """Paths of a tiny Llama with OLMoE's QK-norm."""
    from ray_tpu.models import CONFIGS, LlamaForCausalLM
    from ray_tpu.models.llama import causal_lm_loss

    cfg = dataclasses.replace(CONFIGS["llama-tiny"], remat=True, qk_norm=True)
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    return paths_of(compiled_step(
        model, lambda p, i, t: causal_lm_loss(model.apply(p, i), t), ids
    ))


@pytest.fixture(scope="module")
def tied_paths():
    """Paths of a tiny Llama whose head is the embedding table's ``attend``
    (the benchmark's Mixtral cell ties them)."""
    from ray_tpu.models import CONFIGS, LlamaForCausalLM
    from ray_tpu.models.llama import causal_lm_loss

    cfg = dataclasses.replace(CONFIGS["llama-tiny"], remat=True, tie_embeddings=True)
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    return paths_of(compiled_step(
        model, lambda p, i, t: causal_lm_loss(model.apply(p, i), t), ids
    ))


@pytest.fixture(scope="module")
def laguna_paths():
    """Paths of a tiny laguna model's compiled train step: a full-attention
    layer over a dense FFN and a sliding-window layer over the expert layer,
    each mixer with its own head count, rotation and output gate."""
    from ray_tpu.models.laguna import LagunaForCausalLM, laguna_config
    from ray_tpu.models.llama import chunked_causal_lm_loss

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # read when gmm is traced
    try:
        cfg = laguna_config(
            num_layers=2, layer_types=["full_attention", "sliding_attention"],
            mlp_layer_types=["dense", "sparse"],
            num_attention_heads_per_layer=[2, 4], sliding_window=16,
            rope_parameters={
                "full_attention": {
                    "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                    "original_max_position_embeddings": 4096, "beta_slow": 1,
                    "beta_fast": 64, "attention_factor": 1.4158883,
                    "partial_rotary_factor": 0.5},
                "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                      "partial_rotary_factor": 1}},
            shared_expert_intermediate_size=16, num_experts_held=2,
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_heads=2, num_kv_heads=2, head_dim=16,
            num_experts=8, num_experts_per_tok=2, routed_scaling_factor=2.5,
        )
        model = LagunaForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


def test_full_and_sliding_mixers_carry_their_names_rotation_and_gate(
        laguna_paths, llama_paths):
    """What the benchmark's model.swa_share selects by: a sliding layer's
    mixer is /swa/ and a full layer's stays /attn/; inside each the rotation
    and the output gate, forward and backward, the projections outside."""
    for layer, mixer, other in (("layers_0", tracing.ATTN, tracing.SWA),
                                ("layers_1", tracing.SWA, tracing.ATTN)):
        mine = [p for p in laguna_paths if f"/{layer}/{mixer}/" in p]
        assert not [p for p in laguna_paths if f"/{layer}/{other}/" in p]
        for name in (tracing.ATTN_ROPE, tracing.ATTN_GATE):
            assert {pass_of(p) for p in mine if f"/{mixer}/{name}/" in p} >= {
                "forward", "backward"}, (layer, name)
        assert any(f"/{mixer}/{tracing.ATTN_GATE}/g_proj/" in p for p in mine)
        assert any(f"/{mixer}/q_proj/" in p for p in mine)
        assert not [p for p in mine if f"/{tracing.ATTN_ROPE}/" in p and "proj" in p]
    assert any("/layers_0/mlp/" in p for p in laguna_paths)
    assert any(f"/layers_1/moe/{tracing.MOE_SHARED}/shared/" in p for p in laguna_paths)
    # a Llama layer is all one kind: rotated under the same scope, no gate
    assert any(f"/attn/{tracing.ATTN_ROPE}/" in p for p in llama_paths)
    assert not [p for p in llama_paths
                if f"/{tracing.ATTN_GATE}/" in p or f"/{tracing.SWA}/" in p]



def test_optimizer_scope_is_on_the_update_and_nowhere_in_the_model(llama_paths):
    scoped = [p for p in llama_paths if "/optimizer/" in p]
    assert len(scoped) > 10
    assert not [p for p in scoped if "jvp(" in p or "transpose(" in p]
    # adamw's own arithmetic and apply_updates are both inside
    assert any(p.endswith(("/sqrt", "/rsqrt")) for p in scoped), scoped[:5]
    assert all(p.startswith("jit(train_step)/optimizer/") for p in scoped)


def test_every_instruction_is_forward_backward_replay_or_optimizer(llama_paths):
    passes = {p: pass_of(p) for p in llama_paths}
    assert not [p for p, c in passes.items() if c is None]
    assert set(passes.values()) == {"forward", "backward", "replay", "optimizer"}


def test_qk_norm_scope_holds_both_norms_and_only_where_the_model_has_them(
    llama_paths, qk_norm_paths
):
    scoped = [p for p in qk_norm_paths if f"/attn/{tracing.QK_NORM}/" in p]
    for norm in ("q_norm", "k_norm"):
        for kind in ("forward", "backward"):
            assert [p for p in scoped
                    if f"/{tracing.QK_NORM}/{norm}/" in p and pass_of(p) == kind], (
                norm, kind)
    assert not [p for p in scoped if p.endswith("/dot_general")]
    assert not [p for p in llama_paths if f"/{tracing.QK_NORM}/" in p]


# This file's compiled steps, by fixture (and dispatch branch).
FAMILIES = ("llama_paths", "qk_norm_paths", "tied_paths", "laguna_paths")


@pytest.mark.parametrize("family", FAMILIES)
def test_every_instruction_path_names_a_part_of_the_program(request, family):
    every_instruction_path_names_a_part_of_the_program(paths_in(request, family))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_loss_and_the_chunked_head_carry_their_scopes(request, family):
    the_loss_and_the_chunked_head_carry_their_scopes(paths_in(request, family), family)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_step_shows_the_names_it_is_listed_for(request, family):
    a_step_shows_the_names_it_is_listed_for(paths_in(request, family), family)
