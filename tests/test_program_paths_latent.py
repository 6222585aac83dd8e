"""The in-graph scopes of latent attention where a model rotates and norms it
(sarvam) and where it does not (Kimi-Linear, the KDA hybrid): the ``op_name``
of every instruction of a tiny model's compiled train step, on the CPU
(``tests/program_paths.py`` has the reading and the cases every family
passes).
"""
import os

import jax.numpy as jnp
import pytest

from ray_tpu.util import tracing

from program_paths import (  # noqa: F401 - fixtures
    a_step_shows_the_names_it_is_listed_for, compiled_step,
    every_instruction_path_names_a_part_of_the_program, pass_of, paths_in,
    paths_of, sarvam_paths, the_loss_and_the_chunked_head_carry_their_scopes,
)


@pytest.fixture(scope="module")
def kimi_paths():
    """Paths of a tiny Kimi-Linear's compiled train step: a leading dense
    layer under KDA, KDA and MLA over the expert layer with its shared
    expert, through the chunked loss."""
    from ray_tpu.models.kimi_linear import (
        KimiLinearForCausalLM, kimi_linear_config,
    )
    from ray_tpu.models.llama import chunked_causal_lm_loss

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # read when gmm is traced
    try:
        cfg = kimi_linear_config(
            linear_attn_config={"kda_layers": [1, 2], "full_attn_layers": [3],
                                "num_heads": 2, "head_dim": 16,
                                "short_conv_kernel_size": 4},
            first_k_dense_replace=1, moe_layer_freq=1, num_layers=3,
            num_experts_held=2, vocab_size=128, hidden_size=32,
            intermediate_size=64, moe_intermediate_size=16, num_heads=2,
            num_kv_heads=2, num_experts=8, num_experts_per_tok=2,
            num_shared_experts=1, routed_scaling_factor=2.446, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        )
        model = KimiLinearForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


def test_latent_attention_carries_rope_and_qk_norm_where_a_model_has_them(
        sarvam_paths, kimi_paths):
    """What the benchmark's model.mla_rotary_share selects by: the rotation
    and the per-head norm inside /mla/, forward, backward and replay, in every
    layer of the model that has them and in none of Kimi-Linear's."""
    for layer in ("layers_0", "layers_1"):
        mla = [p for p in sarvam_paths if f"/{layer}/mla/" in p]
        for name in (tracing.MLA_ROPE, tracing.QK_NORM, tracing.MLA_LATENT):
            scoped = [p for p in mla if f"/mla/{name}/" in p]
            assert scoped, (layer, name)
        for name in (tracing.MLA_ROPE, tracing.QK_NORM):
            assert {pass_of(p) for p in mla if f"/mla/{name}/" in p} >= {
                "forward", "backward"}, (layer, name)
        assert any(f"/mla/{tracing.QK_NORM}/q_norm/" in p for p in mla)
        assert any(f"/mla/{tracing.QK_NORM}/k_norm/" in p for p in mla)
    # the projections and the latent are outside both scopes
    assert not [p for p in sarvam_paths if "/rope/" in p and "proj" in p]
    assert any("/layers_0/mlp/" in p for p in sarvam_paths)
    assert any(f"/layers_1/moe/{tracing.MOE_SHARED}/shared/" in p for p in sarvam_paths)
    assert not [p for p in sarvam_paths if "/attn/" in p or "/kda/" in p]
    for name in (tracing.MLA_ROPE, tracing.QK_NORM):
        assert not [p for p in kimi_paths if f"/mla/{name}/" in p], name


def test_the_hybrid_carries_its_mixers_names_and_scopes(kimi_paths):
    """What the benchmark's model.kda_share and model.mla_share select by,
    and the scopes inside the two mixers and the shared expert."""
    for name in (tracing.KDA, tracing.MLA):  # the two of MIXERS it has
        assert any(f"/{name}/" in p for p in kimi_paths), name
    kda = [p for p in kimi_paths if "/kda/" in p]
    for name in (tracing.KDA_CONV, tracing.KDA_GATE, tracing.KDA_SCAN):
        assert any(f"/kda/{name}/" in p for p in kda), name
    # o's RMSNorm and gate are the scan kernels': no operation is left for a
    # scope of their own to name, and the parameter keeps RMSNorm's path
    assert not [p for p in kda if "o_norm" in p or "out_norm" in p]
    assert any(f"/mla/{tracing.MLA_LATENT}/kv_b_proj/" in p for p in kimi_paths)
    assert any(f"/moe/{tracing.MOE_SHARED}/shared/" in p for p in kimi_paths)
    # layer 0 is dense under KDA, layer 2 is MLA over experts; forward,
    # backward and replay all carry the names
    assert any("/layers_0/kda/" in p for p in kimi_paths)
    assert any("/layers_0/mlp/" in p for p in kimi_paths)
    assert any("/layers_2/mla/" in p and "/layers_2/moe/" not in p for p in kimi_paths)
    assert {pass_of(p) for p in kda} >= {"forward", "backward", "replay"}
    assert not [p for p in kimi_paths if "/attn/" in p]


# This file's compiled steps, by fixture (and dispatch branch).
FAMILIES = ("kimi_paths", "sarvam_paths")


@pytest.mark.parametrize("family", FAMILIES)
def test_every_instruction_path_names_a_part_of_the_program(request, family):
    every_instruction_path_names_a_part_of_the_program(paths_in(request, family))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_loss_and_the_chunked_head_carry_their_scopes(request, family):
    the_loss_and_the_chunked_head_carry_their_scopes(paths_in(request, family), family)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_step_shows_the_names_it_is_listed_for(request, family):
    a_step_shows_the_names_it_is_listed_for(paths_in(request, family), family)
