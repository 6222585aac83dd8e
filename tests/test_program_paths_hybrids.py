"""The in-graph scopes of the hybrids over a KDA, a scalar-decay, a state-space
and a short-convolution mixer (Solar-Open2, Olmo-Hybrid, Granite, LFM2): the
``op_name`` of every instruction of a tiny model's compiled train step, on the
CPU (``tests/program_paths.py`` has the reading and the cases every family
passes).
"""
import os

import jax.numpy as jnp
import pytest

from ray_tpu.util import tracing

from program_paths import (
    MOE_SCOPES, a_step_shows_the_names_it_is_listed_for, compiled_step,
    every_instruction_path_names_a_part_of_the_program, pass_of, paths_in,
    paths_of, the_loss_and_the_chunked_head_carry_their_scopes,
)


@pytest.fixture(scope="module")
def solar_paths():
    """Paths of a tiny Solar-Open2's compiled train step: a softmax layer
    without rotation under a gate of q's width, then a KDA layer that doubles
    its write strength, each over the expert layer with its shared expert."""
    from ray_tpu.models.llama import chunked_causal_lm_loss
    from ray_tpu.models.solar_open2 import (
        SolarOpen2ForCausalLM, solar_open2_config,
    )

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # read when gmm is traced
    try:
        cfg = solar_open2_config(
            linear_attn_config={"num_heads": 2, "head_dim": 16,
                                "short_conv_kernel_size": 4, "num_kv_heads": None},
            gqa_layers=[0, 4], first_k_dense_replace=0, num_layers=2,
            num_experts_held=2, vocab_size=128, hidden_size=32,
            intermediate_size=64, moe_intermediate_size=16, num_heads=4,
            num_kv_heads=2, head_dim=16, num_experts=10, num_experts_per_tok=2,
            num_shared_experts=1, use_rope=False, use_gqa_gate=True,
            kda_allow_neg_eigval=True,
        )
        model = SolarOpen2ForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


@pytest.fixture(scope="module")
def olmo_paths():
    """Paths of a tiny Olmo-Hybrid's compiled train step: a Gated DeltaNet
    layer (key heads of 16, value heads of 32) and a rotation-free, QK-normed
    full layer, each over the dense MLP, the norms after the sublayers."""
    from ray_tpu.models.llama import chunked_causal_lm_loss
    from ray_tpu.models.olmo_hybrid import (
        OlmoHybridForCausalLM, olmo_hybrid_config,
    )

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # the scan's kernels, as on the chip
    try:
        cfg = olmo_hybrid_config(
            layer_types=["linear_attention", "full_attention"], num_layers=2,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=32,
            linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
            rope_parameters={"rope_theta": None}, vocab_size=128, hidden_size=32,
            intermediate_size=64, num_heads=4, num_kv_heads=4, head_dim=8,
        )
        model = OlmoHybridForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


@pytest.fixture(scope="module")
def granite_paths():
    """Paths of a tiny Granite 4.0-H's compiled train step: a Mamba-2 layer (4
    heads of 16 over a state of 16, a biased filter) and a NoPE attention
    layer at a scale of its own, each over the dense MLP, under the three
    multipliers, the head tied."""
    from ray_tpu.models.granite_hybrid import (
        GraniteHybridForCausalLM, granite_hybrid_config,
    )
    from ray_tpu.models.llama import chunked_causal_lm_loss

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # the kernels, as on the chip
    try:
        cfg = granite_hybrid_config(
            layer_types=["mamba", "attention"], num_layers=2,
            embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
            attention_multiplier=0.0625, shared_intermediate_size=64,
            mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
            vocab_size=128, hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        )
        model = GraniteHybridForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


@pytest.fixture(scope="module")
def lfm2_paths():
    """Paths of a tiny LFM2-MoE's compiled train step: source layers 1 and 2,
    a gated short convolution over the dense MLP and an attention layer under
    a per-head QK norm over the expert layer with every expert held, the head
    tied."""
    from ray_tpu.models.lfm2 import Lfm2ForCausalLM, lfm2_config
    from ray_tpu.models.llama import chunked_causal_lm_loss

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # the kernels, as on the chip
    try:
        cfg = lfm2_config(
            layer_types=["conv", "conv", "full_attention"], num_dense_layers=2,
            first_layer=1, num_layers=2, conv_L_cache=3, vocab_size=128,
            hidden_size=128, intermediate_size=64, moe_intermediate_size=128,
            num_heads=4, num_kv_heads=2, head_dim=32, num_experts=4,
            num_experts_per_tok=2,
        )
        model = Lfm2ForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


def test_a_short_convolution_hybrid_carries_its_scopes(lfm2_paths):
    """What model.shortconv_share, model.gqa_share, model.mlp_share and
    model.moe_share select by: /shortconv/ with ``conv_in``, ``gated_conv``
    and ``conv_out`` inside it (the projections' flax names under them) and no
    ``conv`` scope of the KDA, GDN and Mamba mixers' readers; /attn/ with
    ``qk_norm`` (a head's channels) and ``rotary``; the dense MLP in the layer
    the source counts below ``num_dense_layers`` and the expert layer's scopes
    in the other."""
    conv = [p for p in lfm2_paths if "/layers_0/shortconv/" in p]
    attn = [p for p in lfm2_paths if "/layers_1/attn/" in p]
    assert conv and attn and not [
        p for p in lfm2_paths if "/layers_1/shortconv/" in p or "/layers_0/attn/" in p]
    for name in (tracing.SHORTCONV_IN, tracing.SHORTCONV_GATED, tracing.SHORTCONV_OUT):
        assert any(f"/shortconv/{name}/" in p for p in conv), name
        assert not [p for p in attn if f"/{name}/" in p], name
    assert any(f"/shortconv/{tracing.SHORTCONV_IN}/in_proj/" in p for p in conv)
    assert any(f"/shortconv/{tracing.SHORTCONV_OUT}/out_proj/" in p for p in conv)
    assert not [p for p in lfm2_paths if f"/{tracing.KDA_CONV}/" in p]
    # every operation of the mixer lies under one of the three
    inside = (tracing.SHORTCONV_IN, tracing.SHORTCONV_GATED, tracing.SHORTCONV_OUT)
    assert not [p for p in conv if not any(f"/shortconv/{n}/" in p for n in inside)]
    for name in (tracing.QK_NORM, tracing.ATTN_ROPE):
        assert any(f"/attn/{name}/" in p for p in attn), name
    assert any(f"/attn/{tracing.QK_NORM}/q_norm/" in p for p in attn)
    assert not [p for p in lfm2_paths if f"/{tracing.ATTN_GATE}/" in p]
    for mixer in (conv, attn):
        assert {pass_of(p) for p in mixer} >= {"forward", "backward"}
    assert any("/layers_0/mlp/" in p for p in lfm2_paths)
    assert not [p for p in lfm2_paths if "/layers_0/moe/" in p or "/layers_1/mlp/" in p]
    for name in MOE_SCOPES:
        assert any(f"/layers_1/moe/{name}/" in p for p in lfm2_paths), name
    assert not [p for p in lfm2_paths if f"/{tracing.MOE_SHARED}/" in p]


def test_a_state_space_hybrid_carries_its_scopes(granite_paths):
    """What model.mamba_share, model.mamba_conv_share and model.gqa_share
    select by: /mamba/ with ``conv`` (the filter, its bias and SiLU), ``step``
    (the softplus) and ``norm`` (the gate and the one norm over every head's
    channels) inside it; /attn/ with no ``rotary`` and no ``qk_norm``; the
    multipliers under the names of what they scale."""
    mamba = [p for p in granite_paths if "/layers_0/mamba/" in p]
    attn = [p for p in granite_paths if "/layers_1/attn/" in p]
    assert mamba and attn and not [
        p for p in granite_paths if "/layers_1/mamba/" in p or "/layers_0/attn/" in p]
    for name in (tracing.KDA_CONV, tracing.MAMBA_STEP, tracing.MAMBA_NORM):
        assert any(f"/mamba/{name}/" in p for p in mamba), name
        assert not [p for p in attn if f"/{name}/" in p], name
    for name in (tracing.ATTN_ROPE, tracing.QK_NORM, tracing.ATTN_GATE):
        assert not [p for p in granite_paths if f"/{name}/" in p], name
    for mixer in (mamba, attn):
        assert {pass_of(p) for p in mixer} >= {"forward", "backward", "replay"}
    # the softplus is the step's and the logistic of the gate the norm's
    assert any(f"/mamba/{tracing.MAMBA_NORM}/" in p and p.endswith("/rsqrt") for p in mamba)
    assert any(f"/{tracing.EMBED}/mul" in p for p in granite_paths)  # embedding x 12
    assert any(f"/{tracing.FINAL_NORM}/mul" in p for p in granite_paths)  # / 8


def test_a_kda_hybrid_over_unrotated_gated_attention_carries_its_scopes(solar_paths):
    """What model.gqa_share, model.attn_gate_share and model.kda_share select
    by in a model whose full layers are ``Attention`` of a kind that turns
    nothing: /attn/ with its ``out_gate`` (the gate's projection inside) and
    no ``rotary`` scope anywhere; /kda/ with ``conv``, ``gate`` (beta's
    doubling lies there) and ``scan``; the expert layer's scopes in both."""
    attn = [p for p in solar_paths if "/layers_0/attn/" in p]
    kda = [p for p in solar_paths if "/layers_1/kda/" in p]
    assert not [p for p in solar_paths if f"/{tracing.ATTN_ROPE}/" in p]
    assert not [p for p in solar_paths
                if "/layers_0/kda/" in p or "/layers_1/attn/" in p or "/mla/" in p]
    assert {pass_of(p) for p in attn if f"/attn/{tracing.ATTN_GATE}/" in p} >= {
        "forward", "backward"}
    assert any(f"/attn/{tracing.ATTN_GATE}/g_proj/" in p for p in attn)
    assert any("/attn/q_proj/" in p for p in attn)
    for name in (tracing.KDA_CONV, tracing.KDA_GATE, tracing.KDA_SCAN):
        assert any(f"/kda/{name}/" in p for p in kda), name
    doubled = [p for p in kda if f"/kda/{tracing.KDA_GATE}/" in p and "mul" in p]
    assert doubled and {pass_of(p) for p in kda} >= {"forward", "backward", "replay"}
    for layer in ("layers_0", "layers_1"):
        for name in (*MOE_SCOPES, tracing.MOE_SHARED):
            assert any(f"/{layer}/moe/{name}/" in p for p in solar_paths), (layer, name)


def test_a_scalar_decay_hybrid_whose_norms_follow_the_sublayers_carries_its_scopes(olmo_paths):
    """What model.gdn_share selects by (/gdn/ with ``conv``, ``gate`` and
    ``scan`` inside it, forward, replay and backward) and what names the two
    norms a layer of the reordered kind has: ``post_mixer_norm`` and
    ``post_ffn_norm`` in every layer, no ``input_norm`` or ``post_attn_norm``
    anywhere; the full layer under /attn/ with ``qk_norm`` and no ``rotary``;
    no /kda/."""
    gdn = [p for p in olmo_paths if "/layers_0/gdn/" in p]
    attn = [p for p in olmo_paths if "/layers_1/attn/" in p]
    assert gdn and attn and not [
        p for p in olmo_paths
        if "/layers_1/gdn/" in p or "/layers_0/attn/" in p or "/kda/" in p]
    for name in (tracing.KDA_CONV, tracing.KDA_GATE, tracing.KDA_SCAN):
        assert any(f"/gdn/{name}/" in p for p in gdn), name
    assert {pass_of(p) for p in gdn} >= {"forward", "backward", "replay"}
    assert any(f"/attn/{tracing.QK_NORM}/" in p for p in attn)
    assert not [p for p in olmo_paths if f"/{tracing.ATTN_ROPE}/" in p]
    for layer in ("layers_0", "layers_1"):
        for name in (tracing.POST_MIXER_NORM, tracing.POST_FFN_NORM, tracing.MLP):
            assert any(f"/{layer}/{name}/" in p for p in olmo_paths), (layer, name)
    assert not [p for p in olmo_paths
                if f"/{tracing.INPUT_NORM}/" in p or f"/{tracing.POST_ATTN_NORM}/" in p]


# This file's compiled steps, by fixture (and dispatch branch).
FAMILIES = ("solar_paths", "olmo_paths", "granite_paths", "lfm2_paths")


@pytest.mark.parametrize("family", FAMILIES)
def test_every_instruction_path_names_a_part_of_the_program(request, family):
    every_instruction_path_names_a_part_of_the_program(paths_in(request, family))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_loss_and_the_chunked_head_carry_their_scopes(request, family):
    the_loss_and_the_chunked_head_carry_their_scopes(paths_in(request, family), family)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_step_shows_the_names_it_is_listed_for(request, family):
    a_step_shows_the_names_it_is_listed_for(paths_in(request, family), family)
