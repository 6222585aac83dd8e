"""The in-graph scopes of the models that choose what a row sees (dots3's indexer
over latent attention, MiniCPM-SALA's block selection beside lightning
attention), held against sarvam's plain latent attention: the ``op_name`` of
every instruction of a tiny model's compiled train step, on the CPU
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
def sala_paths():
    """Paths of a tiny MiniCPM-SALA's compiled train step: a block-sparse
    top-k layer (4 heads over 2 K/V heads, sparse from 33 tokens on) and a
    Lightning layer, each over the dense MLP, under the muP scales."""
    from ray_tpu.models.llama import chunked_causal_lm_loss
    from ray_tpu.models.minicpm_sala import (
        MiniCPMSalaForCausalLM, minicpm_sala_config,
    )

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # the kernels, as on the chip
    try:
        cfg = minicpm_sala_config(
            mixer_types=["minicpm4", "lightning-attn"], num_layers=2,
            published_layers=32, scale_emb=12, scale_depth=1.4, dim_model_base=16,
            lightning_nh=4, lightning_nkv=4, lightning_head_dim=8,
            sparse_config={"kernel_size": 8, "kernel_stride": 4, "block_size": 16,
                           "topk": 4, "init_blocks": 1, "window_size": 32,
                           "dense_len": 32},
            vocab_size=100, hidden_size=32, intermediate_size=64, num_heads=4,
            num_kv_heads=2, head_dim=8,
        )
        model = MiniCPMSalaForCausalLM(cfg)
        ids = jnp.zeros((1, 128), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=64),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


# ----------------------------------------------------------- in-graph scopes


@pytest.fixture(scope="module")
def dots3_paths():
    """Paths of a tiny dots3 model's compiled train step: a full latent layer
    under the indexer's selection over a dense FFN and a sliding latent layer
    of other widths over the expert layer, a gate a head in both."""
    from ray_tpu.models.dots3 import Dots3ForCausalLM, dots3_config
    from ray_tpu.models.llama import chunked_causal_lm_loss

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # read when gmm is traced
    try:
        cfg = dots3_config(
            num_layers=2, layer_types=["full_attention", "sliding_attention"],
            first_k_dense_replace=1, num_heads=2, num_heads_published=4,
            swa_num_heads=2, swa_num_heads_published=4, q_lora_rank=16,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=8, rope_theta=1e4, swa_q_lora_rank=16,
            swa_kv_lora_rank=24, swa_qk_nope_head_dim=16,
            swa_qk_rope_head_dim=8, swa_v_head_dim=8, swa_rope_theta=5e4,
            sliding_window_size=17, index_n_heads=2, index_head_dim=16,
            index_topk=24, attention_gate_type="headwise",
            swa_attention_gate_type="headwise",
            apply_mla_qkv_lora_rescale=True, num_experts_held=2,
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
            num_shared_experts=1,
        )
        model = Dots3ForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


def test_full_and_sliding_latent_mixers_carry_their_names_indexer_and_gate(
        dots3_paths, sarvam_paths, sala_paths):
    """What the benchmark's model.dsa_share, model.dsa_index_share and
    model.swa_mla_share select by: a sliding layer's latent mixer is /swa_mla/
    and a full layer's stays /mla/; inside the full one ``indexer`` (its three
    projections and the key's LayerNorm) and ``select``, forward alone, which
    a reader tells from the sparse mixer's ``select`` by the mixer above it;
    inside each the latents, the rotation and the output gate, forward and
    backward."""
    full = [p for p in dots3_paths if f"/layers_0/{tracing.MLA}/" in p]
    sliding = [p for p in dots3_paths if f"/layers_1/{tracing.SWA_MLA}/" in p]
    assert full and sliding
    assert not [p for p in dots3_paths
                if f"/layers_0/{tracing.SWA_MLA}/" in p or f"/layers_1/{tracing.MLA}/" in p]
    for mixer, mine in ((tracing.MLA, full), (tracing.SWA_MLA, sliding)):
        for name in (tracing.MLA_LATENT, tracing.MLA_Q_LATENT, tracing.MLA_ROPE,
                     tracing.ATTN_GATE):
            assert {pass_of(p) for p in mine if f"/{mixer}/{name}/" in p} >= {
                "forward", "backward"}, (mixer, name)
        assert any(f"/{mixer}/{tracing.ATTN_GATE}/g_proj/" in p for p in mine)
        assert any(f"/{mixer}/o_proj/" in p for p in mine)
    for name in (tracing.INDEXER, tracing.SPARSE_SELECT):
        scoped = [p for p in full if f"/{tracing.MLA}/{name}/" in p]
        assert scoped and {pass_of(p) for p in scoped} <= {"forward", "replay"}, name
        assert not [p for p in sliding if f"/{name}/" in p], name
    for module in ("index_q_proj", "index_k_proj", "index_k_norm", "index_w_proj"):
        assert any(f"/{tracing.MLA}/{tracing.INDEXER}/{module}/" in p for p in full), module
    # the other models' /mla/ has neither, and the sparse mixer's select is its own
    assert not [p for p in sarvam_paths
                if f"/{tracing.INDEXER}/" in p or f"/{tracing.SPARSE_SELECT}/" in p]
    assert not [p for p in sala_paths if f"/{tracing.MLA}/" in p]
    assert any("/layers_0/mlp/" in p for p in dots3_paths)
    assert any(f"/layers_1/moe/{tracing.MOE_SHARED}/shared/" in p for p in dots3_paths)


def test_a_sparse_and_lightning_hybrid_carries_its_scopes(sala_paths):
    """What model.sparse_share, model.sparse_select_share and
    model.lightning_share select by: /sparse/ with ``select`` (forward alone:
    nothing of the choice is differentiated, and the replay is handed the
    set), ``qk_norm`` and ``out_gate`` inside it and no ``rotary``;
    /lightning/ with ``qk_norm`` and ``rotary``; the muP scales under the
    names of what they scale."""
    sparse = [p for p in sala_paths if "/layers_0/sparse/" in p]
    lightning = [p for p in sala_paths if "/layers_1/lightning/" in p]
    assert sparse and lightning and not [
        p for p in sala_paths
        if "/layers_1/sparse/" in p or "/layers_0/lightning/" in p or "/attn/" in p]
    for name in (tracing.SPARSE_SELECT, tracing.QK_NORM, tracing.ATTN_GATE):
        assert any(f"/sparse/{name}/" in p for p in sparse), name
    assert not [p for p in sparse if f"/{tracing.ATTN_ROPE}/" in p]
    select = [p for p in sparse if f"/sparse/{tracing.SPARSE_SELECT}/" in p]
    assert {pass_of(p) for p in select} == {"forward"}
    for name in (tracing.QK_NORM, tracing.ATTN_ROPE):
        assert any(f"/lightning/{name}/" in p for p in lightning), name
    assert not [p for p in lightning if f"/{tracing.SPARSE_SELECT}/" in p]
    for mixer in (sparse, lightning):
        assert {pass_of(p) for p in mixer} >= {"forward", "backward", "replay"}
    # the vocabulary of 100 fills no lane: the loss takes its gold logit by a
    # select and a sum, and gathers nothing
    loss = [p for p in sala_paths if f"({tracing.LOSS})" in p]
    assert loss and not [p for p in loss if p.endswith("/gather")]


# This file's compiled steps, by fixture (and dispatch branch).
FAMILIES = ("sala_paths", "dots3_paths")


@pytest.mark.parametrize("family", FAMILIES)
def test_every_instruction_path_names_a_part_of_the_program(request, family):
    every_instruction_path_names_a_part_of_the_program(paths_in(request, family))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_loss_and_the_chunked_head_carry_their_scopes(request, family):
    the_loss_and_the_chunked_head_carry_their_scopes(paths_in(request, family), family)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_step_shows_the_names_it_is_listed_for(request, family):
    a_step_shows_the_names_it_is_listed_for(paths_in(request, family), family)
