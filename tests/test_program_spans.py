"""Program spans and scopes (``ray_tpu/util/tracing.py``).

In-graph scopes are checked on the compiled text of tiny models (CPU): the
``op_name`` of every instruction is what a device trace shows on the chip.
Host spans are checked in the xplane a CPU ``jax.profiler`` session writes:
the same file, lines and clock the benchmark's readers take from the chip.
"""
import contextlib
import dataclasses
import glob
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import optax
import pytest

import ray_tpu
from ray_tpu.train import make_train_step
from ray_tpu.util import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MOE_SCOPES = (tracing.MOE_ROUTER, tracing.MOE_DISPATCH, tracing.MOE_EXPERTS,
              tracing.MOE_COMBINE)
# On the CPU an expert matmul is a dot_general in every dispatch branch:
# ragged_dot lowers to one, and the Pallas kernel of "gmm" runs in interpret
# mode, as in test_moe_models.py.
BRANCHES = ("capacity", "gmm", "ragged")


def paths_of(compiled) -> list:
    """The ``op_name`` of every instruction that has a path (parameters and
    the bodies of reductions carry a bare name)."""
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return [n for n in names if n.startswith("jit(")]


def pass_of(path: str):
    """The benchmark readers' rule (benchmarks/lib/program_trace.py)."""
    classes = [
        "rematted_computation" in path,
        "transpose(" in path and "rematted_computation" not in path,
        "jvp(" in path and "transpose(" not in path,
        "/" + tracing.OPTIMIZER + "/" in path,
    ]
    if sum(classes) != 1:
        return None
    return ("replay", "backward", "forward", "optimizer")[classes.index(True)]


def compiled_step(model, loss_fn, ids):
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    tx = optax.adamw(1e-3)
    step = make_train_step(loss_fn, tx)
    return step.lower(params, tx.init(params), ids, ids).compile()


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
def moe_paths():
    """dispatch branch -> paths of a tiny Mixtral's compiled train step."""
    from ray_tpu.models.mixtral import CONFIGS, MixtralForCausalLM, moe_lm_loss

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # read when gmm is traced
    try:
        out = {}
        for branch in BRANCHES:
            cfg = dataclasses.replace(
                CONFIGS["mixtral-tiny"], moe_dispatch=branch, remat=True,
                remat_policy="nothing",
            )
            model = MixtralForCausalLM(cfg)
            ids = jnp.zeros((2, 64), jnp.int32)
            out[branch] = paths_of(compiled_step(
                model, lambda p, i, t, m=model: moe_lm_loss(m, p, i, t), ids
            ))
        return out
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


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


@pytest.fixture(scope="module")
def sarvam_paths():
    """Paths of a tiny sarvam_mla model's compiled train step: a dense and
    an expert layer, each under latent attention with its 8-wide parts
    rotated under YaRN and a per-head QK norm."""
    from ray_tpu.models.llama import chunked_causal_lm_loss
    from ray_tpu.models.sarvam_mla import SarvamMLAForCausalLM, sarvam_mla_config

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # read when gmm is traced
    try:
        cfg = sarvam_mla_config(
            num_layers=2, num_experts_held=2, vocab_size=128, hidden_size=32,
            intermediate_size=64, moe_intermediate_size=16, num_heads=2,
            num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
            routed_scaling_factor=2.5, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16,
            rope_scaling={"type": "deepseek_yarn", "factor": 40,
                          "original_max_position_embeddings": 4096,
                          "mscale": 1, "mscale_all_dim": 1},
        )
        model = SarvamMLAForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


@pytest.fixture(scope="module")
def xing4_paths():
    """Paths of a tiny xing4_0 model's compiled train step: a dense and an
    expert layer on four hyper-connected streams under latent attention with
    a q latent, and the multi-token-prediction module in the loss."""
    from ray_tpu.models.xing4 import (
        Xing4ForCausalLM, mtp_chunked_lm_loss, xing4_config,
    )

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # read when gmm is traced
    try:
        cfg = xing4_config(
            num_layers=2, first_k_dense_replace=1, num_experts_held=2,
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_heads=2, num_experts=8,
            num_experts_per_tok=2, num_shared_experts=1,
            routed_scaling_factor=2.0, kv_lora_rank=16, q_lora_rank=24,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_scaling={"type": "yarn", "factor": 64,
                          "original_max_position_embeddings": 4096,
                          "mscale": 1, "mscale_all_dim": 1},
        )
        model = Xing4ForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: mtp_chunked_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


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


def test_hyper_connections_q_latent_and_the_mtp_module_carry_their_scopes(
        xing4_paths, sarvam_paths):
    """What the benchmark's model.hc_share, model.hc_roofline and
    model.mtp_share select by: /hc/ around the maps, the read and the write of
    every sublayer and not around the sublayer itself; /mtp/ on the module and
    (mtp) on its pass of the head in the loss; the q latent inside /mla/."""
    hc = [p for p in xing4_paths if f"/{tracing.HC}/" in p]
    for layer in ("layers_0", "layers_1", "mtp_layer"):
        for where in ("mixer_hc", "ffn_hc"):
            mine = [p for p in hc if f"/{layer}/{where}/hc/" in p]
            assert any(f"/hc/{tracing.HC_PRE}/" in p for p in mine), (layer, where)
            assert any(f"/hc/{tracing.HC_SINKHORN}/" in p for p in mine), (layer, where)
        assert any(f"/{layer}/hc/{tracing.HC_POST}/" in p for p in hc), layer
    assert {pass_of(p) for p in hc} >= {"forward", "backward"}
    # the sublayers are outside: a mixer's or an FFN's time is not the path's
    assert not [p for p in hc if re.search(r"/(mla|mlp|moe)/", p)]
    assert any("/layers_1/mla/q_latent/q_a_proj/" in p for p in xing4_paths)
    assert any("/mla/q_latent/q_a_norm/" in p for p in xing4_paths)
    assert any("/mla/q_latent/q_b_proj/" in p for p in xing4_paths)
    assert any("/mla/rope/" in p for p in xing4_paths)
    assert not [p for p in xing4_paths if "/mla/qk_norm/" in p or "/mla/q_proj/" in p]
    module = [p for p in xing4_paths if f"/{tracing.MTP}/" in p]
    assert any("/mtp/mtp_proj/" in p for p in module)
    assert any("/mtp/mtp_layer/mla/" in p for p in module)
    assert any("/mtp/mtp_layer/moe/experts/" in p for p in module)
    assert any("/mtp/mtp_layer/mixer_hc/hc/" in p for p in module)
    head = [p for p in xing4_paths if f"({tracing.MTP})" in p]
    assert any("dot_general" in p for p in head)
    assert {pass_of(p) for p in head} >= {"forward", "backward"}
    # the main model's layers are not the module's
    assert not [p for p in module if "/layers_" in p]
    # and a model without them carries none of the names
    for name in (tracing.HC, tracing.MLA_Q_LATENT, tracing.MTP):
        assert not [p for p in sarvam_paths if f"/{name}/" in p], name


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


@pytest.mark.parametrize("branch", BRANCHES)
def test_moe_layer_carries_the_four_scopes(moe_paths, branch):
    in_moe = [p for p in moe_paths[branch] if "/moe/" in p]
    for name in MOE_SCOPES:
        assert any(f"/moe/{name}/" in p for p in in_moe), name
    # the flax scope stays in front, and nothing of the layer is unnamed
    unnamed = [p for p in in_moe
               if not re.search(r"/moe/(%s)/" % "|".join(MOE_SCOPES), p)]
    assert not unnamed, unnamed[:5]
    assert all(pass_of(p) in ("forward", "backward", "replay") for p in in_moe)


def test_gmm_dispatch_tells_its_index_work_from_its_row_gather(moe_paths):
    nested = f"/moe/{tracing.MOE_DISPATCH}/{tracing.MOE_LAYOUT}/"
    layout = [p for p in moe_paths["gmm"] if nested in p]
    assert any(p.endswith("/sort") for p in layout), layout[:5]  # the argsort
    assert not [p for p in layout if p.endswith("/dot_general")]
    # the row gather into the tile-aligned buffer is dispatch's own
    rows = [p for p in moe_paths["gmm"]
            if f"/moe/{tracing.MOE_DISPATCH}/" in p and nested not in p]
    assert any(p.endswith("/gather") for p in rows), rows[:5]
    for branch in ("capacity", "ragged"):
        assert not [p for p in moe_paths[branch] if f"/{tracing.MOE_LAYOUT}/" in p]


def test_gmm_moves_its_rows_by_gathers_forward_and_backward(moe_paths):
    def scatter_adds(branch):
        return [p for p in moe_paths[branch]
                if "/moe/" in p and p.endswith("/scatter-add")]

    # What is left adds scalars: the layout's bincount and the gradient of
    # the router's top_k.
    scalars = (f"/moe/{tracing.MOE_ROUTER}/",
               f"/moe/{tracing.MOE_DISPATCH}/{tracing.MOE_LAYOUT}/")
    assert not [p for p in scatter_adds("gmm")
                if not any(scope in p for scope in scalars)]
    # The oracle's combine is the scatter-add this check has to be able to see.
    assert [p for p in scatter_adds("ragged")
            if f"/moe/{tracing.MOE_COMBINE}/" in p]
    # The hand-written gradients' gathers keep their layer's scope.
    for scope in (tracing.MOE_DISPATCH, tracing.MOE_COMBINE):
        back = [p for p in moe_paths["gmm"] if p.endswith("/gather")
                and f"/moe/{scope}/" in p and f"/{tracing.MOE_LAYOUT}/" not in p
                and pass_of(p) == "backward"]
        assert back, scope


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


@pytest.mark.parametrize("branch", BRANCHES)
def test_expert_matmuls_are_under_experts_forward_and_backward(moe_paths, branch):
    matmuls = [p for p in moe_paths[branch]
               if "/moe/" in p and p.endswith("/dot_general")]
    experts = [p for p in matmuls if "/moe/experts/" in p]
    # outside `experts` the layer multiplies only in its router
    assert all("/moe/router/" in p for p in matmuls if p not in experts)
    per_pass = {c: [p for p in experts if pass_of(p) == c]
                for c in ("forward", "backward", "replay")}
    layers = 2
    assert len(per_pass["forward"]) >= 3 * layers, per_pass["forward"]
    assert len(per_pass["replay"]) >= 3 * layers
    assert len(per_pass["backward"]) >= 6 * layers  # two gradients a matmul


# Every family's compiled step, by fixture (and dispatch branch).
FAMILIES = ("llama_paths", "qk_norm_paths", "tied_paths", "moe_paths:capacity", "moe_paths:gmm",
            "moe_paths:ragged", "kimi_paths", "sarvam_paths", "xing4_paths",
            "laguna_paths", "solar_paths", "olmo_paths", "sala_paths", "granite_paths",
            "lfm2_paths", "dots3_paths")
# Paths that may hold no name of the program, and why.
EXEMPT = (
    # _positions' arange, inside the model's __call__ and outside every part:
    # integer positions shared by every layer, no device time of their own
    (r"^jit\(train_step\)/jvp\(\w+ForCausalLM\)/iota$", "positions"),
    # JAX's own, at a layer's checkpoint boundary: the transposed remat2
    # equation rounds the residual stream's summed cotangent to the stream's
    # dtype outside the layer's name, which flax opens inside the checkpoint.
    # No line of the program emits it; the benchmark's step.unnamed_share
    # reads what it costs on the chip (PERF.md 7)
    (r"^jit\(train_step\)/transpose\(jvp\((\w+ForCausalLM|mtp)\)\)/(jvp\(\w+\)/|mtp/)*remat2$",
     "remat boundary"),
)


def paths_in(request, family):
    fixture, _, branch = family.partition(":")
    paths = request.getfixturevalue(fixture)
    return paths[branch] if branch else paths


@pytest.mark.parametrize("family", FAMILIES)
def test_every_instruction_path_names_a_part_of_the_program(request, family):
    """The scope tree is closed over the step: the reader of the benchmark's
    step table (benchmarks/lib/step_table.py, by tracing's lists alone) finds
    a part for every instruction but the exempt, and a pass for each."""
    from benchmarks.lib import step_table

    nameless = [
        p for p in paths_in(request, family)
        if not step_table.part_of(p)[0]
        and not any(re.search(pattern, p) for pattern, _ in EXEMPT)
    ]
    assert not nameless, sorted(set(nameless))[:40]


LOSS_KINDS = {
    "llama_paths": "full", "qk_norm_paths": "full", "tied_paths": "full",
    "moe_paths:capacity": "full",
    "moe_paths:gmm": "full", "moe_paths:ragged": "full", "kimi_paths": "chunked",
    "sarvam_paths": "chunked", "laguna_paths": "chunked", "solar_paths": "chunked",
    "olmo_paths": "chunked", "sala_paths": "chunked", "granite_paths": "chunked",
    "lfm2_paths": "chunked", "dots3_paths": "chunked",
    "xing4_paths": "mtp",
}


@pytest.mark.parametrize("family", FAMILIES)
def test_the_loss_and_the_chunked_head_carry_their_scopes(request, family):
    """What the benchmark's model.head_loss_share selects by. A loss function
    is called outside every flax module, directly under the transform, so
    JAX renders its scope in the brackets: jvp(loss), transpose(jvp(loss)).
    The full-logit loss multiplies nothing (its matmul is the module
    lm_head, or the tied table's attend); the chunked one multiplies under
    ``head`` alone, three times a chunk and all of them forward: its rule
    (models/llama.py ``_chunked_nll``) makes a chunk's gradients in the
    forward scan, replays nothing and leaves the backward pass the cotangent's
    two scalings, under ``loss``; the MTP loss opens ``loss`` inside ``mtp``,
    so its second pass of the head reads jvp(mtp)/loss/ and "(mtp)" still
    finds it."""
    paths = paths_in(request, family)
    kind = LOSS_KINDS[family]
    top = [p for p in paths if f"({tracing.LOSS})" in p]
    # (a cotangent of 1.0 folds the chunked rule's scalings away, and with a
    # batch of one the reshapes back to [B, T, H] too)
    assert {pass_of(p) for p in top} - {"backward"} == {"forward"}
    assert kind != "full" or "backward" in {pass_of(p) for p in top}
    assert all(p.startswith((f"jit(train_step)/jvp({tracing.LOSS})/",
                             f"jit(train_step)/transpose(jvp({tracing.LOSS}))/"))
               for p in top)
    assert any(p.endswith("/reduce_max") for p in top)  # the logsumexp
    assert not [p for p in top if "ForCausalLM" in p or "/layers_" in p]
    matmuls = [p for p in top if p.endswith("/dot_general")]
    if kind == "full":
        assert not matmuls and not [p for p in paths if f"/{tracing.LOSS_HEAD}/" in p]
        # the module lm_head, or the tied table's attend under the head's name
        head = [p for p in paths if p.endswith("/dot_general")
                and f"/{tracing.LM_HEAD}/" in p]
        assert {pass_of(p) for p in head} >= {"forward", "backward"}
        assert not [p for p in paths if ".attend/" in p and f"/{tracing.LM_HEAD}/" not in p]
        assert (family == "tied_paths") == any(
            f"/{tracing.LM_HEAD}/{tracing.EMBED}.attend/dot_general" in p for p in paths)
        return
    the_heads = f"jit(train_step)/jvp({tracing.LOSS})/while/body/closed_call/head/dot_general"
    assert set(matmuls) == {the_heads} and pass_of(the_heads) == "forward"
    assert not [p for p in paths if "rematted_computation" in p and tracing.LOSS in p]
    # nothing but the matmuls, the casts around them and the sum into the
    # head's gradient (the product's own output fusion) is the head's
    assert {p.rpartition("/")[2] for p in paths if f"/{tracing.LOSS_HEAD}/" in p} <= {
        "dot_general", "convert_element_type", "transpose", "add"}
    # what is left for the backward pass is the loss's, and no loop
    assert not [p for p in top if pass_of(p) == "backward"
                and ("/while" in p or f"/{tracing.LOSS_HEAD}/" in p)]
    second = [p for p in paths if f"({tracing.MTP})" in p]
    if kind != "mtp":
        assert not second
        return
    # (the mask of the positions that have a target and the targets' roll are
    # the module's and outside the loss function)
    assert all(f"({tracing.MTP})/{tracing.LOSS}/{tracing.LOSS_HEAD}/" in p or
               f"/{tracing.LOSS}/while/" in p
               for p in second if p.endswith("/dot_general"))
    assert not [p for p in second if f"({tracing.LOSS})" in p]
    again = [p for p in second if p.endswith(f"/{tracing.LOSS_HEAD}/dot_general")]
    assert {pass_of(p) for p in again} == {"forward"}
    # its cotangent is mtp_weight, not 1.0: the rule's two scalings stay
    assert f"jit(train_step)/transpose(jvp({tracing.MTP}))/{tracing.LOSS}/mul" in second
    # the sum of the two terms is the loss's, outside the module's scope
    assert f"jit(train_step)/jvp({tracing.LOSS})/mul" in top


# ---------------------------------------------------------------- host spans


def host_lines(trace_dir: str) -> list:
    """One list per thread that left program spans in the session's xplane:
    [(name, start ns, end ns)] in start order."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    ))
    assert len(found) == 1, found
    lines = []
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            spans = sorted(
                ((e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in line.events if e.name.startswith("ray_tpu.")),
                key=lambda s: s[1],
            )
            if spans:
                lines.append(spans)
    return lines


@contextlib.contextmanager
def profiler_session(trace_dir: str):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def session_lines(tmp_path_factory):
    """A TrainWorker whose loop reports three times from its own thread
    while this thread drains ``next_result``, under a profiler session."""
    from ray_tpu.train.trainer import TrainWorker

    trace_dir = str(tmp_path_factory.mktemp("session_trace"))
    gate = threading.Semaphore(0)

    def loop(config):
        for i in range(3):
            assert gate.acquire(timeout=30)  # the drain waits first: a real wait
            ray_tpu.train.report({"step": i})

    worker = TrainWorker(0, 1, "spans", None)
    results = []
    with profiler_session(trace_dir):
        worker.run(loop, {})
        for _ in range(4):  # three reports and "done"
            gate.release()
            results.append(worker.next_result())
    worker._thread.join(timeout=30)
    assert not worker._thread.is_alive()
    assert [r[0] for r in results] == ["report"] * 3 + ["done"]
    return host_lines(trace_dir)


def test_report_and_next_result_are_spans_on_two_threads_in_order(session_lines):
    by_name = {}
    for i, line in enumerate(session_lines):
        for name, start, end in line:
            by_name.setdefault(name, []).append((i, start, end))
    reports = by_name[tracing.TRAIN_REPORT]
    drains = by_name[tracing.TRAIN_NEXT_RESULT]
    waits = by_name[tracing.TRAIN_RESULT_WAIT]
    assert len(reports) == 3 and len(drains) == 4 and len(waits) == 4
    loop_line, = {i for i, _, _ in reports}
    drain_line, = {i for i, _, _ in drains}
    assert loop_line != drain_line
    assert {i for i, _, _ in waits} == {drain_line}
    for (_, w0, w1), (_, d0, d1) in zip(waits, drains):
        assert d0 <= w0 <= w1 <= d1  # the wait lies inside its call
    for (_, r0, _), (_, d0, d1) in zip(reports, drains):
        assert d0 <= d1 and r0 <= d1  # first in, first out: report i ends call i
    for (_, _, before), (_, start, _) in zip(drains, drains[1:]):
        assert before <= start


@pytest.fixture(scope="module")
def actor_lines(tmp_path_factory):
    """An actor that opens a profiler session in its own process, serves a
    call under it and closes it: the worker's spans are in its xplane."""
    trace_dir = str(tmp_path_factory.mktemp("actor_trace"))

    @ray_tpu.remote
    class Traced:
        def start(self, trace_dir):
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            return True

        def work(self, x):
            return x + 1

        def stop(self):
            import jax

            jax.profiler.stop_trace()
            return True

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        actor = Traced.remote()
        assert ray_tpu.get(actor.start.remote(trace_dir), timeout=120)
        assert ray_tpu.get([actor.work.remote(i) for i in range(3)]) == [1, 2, 3]
        assert ray_tpu.get(actor.stop.remote(), timeout=120)
    finally:
        ray_tpu.shutdown()
    return host_lines(trace_dir)


def test_actor_call_leaves_exec_reply_and_recv(actor_lines):
    names = [name for line in actor_lines for name, _, _ in line]
    for name in (tracing.WORKER_EXEC, tracing.WORKER_REPLY, tracing.WORKER_RECV):
        assert names.count(name) >= 3, (name, names)
    # a call's reply follows its execution on the thread that ran it
    for line in actor_lines:
        execs = [s for s in line if s[0] == tracing.WORKER_EXEC]
        replies = [s for s in line if s[0] == tracing.WORKER_REPLY]
        for (_, _, done), (_, start, _) in zip(execs, replies[-len(execs):]):
            assert done <= start


def test_names_emitted_are_exactly_the_list(
    llama_paths, qk_norm_paths, moe_paths, kimi_paths, sarvam_paths,
    xing4_paths, laguna_paths, solar_paths, olmo_paths, sala_paths, granite_paths,
    lfm2_paths, dots3_paths, session_lines, actor_lines
):
    spans = {name for lines in (session_lines, actor_lines)
             for line in lines for name, _, _ in line}
    assert spans == set(tracing.HOST_SPANS)
    assert all(name.startswith("ray_tpu.") for name in tracing.HOST_SPANS)
    paths = llama_paths + qk_norm_paths + kimi_paths + sarvam_paths + xing4_paths + laguna_paths + solar_paths + olmo_paths + sala_paths + granite_paths + lfm2_paths + dots3_paths + [
        p for ps in moe_paths.values() for p in ps]
    for name in tracing.SCOPES:  # a scope directly under a transform is in its brackets
        assert any(f"/{name}/" in p or f"({name})/" in p for p in paths), name
    for name in tracing.MIXERS + tracing.BODY:  # flax names; a layer's ends in its index
        assert any(f"/{name}/" in p or f"/{name}0/" in p for p in paths), name


def test_source_names_no_span_or_scope_outside_the_list():
    """Every span and scope in ray_tpu/ is opened through util/tracing.py
    with one of its constants."""
    constants = {k for k, v in vars(tracing).items()
                 if k.isupper() and isinstance(v, str)}
    assert {getattr(tracing, k) for k in constants} == (
        set(tracing.HOST_SPANS) | set(tracing.SETUP_SPANS) | set(tracing.SCOPES)
        | set(tracing.MIXERS) | set(tracing.BODY)
    )
    literal = re.compile(r'name=f?"(?:%s)' % "|".join(tracing.MIXERS + tracing.BODY))
    calls = 0
    for path in glob.glob(os.path.join(REPO, "ray_tpu", "**", "*.py"), recursive=True):
        with open(path) as f:
            source = f.read()
        if path.endswith(os.path.join("util", "tracing.py")):
            continue
        assert not re.search(r"named_scope|TraceAnnotation|jax\.profiler", source), path
        if os.sep + "models" + os.sep in path:  # a module is named from the lists
            assert not literal.search(source), (path, literal.search(source))
        for arg in re.findall(r"\b_?tracing\.(?:span|scope)\(([^)]*)\)", source):
            calls += 1
            assert re.fullmatch(r"_?tracing\.([A-Z_]+)", arg), (path, arg)
            assert arg.split(".")[1] in constants, (path, arg)
    assert calls >= 20


def test_the_key_value_span_store_is_gone():
    for name in ("inject", "record_span", "get_trace", "new_context", "enabled"):
        assert not hasattr(tracing, name), name
    with open(tracing.__file__) as f:
        source = f.read()
    assert "RAY_TPU_TRACE" not in source and "kv_put" not in source


def test_import_leaves_jax_out_and_span_is_a_noop_without_it():
    code = (
        "import sys, contextlib\n"
        "import ray_tpu.util.tracing as t\n"
        "import ray_tpu\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "cm = t.span(t.TRAIN_REPORT)\n"
        "assert isinstance(cm, contextlib.nullcontext), cm\n"
        "with cm:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True,
        timeout=120, env={**os.environ, "PYTHONPATH": REPO},
    )
    assert done.returncode == 0 and done.stdout.strip() == "ok", done.stderr
