"""The in-graph scopes of hyper-connections, the q latent and the MTP module
(Xing4), held against sarvam's, which has none: the ``op_name`` of every
instruction of a tiny model's compiled train step, on the CPU
(``tests/program_paths.py`` has the reading and the cases every family
passes).
"""
import os
import re

import jax.numpy as jnp
import pytest

from ray_tpu.util import tracing

from program_paths import (  # noqa: F401 - fixtures
    a_step_shows_the_names_it_is_listed_for, compiled_step,
    every_instruction_path_names_a_part_of_the_program, pass_of, paths_in,
    paths_of, sarvam_paths, the_loss_and_the_chunked_head_carry_their_scopes,
)


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


# This file's compiled steps, by fixture (and dispatch branch).
FAMILIES = ("xing4_paths",)


@pytest.mark.parametrize("family", FAMILIES)
def test_every_instruction_path_names_a_part_of_the_program(request, family):
    every_instruction_path_names_a_part_of_the_program(paths_in(request, family))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_loss_and_the_chunked_head_carry_their_scopes(request, family):
    the_loss_and_the_chunked_head_carry_their_scopes(paths_in(request, family), family)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_step_shows_the_names_it_is_listed_for(request, family):
    a_step_shows_the_names_it_is_listed_for(paths_in(request, family), family)
