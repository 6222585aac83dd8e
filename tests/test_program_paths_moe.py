"""The in-graph scopes of the expert layer under each dispatch branch: the
``op_name`` of every instruction of a tiny model's compiled train step, on the
CPU (``tests/program_paths.py`` has the reading and the cases every family
passes).
"""
import dataclasses
import os
import re

import jax.numpy as jnp
import pytest

from ray_tpu.util import tracing

from program_paths import (
    MOE_SCOPES, a_step_shows_the_names_it_is_listed_for, compiled_step,
    every_instruction_path_names_a_part_of_the_program, pass_of, paths_in,
    paths_of, the_loss_and_the_chunked_head_carry_their_scopes,
)


# On the CPU an expert matmul is a dot_general in every dispatch branch:
# ragged_dot lowers to one, and the Pallas kernel of "gmm" runs in interpret
# mode, as in test_moe_models.py and test_moe_expert_ffn.py.
BRANCHES = ("capacity", "gmm", "ragged")


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


# This file's compiled steps, by fixture (and dispatch branch).
FAMILIES = ("moe_paths:capacity", "moe_paths:gmm", "moe_paths:ragged")


@pytest.mark.parametrize("family", FAMILIES)
def test_every_instruction_path_names_a_part_of_the_program(request, family):
    every_instruction_path_names_a_part_of_the_program(paths_in(request, family))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_loss_and_the_chunked_head_carry_their_scopes(request, family):
    the_loss_and_the_chunked_head_carry_their_scopes(paths_in(request, family), family)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_step_shows_the_names_it_is_listed_for(request, family):
    a_step_shows_the_names_it_is_listed_for(paths_in(request, family), family)
