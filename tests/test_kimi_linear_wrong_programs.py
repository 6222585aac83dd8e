"""Kimi-Linear's architecture through the program's models, on the CPU: a program
of another function is far from the reference
(``tests/test_kimi_linear_model.py`` has the model against its reference and
says what the reference is; ``tests/kimi_linear_cases.py`` what the files
share).
"""
import dataclasses

import jax
import pytest

from benchmarks.lib.checks import logits_agreement
from ray_tpu.models.kimi_linear import KimiLinearForCausalLM

from kimi_linear_cases import (  # noqa: F401 - fixtures
    expected, interpret, kimi_f32,
)


@pytest.mark.parametrize("wrong", [
    {"routed_scaling_factor": 1.0},  # the 2.446 left out
    {"num_shared_experts": 0},  # the shared expert left out
    {"norm_topk_prob": False},  # gates not renormalised
    {"experts_held": (4, 8)},  # another rank's experts
], ids=lambda w: "-".join(w))
def test_a_program_of_another_function_is_far_from_the_reference(
        kimi_f32, expected, wrong):
    config, model, params, ids = kimi_f32
    other = KimiLinearForCausalLM(dataclasses.replace(model.cfg, **wrong))
    result = logits_agreement(
        jax.jit(other.apply)(params, ids[None])[0], expected,
        {"per_position_rel_err": 1e-3, "min_share_within": 0.5},
    )
    assert not result["ok"], result
