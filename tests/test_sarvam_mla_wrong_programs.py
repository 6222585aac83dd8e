"""sarvam's architecture through the program's models, on the CPU: a program of
another function is far from the reference (``tests/test_sarvam_mla_model.py``
has the model against its reference and says what the reference is;
``tests/sarvam_cases.py`` what the files share).
"""
import dataclasses

import jax
import pytest

from benchmarks.lib.checks import logits_agreement
from ray_tpu.models.sarvam_mla import SarvamMLAForCausalLM

from sarvam_cases import (  # noqa: F401 - fixtures
    expected, interpret, sarvam_f32,
)


def without_mscale(cfg):
    """YaRN's table kept, the softmax scale's mscale squared left out."""
    return dataclasses.replace(cfg, rope_scaling=dataclasses.replace(
        cfg.rope_scaling, mscale=0.0, mscale_all_dim=0.0))


@pytest.mark.parametrize("wrong", [
    without_mscale,  # 192^-1/2 alone
    lambda cfg: dataclasses.replace(cfg, rope_scaling=None),  # the plain table
    lambda cfg: dataclasses.replace(cfg, mla_rope=False),  # nothing rotated
    lambda cfg: dataclasses.replace(cfg, qk_head_norm=False),  # the QK norm left out
    lambda cfg: dataclasses.replace(cfg, routed_scaling_factor=1.0),  # the 2.5 left out
    lambda cfg: dataclasses.replace(cfg, num_shared_experts=0),
    lambda cfg: dataclasses.replace(cfg, norm_topk_prob=False),
    lambda cfg: dataclasses.replace(cfg, experts_held=(4, 8)),  # another rank's experts
], ids=["no-mscale", "no-yarn", "no-rotation", "no-qk-norm", "no-scaling",
        "no-shared-expert", "no-renormalisation", "another-rank"])
def test_a_program_of_another_function_is_far_from_the_reference(
        sarvam_f32, expected, wrong):
    config, model, params, ids = sarvam_f32
    other = SarvamMLAForCausalLM(wrong(model.cfg))
    if not other.cfg.qk_head_norm:
        params = jax.tree_util.tree_map(lambda a: a, params)
        for i in range(5):
            del params["params"][f"layers_{i}"]["mla"]["q_norm"]
            del params["params"][f"layers_{i}"]["mla"]["k_norm"]
    result = logits_agreement(
        jax.jit(other.apply)(params, ids[None])[0], expected,
        {"per_position_rel_err": 1e-3, "min_share_within": 0.5},
    )
    assert not result["ok"], result
