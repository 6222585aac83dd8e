"""Xing4's architecture through the program's models, on the CPU:
``benchmarks/tools/wrong_xing4.py``'s programs of another function are far
from the reference (``tests/test_xing4_model.py`` has the model against its
reference and says what the reference is; ``tests/xing4_cases.py`` what the
files share).
"""
import jax
import pytest

from benchmarks.lib.checks import logits_agreement
from benchmarks.tools import wrong_xing4
from ray_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM

from xing4_cases import (  # noqa: F401 - fixtures
    LOOSE, PUBLISHED_YARN, expected_logits, interpret, xing4_f32,
)


@pytest.mark.parametrize("wrong", sorted(wrong_xing4.programs(Xing4Config(
    rope_scaling=PUBLISHED_YARN))))
def test_a_wrong_program_is_far_from_the_reference(xing4_f32, expected_logits, wrong):
    """``wrong_xing4.py``'s programs of another function, in float32."""
    config, model, params, ids = xing4_f32
    (other,) = wrong_xing4.programs(model.cfg)[wrong]
    result = logits_agreement(
        jax.jit(Xing4ForCausalLM(other).apply)(params, ids[None])[0],
        expected_logits, LOOSE)
    assert not result["ok"], result
