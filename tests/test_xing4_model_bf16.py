"""Xing4's architecture through the program's models, on the CPU: the bfloat16
program is near the reference and is not it (``tests/test_xing4_model.py`` has
the model against its reference and says what the reference is;
``tests/xing4_cases.py`` what the files share).
"""
import jax
import pytest

from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import xing4_decoder as reference

from xing4_cases import SEQ, interpret, xing4  # noqa: F401 - fixtures


@pytest.fixture(scope="module")
def xing4_bf16():
    return xing4("bfloat16")


def test_logits_in_bfloat16_are_near_the_reference_and_not_it(xing4_bf16):
    config, model, params, ids = xing4_bf16
    system = jax.jit(model.apply)(params, ids[None])[0]
    expected = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 0.1, "min_share_within": 0.9})
    assert result["ok"], result
    assert result["rel_err_median"] > 1e-4  # the system is not the reference
