"""The interpreted flash kernels at heads of 128 and 128 channels, over every
tiling: the forward is bit for bit the masked-everywhere result, and so are the
float32 gradients, which start from the same forward (``tests/flash_cases.py``
has the bodies and makes a float32 forward once for both).
"""
import jax.numpy as jnp
import pytest

from flash_cases import (  # noqa: F401 - the fixture
    HEAD_DIMS, TILINGS, _interpret_mode,
    float32_gradients_are_bit_for_bit_the_masked_everywhere_result,
    forward_is_bit_for_bit_the_masked_everywhere_result,
)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("d,d_v", HEAD_DIMS[0:1])
@pytest.mark.parametrize("tq,tk,bq,bk,causal", TILINGS)
def test_forward_is_bit_for_bit_the_masked_everywhere_result(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v, dtype):
    forward_is_bit_for_bit_the_masked_everywhere_result(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v, dtype)


@pytest.mark.parametrize("d,d_v", HEAD_DIMS[0:1])
@pytest.mark.parametrize("tq,tk,bq,bk,causal", TILINGS)
def test_float32_gradients_are_bit_for_bit_the_masked_everywhere_result(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v):
    float32_gradients_are_bit_for_bit_the_masked_everywhere_result(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v)
