"""The interpreted flash kernels at heads of 64 and 64 channels, over every
tiling: (dq, dk, dv) against the XLA block backward on the same (o, lse)
(``tests/flash_cases.py`` has the body).
"""
import jax.numpy as jnp
import pytest

from flash_cases import (  # noqa: F401 - the fixture
    HEAD_DIMS, TILINGS, _interpret_mode, gradients_match_the_xla_block_backward,
)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5), (jnp.bfloat16, 0.03)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d,d_v", HEAD_DIMS[2:3])
@pytest.mark.parametrize("tq,tk,bq,bk,causal", TILINGS)
def test_gradients_match_the_xla_block_backward(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v, dtype, atol):
    gradients_match_the_xla_block_backward(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v, dtype, atol)
