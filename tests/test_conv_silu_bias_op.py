"""The convolution with a bias (``conv_silu(bias=)``) of ``ray_tpu/ops/kda.py``
on the CPU: the bias added before the SiLU, and without one the text the
kernels lowered to before they took one.

One of the six kernel families of ``ray_tpu/ops/kda.py``, a test file each
(ROADMAP C15's seams: the module's split moves one test file with each
family).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

from kda_cases import (
    CONV_CASES, conv_and_gradients, conv_inputs, conv_reference, heads_first,
    pallas_calls,
)


# --------------------------------- the convolution with a bias (``conv_silu``)
def biased_reference(x, w, b, dtype):
    return jax.nn.silu(kda.short_conv(x, w) + b).astype(dtype)


@pytest.mark.parametrize("case", ["three-blocks", "batch-of-2", "bfloat16-out",
                                  "tokens-do-not-tile", "heads-of-96",
                                  "heads-of-192-bfloat16", "heads-do-not-tile"])
def test_the_fused_convolution_adds_its_bias_before_the_silu(monkeypatch, case):
    """``conv_silu(..., bias=b)`` is ``silu(short_conv(x, w) + b)``: the value
    and the gradients in x, in the filter and in the bias (dz's own sum over
    batch and time, added up in float32 where the filter's is), by the kernels
    where the shape tiles and by XLA where it does not, the output tokens
    first or heads first."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    batch, t, channels, dtype, heads, blocks = CONV_CASES[case]
    x, w, dy = conv_inputs(batch, t, channels, dtype, heads=heads)
    b = jax.random.uniform(jax.random.PRNGKey(7), (channels,), jnp.float32, -0.5, 0.5)
    run = lambda x, w, b: kda.conv_silu(x, w, dtype, b, heads)  # noqa: E731
    both = jax.make_jaxpr(lambda *a: jax.vjp(run, *a)[1](dy))(x, w, b)
    calls = pallas_calls(both.jaxpr, [])
    names = [eqn.params["jaxpr"].debug_info.func_name for eqn in calls]
    assert names == (["_conv_fwd_kernel", "_conv_bwd_kernel"] if blocks else [])
    if blocks:  # the bias goes in as an operand and its cotangent comes out
        assert [len(eqn.invars) for eqn in calls] == [4, 7]
        assert [len(eqn.outvars) for eqn in calls] == [1, 3]
    y, vjp = jax.vjp(run, x, w, b)
    y_ref, vjp_ref = jax.vjp(lambda *a: heads_first(biased_reference(*a, dtype), heads), x, w, b)
    np.testing.assert_allclose(
        y.astype(jnp.float32), y_ref.astype(jnp.float32),
        rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-6)
    for name, got, want in zip("x w b".split(), vjp(dy), vjp_ref(dy)):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        np.testing.assert_allclose(
            got, want, rtol=1e-5, atol=1e-5 * float(jnp.abs(want).max()), err_msg=name)
    # and the bias is not nothing: without it the output is another
    assert float(jnp.abs(y.astype(jnp.float32)
                         - kda.conv_silu(x, w, dtype, heads=heads).astype(jnp.float32)).max()) > 0.1


def test_without_a_bias_the_convolution_lowers_what_it_lowered(monkeypatch):
    """``bias=None`` adds no operand, no output and no operation: the calls
    are the two of three and six operands that they were, and their values
    those of ``silu(short_conv)`` (the held digests of the Kimi-Linear step,
    tests/test_solar_open2_model.py, read the lowered text itself)."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    x, w, dy = conv_inputs(2, 256, 128, jnp.float32)
    plain = jax.make_jaxpr(lambda *a: conv_and_gradients(kda.conv_silu, *a))(x, w, dy)
    calls = pallas_calls(plain.jaxpr, [])
    assert [len(eqn.invars) for eqn in calls] == [3, 6]
    assert [len(eqn.outvars) for eqn in calls] == [1, 2]
    none = jax.make_jaxpr(lambda x, w, dy: jax.vjp(
        lambda x, w: kda.conv_silu(x, w, dy.dtype, None), x, w)[1](dy))(x, w, dy)
    assert str(pallas_calls(none.jaxpr, [])[0].params["jaxpr"]) == str(
        calls[0].params["jaxpr"])
    y, dx, dw = conv_and_gradients(kda.conv_silu, x, w, dy)
    y_ref, dx_ref, dw_ref = conv_and_gradients(conv_reference, x, w, dy)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-5, atol=1e-6)
