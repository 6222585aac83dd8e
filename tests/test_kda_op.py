"""The gated delta rule of ``ray_tpu/ops/kda.py`` on the CPU: the chunked
function and its ``custom_vjp``, which take q and k raw and give o
normalised and gated, against the plain way (L2 norms, the token-by-token
recurrence, ``RMSNorm``, the gate's sigmoid), forward and every gradient, at
several sequence lengths and at a decay near 0 and near 1; the Pallas scan
kernels in interpret mode against the same, two heads a grid step and, where
the heads do not pair off, one; a head through the pair path against the
same head alone, bit for bit; rows of zeros; the weight's gradient over
batch rows; the gate beside the convolution. The write strength
runs over (0, 1) and, as a configuration with negative eigenvalues doubles
it, over (0, 2): the kernels against the recurrence there, the state they
carry against the recurrence's own, what a cap at 1 or a doubling left out
would give, and the bfloat16 matmuls' distance from float32. The chunk's
inverse T = (I + A)^-1 is a function with a derivative rule of its own (-T^T
dT T^T): the rule against autodiff of the doubling, the T the forward kernel
writes under a gradient against the system's inverse, the backward kernel fed
that T against one that remakes it, and the matmuls a backward grid step
holds.

One of the six kernel families of ``ray_tpu/ops/kda.py``, a test file each
(ROADMAP C15's seams: the module's split moves one test file with each
family): this one KDA's, with ``tests/test_kda_kernels_op.py`` (the kernels
interpreted), ``test_kda_inverse_op.py`` (the chunk's inverse) and
``test_kda_heads_op.py`` (heads a grid step, the norms around the scan) over
``tests/kda_recurrence.py``; ``tests/test_gdn_op.py`` the scalar decay's,
``tests/test_lightning_op.py`` the fixed decay's, ``tests/test_ssd_op.py`` the
step-scaled decay's, ``tests/test_conv_silu_op.py`` the convolutions' and
``tests/test_conv_silu_bias_op.py`` the convolution's with a bias;
``tests/kda_cases.py`` has what they share.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

from kda_recurrence import chunk_kda, compare, inputs, oracle


@pytest.mark.parametrize("decay", [1e-3, 0.3, 30.0], ids=["near1", "mid", "near0"])
@pytest.mark.parametrize("t", [64, 100, 256])
def test_chunked_form_and_its_vjp_are_the_recurrence(t, decay):
    """The XLA form (``lax.scan`` over ``_head_chunk``, differentiated by
    JAX), a length that is no whole number of chunks among them."""
    compare(t, decay)


def test_a_strong_decay_neither_overflows_nor_loses_the_state():
    """exp(-50) a step: every exponent the chunked form takes is <= 0."""
    q, k, v, g, beta, gate, weight = inputs(128, 1.0)
    g = jnp.full_like(g, -50.0).at[:, ::7].set(-1e-4)
    got = chunk_kda(q, k, v, g, beta, gate, weight)
    assert bool(jnp.isfinite(got).all())
    want = oracle(q, k, v, g, beta, gate, weight)
    # the running sum of g reaches -3000 in a chunk: its float32 rounding
    # (2e-4) is the exponent's
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4 * float(jnp.abs(want).max()))


def test_the_gate_is_a_negative_log_decay_per_channel():
    f = jnp.asarray(np.random.default_rng(0).normal(size=(1, 5, 3, 4)), jnp.float32)
    a_log = jnp.log(jnp.asarray([1.0, 4.0, 16.0]))
    dt_bias = jnp.zeros((3, 4))
    g = kda.kda_gate(f, a_log, dt_bias)
    assert g.dtype == jnp.float32 and bool((g < 0).all())
    np.testing.assert_allclose(
        g, -jnp.asarray([1.0, 4.0, 16.0])[:, None] * jax.nn.softplus(f), rtol=1e-6)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(3, 8)), jnp.float32)
    np.testing.assert_allclose(jnp.sum(kda.l2norm(x) ** 2, -1), 1.0, rtol=1e-4)
