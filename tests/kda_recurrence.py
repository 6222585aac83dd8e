"""What the KDA family's test files share (``tests/test_kda_op.py``,
``test_kda_kernels_op.py``, ``test_kda_inverse_op.py``,
``test_kda_heads_op.py``): ``chunk_kda`` at the tests' scale, the token-by-
token recurrence and the plain way around it (``oracle``), the inputs, and the
comparison of values and every gradient (``compare``). ``tests/kda_cases.py``
has what all six families of ``ray_tpu/ops/kda.py`` share. A plain module: a
piece imports what it reads by name.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import kda

from kda_cases import B, DK, DV, H, NAMES, RMS_EPS, SCALE, gated_norm


chunk_kda = functools.partial(kda.chunk_kda, scale=SCALE, rms_eps=RMS_EPS)


def fresh():
    """``chunk_kda`` as a new function object: ``jit`` and ``make_jaxpr`` keep
    their traces by the function, and which path a trace took (the kernels
    or ``lax.scan``) follows RAY_TPU_PALLAS_INTERPRET, which they do not
    see."""
    return lambda *a: chunk_kda(*a)


def oracle(q, k, v, g, beta, gate, weight):
    """What ``chunk_kda`` computes, the plain way."""
    o = recurrence(kda.l2norm(q) * SCALE, kda.l2norm(k), v, g, beta)
    return gated_norm(o, gate, weight)


def recurrence(q, k, v, g, beta):
    """S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T; o_t = S_t^T q_t,
    one token at a time. [B, T, H, d] layouts as ``chunk_kda``."""
    def one(q, k, v, g, beta):  # one (batch, head): [T, d]
        def step(S, x):
            q, k, v, g, b = x
            S = jnp.exp(g)[:, None] * S
            S = S + b * jnp.outer(k, v - S.T @ k)
            return S, S.T @ q

        return jax.lax.scan(step, jnp.zeros((DK, DV)), (q, k, v, g, beta))[1]

    heads = jax.vmap(one, in_axes=(1, 1, 1, 1, 1), out_axes=1)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(heads)(q, k, v, g, beta)


def inputs(t, decay, seed=0, heads=H, beta_max=1.0):
    """q, k raw, as the mixer's SiLU leaves them; g = -decay x uniform(0.5,
    1.5): exp(g) is near 1 at decay 1e-3 and under 1e-6 at decay 30; the
    output gate before its sigmoid and the norm's weight. beta is a sigmoid
    in (0, 1), or ``beta_max`` times one of logits three times as wide, so
    that at 2 it passes 1.9 and falls under 0.1."""
    r = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa: E731
    q, k = draw(B, t, heads, DK), draw(B, t, heads, DK)
    v = draw(B, t, heads, DV)
    g = -jnp.asarray(r.uniform(0.5, 1.5, size=(B, t, heads, DK)), jnp.float32) * decay
    logits = draw(B, t, heads)
    beta = jax.nn.sigmoid(logits if beta_max == 1.0 else 3.0 * logits) * beta_max
    return q, k, v, g, beta, draw(B, t, heads, DV), 1.0 + 0.3 * draw(DV)


def compare(t, decay, heads=H, args=None, beta_max=1.0):
    args = args or inputs(t, decay, heads=heads, beta_max=beta_max)
    if beta_max > 1.0:
        assert float(args[4].max()) > 1.9 and float(args[4].min()) < 0.1
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)
    want = oracle(*args)
    got = jax.jit(fresh())(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * float(jnp.abs(want).max()))
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(chunk_kda(*a) * w), argnums=range(7)))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(oracle(*a) * w), argnums=range(7))(*args)
    for name, a, b in zip(NAMES, grads, wanted):
        assert float(jnp.abs(b).max()) > 0 and bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-4 * float(jnp.abs(b).max()), err_msg=name)
