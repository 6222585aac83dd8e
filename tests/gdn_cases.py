"""What the scalar decay's two test files share (``tests/test_gdn_op.py``,
``tests/test_gdn_layout_op.py``): ``chunk_gdn`` fed tokens-first q and k, its
inputs, and its oracle. ``tests/kda_cases.py`` has what all six families of
``ray_tpu/ops/kda.py`` share.
"""
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import RMSNorm
from ray_tpu.ops import kda

from kda_cases import B, H, NAMES, RMS_EPS


# ------------------------------------------- the scalar decay (``chunk_gdn``)
# Gated DeltaNet's road: one log-decay a head and token, key and value heads
# of widths of their own that fill no vreg, SiLU for the output gate's
# sigmoid. Against the same token-by-token recurrence, fed g broadcast.
GDK, GDV = 24, 48


def chunk_gdn(q, k, v, g, beta, gate, weight):
    """``kda.chunk_gdn`` of q and k that lie tokens first, [B, T, H, dk], as
    every oracle here has them: they go in as the one array [B, 2, H, T, dk],
    heads first, as the mixer's convolution writes them, and the seven
    gradients come back through the transpositions."""
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    return kda.chunk_gdn(
        jnp.stack([heads_first(q), heads_first(k)], 1), v, g, beta, gate, weight,
        scale=GDK ** -0.5, rms_eps=RMS_EPS)


def gdn_inputs(t, decay, seed=0, heads=H):
    """As ``inputs`` with beta over (0, 2), g [B, T, H] and dk != dv."""
    r = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa: E731
    q, k, v = draw(B, t, heads, GDK), draw(B, t, heads, GDK), draw(B, t, heads, GDV)
    g = -jnp.asarray(r.uniform(0.5, 1.5, size=(B, t, heads)), jnp.float32) * decay
    beta = 2.0 * jax.nn.sigmoid(3.0 * draw(B, t, heads))
    return q, k, v, g, beta, draw(B, t, heads, GDV), 1.0 + 0.3 * draw(GDV)


def gdn_oracle(q, k, v, g, beta, gate, weight):
    """What ``chunk_gdn`` computes, the plain way."""
    def one(q, k, v, g, b):  # one (batch, head): [T, d]
        def step(S, x):
            q, k, v, g, b = x
            S = jnp.exp(g) * S
            S = S + b * jnp.outer(k, v - S.T @ k)
            return S, S.T @ q

        return jax.lax.scan(step, jnp.zeros((GDK, GDV)), (q, k, v, g, b))[1]

    heads = jax.vmap(one, in_axes=(1, 1, 1, 1, 1), out_axes=1)
    with jax.default_matmul_precision("highest"):
        o = jax.vmap(heads)(kda.l2norm(q) * GDK ** -0.5, kda.l2norm(k), v, g, beta)
    normed = RMSNorm(RMS_EPS).apply({"params": {"scale": weight}}, o)
    return normed * jax.nn.silu(gate)


def gdn_compare(t, decay, heads=H):
    args = gdn_inputs(t, decay, heads=heads)
    assert float(args[4].max()) > 1.9 and float(args[4].min()) < 0.1
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)
    want = gdn_oracle(*args)
    got = jax.jit(lambda *a: chunk_gdn(*a))(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * float(jnp.abs(want).max()))
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(chunk_gdn(*a) * w), argnums=range(7)))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(gdn_oracle(*a) * w), argnums=range(7))(*args)
    for name, a, b in zip(NAMES, grads, wanted):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        # Under a strong decay g's cotangent is what cancellation leaves of
        # terms a thousand times its size.
        atol = (2e-3 if name == "g" else 2e-4) * float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=atol, err_msg=name)
