"""The gated delta rule of ``ray_tpu/ops/kda.py`` on the CPU: the state the
kernels carry against the recurrence's own, what a write strength capped at
one or left undoubled would give, and the bfloat16 matmuls' distance from
float32 with a write strength up to two (``tests/test_kda_op.py`` says
what the rule is held to and names the family's files;
``tests/kda_recurrence.py`` has the recurrence and the comparison).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

from kda_cases import B, DK, DV, H, RMS_EPS, SCALE
from kda_recurrence import chunk_kda, fresh, inputs, oracle


def states_of_the_recurrence(k, v, g, beta):
    """The state before token t for every t, [B, T, H, dk, dv]."""
    def one(k, v, g, beta):
        def step(S, x):
            k, v, g, b = x
            S_next = jnp.exp(g)[:, None] * S
            S_next = S_next + b * jnp.outer(k, v - S_next.T @ k)
            return S_next, S

        return jax.lax.scan(step, jnp.zeros((DK, DV)), (k, v, g, beta))[1]

    heads = jax.vmap(one, in_axes=(1, 1, 1, 1), out_axes=1)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(heads)(k, v, g, beta)


@pytest.mark.parametrize("beta_max", [1.0, 2.0], ids=["beta<1", "beta<2"])
def test_the_state_the_kernel_carries_is_the_recurrences(monkeypatch, beta_max):
    """The forward kernel under a gradient writes the state at every chunk's
    start (transposed, [dv, dk] a head): it is the token-by-token
    recurrence's state before that chunk's first token, also where beta
    passes 1 and a write overshoots what the key held (an eigenvalue 1 -
    beta below zero)."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    t = 256
    q, k, v, g, beta, gate, weight = inputs(t, 0.02, beta_max=beta_max)
    flat = lambda x: x.reshape(B, t, -1)  # noqa: E731
    _, states, _ = kda._forward_pallas(
        flat(q), flat(k), flat(v), flat(g), beta.transpose(0, 2, 1)[..., None],
        flat(gate), weight[None], H, (SCALE, 1e-6, RMS_EPS), states=True)
    want = states_of_the_recurrence(kda.l2norm(k), v, g, beta)[:, ::kda.CHUNK]
    got = states.reshape(B, t // kda.CHUNK, DV, H, DK).transpose(0, 1, 3, 4, 2)
    assert not np.asarray(got[:, 0]).any() and float(jnp.abs(want[:, -1]).max()) > 0.1
    np.testing.assert_allclose(
        got, want, rtol=2e-4, atol=2e-5 * float(jnp.abs(want).max()))


def test_bfloat16_matmuls_hold_with_a_write_strength_up_to_two(monkeypatch):
    """The inverse by doubling rounds X to the matmuls' dtype at five levels
    and the chunk's system has entries up to beta in size: with bfloat16
    operands, keys that repeat (eight directions and a little noise, so that
    k_t k_s is near 1 inside a chunk) and next to no decay, the kernels'
    output and gradients stay within a few hundredths of the float32
    recurrence's, at beta in (0, 2) as at beta in (0, 1), and finite."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    t = 128
    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))  # noqa: E731
    for beta_max, limit in ((1.0, 0.03), (2.0, 0.06)):
        q, k, v, g, beta, gate, weight = inputs(t, 1e-3, beta_max=beta_max)
        r = np.random.default_rng(7)
        base, at = r.normal(size=(8, H, DK)), r.integers(0, 8, size=(B, t))
        k = jnp.asarray(base[at] + 0.05 * r.normal(size=k.shape), jnp.float32)
        q = jnp.asarray(base[at] + 0.05 * r.normal(size=q.shape), jnp.float32)
        w = jnp.asarray(r.normal(size=v.shape), jnp.float32)
        args = (q, k, v, g, beta, gate, weight)
        run = lambda f, v_dtype: jax.jit(jax.value_and_grad(  # noqa: E731
            lambda q, k, g, beta: jnp.sum(f(
                q, k, v.astype(v_dtype), g, beta, gate, weight
            ).astype(jnp.float32) * w), argnums=(0, 1, 2, 3)))(q, k, g, beta)
        got = jax.jit(fresh())(q, k, v.astype(jnp.bfloat16), *args[3:])
        assert got.dtype == jnp.bfloat16 and bool(jnp.isfinite(got).all())
        assert rel(got, oracle(*args)) < limit
        (_, grads), (_, wanted) = run(chunk_kda, jnp.bfloat16), run(oracle, jnp.float32)
        for name, a, b in zip("q k g beta".split(), grads, wanted):
            assert bool(jnp.isfinite(a).all()), name
            assert rel(a, b) < 2 * limit, (beta_max, name, rel(a, b))


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("wrong", ["capped", "undoubled"])
def test_a_write_strength_capped_at_one_or_left_undoubled_is_another_function(
        monkeypatch, path, wrong):
    """With beta over (0, 2) the chunked form is the recurrence at that beta
    and not at min(beta, 1) nor at beta / 2: nothing inside clips it."""
    if path == "pallas":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v, g, beta, gate, weight = inputs(128, 1e-3, beta_max=2.0)
    got = jax.jit(fresh())(q, k, v, g, beta, gate, weight)
    scale = float(jnp.abs(got).max())
    np.testing.assert_allclose(
        got, oracle(q, k, v, g, beta, gate, weight), rtol=2e-4, atol=2e-5 * scale)
    other = jnp.minimum(beta, 1.0) if wrong == "capped" else beta / 2
    far = oracle(q, k, v, g, other, gate, weight)
    assert float(jnp.abs(got - far).max()) > 0.1 * scale
