"""The gated delta rule of ``ray_tpu/ops/kda.py`` on the CPU: the chunk's inverse
T = (I + A)^-1 as a function with a derivative rule of its own: the rule
against autodiff of the doubling, a pair's inverse side by side, the T the
forward kernel writes, the backward kernel fed it, and the matmuls a backward
grid step holds (``tests/test_kda_op.py`` says what the rule is held to and
names the family's files; ``tests/kda_recurrence.py`` has the recurrence and
the comparison).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

from kda_cases import B, DK, NAMES, RMS_EPS, SCALE, pallas_calls
from kda_recurrence import chunk_kda, inputs


def chunk_system(stacked, beta_max, seed=5):
    """A [P * C, P * C] float32 as ``_head_chunk`` builds it from unit keys
    that repeat (eight directions and a little noise) and no decay: beta_t
    k_t k_s below the diagonal of every head's block, near beta_t or near 0,
    and, so that the masks have something to drop, noise everywhere else."""
    r = np.random.default_rng(seed)
    n = stacked * kda.CHUNK
    base = r.normal(size=(8, DK))[r.integers(0, 8, size=n)]
    k = np.asarray(kda.l2norm(jnp.asarray(base + 0.05 * r.normal(size=(n, DK)), jnp.float32)))
    beta = beta_max / (1.0 + np.exp(-3.0 * r.normal(size=(n, 1))))
    lower = np.kron(np.eye(stacked), np.tril(np.ones((kda.CHUNK,) * 2), -1)) > 0
    A = np.where(lower, beta * (k @ k.T), r.normal(size=(n, n)))
    return jnp.asarray(A, jnp.float32), lower


@pytest.mark.parametrize("stacked", [1, 2], ids=["one-head", "pair"])
@pytest.mark.parametrize("beta_max", [1.0, 2.0], ids=["beta<1", "beta<2"])
def test_the_inverses_own_rule_is_autodiff_of_the_doubling(stacked, beta_max):
    """``_unit_lower_inverse`` in float32: its value is the doubling's to the
    bit and the inverse of I + the strict lower triangle of every head's
    block; its rule, -T^T dT T^T, is what JAX gives through the ten matmuls
    of the doubling, to float32 rounding, at beta up to 1 and up to 2; A's
    cotangent is an exact zero on and above the diagonal and between stacked
    heads, whatever dT holds there; and handed T it gives the same without
    the doubling."""
    A, lower = chunk_system(stacked, beta_max)
    n = A.shape[0]
    masks, eye = kda._masks(n)
    doubling = lambda A: kda._doubling(jnp.float32, A, masks, eye)  # noqa: E731
    T, pull_chain = jax.vjp(doubling, A)
    got, pull = jax.vjp(
        lambda A: kda._unit_lower_inverse(jnp.float32, A, masks, eye, None), A)
    np.testing.assert_array_equal(got, T)
    exact = np.linalg.inv(np.eye(n) + np.where(lower, np.asarray(A, np.float64), 0.0))
    assert np.abs(exact - np.eye(n)).max() > 0.9 * beta_max  # far from the identity
    np.testing.assert_allclose(T, exact, rtol=0, atol=1e-5 * np.abs(exact).max())
    dT = jnp.asarray(np.random.default_rng(6).normal(size=(n, n)), jnp.float32)
    (want,), (dA,) = pull_chain(dT), pull(dT)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(dA, want, rtol=0, atol=1e-5 * float(jnp.abs(want).max()))
    assert not np.asarray(dA)[~lower].any() and not np.asarray(want)[~lower].any()
    handed, pull_handed = jax.vjp(
        lambda A: kda._unit_lower_inverse(jnp.float32, A, masks, eye, T), A)
    np.testing.assert_array_equal(handed, T)
    np.testing.assert_array_equal(pull_handed(dT)[0], dA)
    jaxpr = jax.make_jaxpr(lambda A: jax.vjp(
        lambda A: kda._unit_lower_inverse(jnp.float32, A, masks, eye, T), A)[1](dT))(A)
    assert dot_generals(jaxpr.jaxpr) == 2


def test_a_pairs_inverse_lies_side_by_side_and_comes_back_block_diagonal():
    """``_diagonal`` sums a block-diagonal T's row blocks, which adds exact
    zeros to each head's [C, C] block and lays them side by side on lanes;
    ``_block_diagonal`` is its inverse; at one head both are the identity."""
    c = kda.CHUNK
    r = np.random.default_rng(8)
    blocks = [jnp.asarray(r.normal(size=(c, c)), jnp.bfloat16) for _ in range(2)]
    zero = jnp.zeros((c, c), jnp.bfloat16)
    T = jnp.block([[blocks[0], -zero], [zero, blocks[1]]])
    D = kda._diagonal(T, 2)
    assert D.shape == (c, 2 * c) and D.dtype == jnp.bfloat16
    np.testing.assert_array_equal(D, jnp.concatenate(blocks, axis=1))
    np.testing.assert_array_equal(kda._block_diagonal(D, 2), T)
    assert kda._diagonal(blocks[0], 1) is blocks[0]
    assert kda._block_diagonal(blocks[0], 1) is blocks[0]


@pytest.mark.parametrize("heads", [4, 3], ids=["pairs", "odd"])
@pytest.mark.parametrize("beta_max", [1.0, 2.0], ids=["beta<1", "beta<2"])
def test_the_inverse_the_forward_kernel_writes_is_the_chunks_systems(
        monkeypatch, heads, beta_max):
    """Under a gradient the forward kernel writes T where it writes the
    states, [B, N, H / P, C, P * C], head h's block at step h // P on lanes
    (h % P) * C onward: the inverse of I + A with A[t, s] = beta_t sum_c k_t[c]
    k_s[c] exp(G_t[c] - G_s[c]) below the diagonal, k L2-normalised."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    t, c = 128, kda.CHUNK
    q, k, v, g, beta, gate, weight = inputs(t, 0.02, heads=heads, beta_max=beta_max)
    flat = lambda x: x.reshape(B, t, -1)  # noqa: E731
    _, _, inverses = kda._forward_pallas(
        flat(q), flat(k), flat(v), flat(g), beta.transpose(0, 2, 1)[..., None],
        flat(gate), weight[None], heads, (SCALE, 1e-6, RMS_EPS), states=True)
    p = kda._heads_a_step(heads)
    assert inverses.shape == (B, t // c, heads // p, c, p * c) and inverses.dtype == v.dtype
    kn = np.asarray(kda.l2norm(k), np.float64)
    for b, n, h in [(0, 0, 0), (1, 1, heads - 1), (0, 1, 1)]:
        rows = slice(n * c, (n + 1) * c)
        G = np.cumsum(np.asarray(g[b, rows, h], np.float64), 0)
        kk = np.einsum("tc,sc,tsc->ts", kn[b, rows, h], kn[b, rows, h],
                       np.exp(np.minimum(G[:, None] - G[None], 0.0)))
        A = np.tril(np.asarray(beta[b, rows, h], np.float64)[:, None] * kk, -1)
        want = np.linalg.inv(np.eye(c) + A)
        got = inverses[b, n, h // p, :, (h % p) * c:(h % p + 1) * c]
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("heads", [4, 3], ids=["pairs", "odd"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_backward_kernel_fed_the_forwards_inverse_is_one_that_remakes_it(
        monkeypatch, heads, dtype):
    """T is stored in the dtype it was multiplied in, so what the backward
    kernel reads is what a replay of the doubling would remake: every
    gradient equals, bit for bit, that of a backward kernel that is handed
    no T (``_unit_lower_inverse`` then runs the doubling, under the same
    rule), at a write strength up to 2."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v, *rest = inputs(192, 0.02, heads=heads, beta_max=2.0)
    args = (q, k, v.astype(dtype), *rest)
    w = jnp.asarray(np.random.default_rng(1).normal(size=v.shape), jnp.float32)
    grad = lambda: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(chunk_kda(*a).astype(jnp.float32) * w), argnums=range(7)))(*args)
    fed = grad()
    monkeypatch.setattr(kda, "_block_diagonal", lambda D, p: None)
    remade = grad()  # traced anew: ``grad`` builds a new function
    for name, a, b in zip(NAMES, fed, remade):
        assert float(jnp.abs(a.astype(jnp.float32)).max()) > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


def dot_generals(jaxpr):
    """Number of dot_general equations in a jaxpr, nested ones too."""
    return sum(
        (eqn.primitive.name == "dot_general")
        + sum(dot_generals(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


@pytest.mark.parametrize("heads,most", [(3, 60), (4, 69)], ids=["one-a-step", "pair"])
def test_a_backward_grid_step_holds_no_chain_of_the_inverse(monkeypatch, heads, most):
    """What a backward grid step multiplies: the chunk's nineteen products
    once (twelve level products, q k^T on the diagonal, W, U0, the state's
    three, Aqk U) and two gradients each, and the inverse's two, -T^T dT
    T^T: 59, and nine more where two heads' states are a head's own. The
    doubling's ten and their twenty gradients, which autodiff of a replayed
    chain brought (87 and 96), are not among them. Beside them the running
    sums and g's cotangent are three exact products each, in both kernels."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = inputs(128, 0.3, heads=heads)
    both = jax.make_jaxpr(jax.grad(lambda *a: chunk_kda(*a).sum()))(*args)
    forward, backward = (eqn.params["jaxpr"] for eqn in pallas_calls(both.jaxpr, []))
    sums = 3
    assert dot_generals(forward) - sums == 29 + 3 * (kda._heads_a_step(heads) - 1)
    assert 50 < dot_generals(backward) - 2 * sums <= most
