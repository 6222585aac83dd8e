"""The gated delta rule of ``ray_tpu/ops/kda.py`` on the CPU: the chunked
function and its ``custom_vjp`` against the token-by-token recurrence,
forward and every gradient, at several sequence lengths and at a decay near
0 and near 1; the Pallas scan kernels in interpret mode against the same,
two heads a grid step and, where the heads do not pair off, one; a head
through the pair path against the same head alone, bit for bit; the short
convolution and the gate beside it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

B, H, DK, DV = 2, 2, 32, 16


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


def inputs(t, decay, seed=0, heads=H):
    """q, k normalised as the mixer does; g = -decay x uniform(0.5, 1.5):
    exp(g) is near 1 at decay 1e-3 and under 1e-6 at decay 30."""
    r = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa: E731
    q = kda.l2norm(draw(B, t, heads, DK)) * DK ** -0.5
    k = kda.l2norm(draw(B, t, heads, DK))
    v = draw(B, t, heads, DV)
    g = -jnp.asarray(r.uniform(0.5, 1.5, size=(B, t, heads, DK)), jnp.float32) * decay
    beta = jax.nn.sigmoid(draw(B, t, heads))
    return q, k, v, g, beta


def compare(t, decay, heads=H):
    args = inputs(t, decay, heads=heads)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)
    want = recurrence(*args)
    got = jax.jit(kda.chunk_kda)(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * float(jnp.abs(want).max()))
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda.chunk_kda(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)
    wanted = jax.grad(
        lambda *a: jnp.sum(recurrence(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), grads, wanted):
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-4 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("decay", [1e-3, 0.3, 30.0], ids=["near1", "mid", "near0"])
@pytest.mark.parametrize("t", [64, 100, 256])
def test_chunked_form_and_its_vjp_are_the_recurrence(t, decay):
    """The XLA form (``lax.scan`` over ``_head_chunk``, differentiated by
    JAX), a length that is no whole number of chunks among them."""
    compare(t, decay)


@pytest.mark.parametrize("heads", [2, 3], ids=["pair", "odd"])
@pytest.mark.parametrize("t,decay", [(100, 0.3), (256, 1e-3), (192, 30.0)])
def test_pallas_kernels_in_interpret_mode_are_the_recurrence(monkeypatch, t, decay, heads):
    """The forward kernel and, under its ``custom_vjp``, the backward kernel
    that differentiates ``_head_chunk`` where it stands: two heads a grid
    step, and three heads one a step."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    compare(t, decay, heads)


def test_a_head_through_the_pair_path_is_the_head_alone_bit_for_bit(monkeypatch):
    """Stacked on another head's rows a head's sums gain exact zeros and
    nothing else: output and all five gradients of four heads, two a grid
    step, equal those of the same call one head a step, and those of each
    head in a call of its own. (g's gradient leaves the kernel through one
    matmul over every head's lanes, which the interpreter's backend sums in
    another order at another width: against a one-head call it agrees to
    rounding, from either path.)"""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = inputs(192, 0.3, heads=4)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)

    def run(w, *a):
        loss = lambda *a: jnp.sum(kda.chunk_kda(*a) * w)  # noqa: E731
        return kda.chunk_kda(*a), *jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*a)

    names = "o q k v g beta".split()
    paired = jax.jit(run)(w, *args)
    for h in range(4):
        alone = lambda x: x[:, :, h:h + 1]  # noqa: E731
        for name, a, b in zip(names, paired, jax.jit(run)(alone(w), *map(alone, args))):
            assert float(jnp.abs(b).max()) > 0, name
            if name == "g":
                np.testing.assert_allclose(
                    alone(a), b, rtol=0, atol=1e-6 * float(jnp.abs(b).max()))
            else:
                np.testing.assert_array_equal(alone(a), b, err_msg=f"head {h}: {name}")
    monkeypatch.setattr(kda, "_PAIR", 1)  # the same call, one head a step
    for name, a, b in zip(names, paired, jax.jit(run)(w, *args)):
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_the_masks_of_stacked_heads_are_block_diagonal():
    """A level's block of 2b <= 64 rows never spans two heads: over 128 rows
    every mask is the one-head mask on both diagonal blocks and false
    between heads."""
    c = kda.CHUNK
    (one, eye1), (two, eye2) = kda._masks(c), kda._masks(2 * c)
    lower = np.tril(np.ones((c, c), bool), -1)
    assert (sum(np.asarray(m, int) for m in one.values()) == lower).all()
    for b, mask in two.items():
        mask = np.asarray(mask)
        assert not mask[:c, c:].any() and not mask[c:, :c].any(), b
        assert (mask[:c, :c] == one[b]).all() and (mask[c:, c:] == one[b]).all(), b
    assert (np.asarray(eye2) == np.eye(2 * c, dtype=bool)).all()
    assert (np.asarray(eye1) == np.eye(c, dtype=bool)).all()


def pallas_calls(jaxpr, found):
    """Every pallas_call equation in a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            pallas_calls(sub, found)
    return found


def pallas_outputs(jaxpr):
    """Number of outputs of every pallas_call in a jaxpr, nested ones too."""
    return [len(eqn.outvars) for eqn in pallas_calls(jaxpr, [])]


@pytest.mark.parametrize("heads,steps", [(32, 16), (3, 3)])
def test_the_grid_takes_two_heads_a_step_where_they_pair_off(monkeypatch, heads, steps):
    """Forward (with its states) and backward, as a gradient lowers them."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = inputs(128, 0.3, heads=heads)
    both = jax.make_jaxpr(jax.grad(lambda *a: kda.chunk_kda(*a).sum()))(*args)
    grids = [eqn.params["grid_mapping"].grid for eqn in pallas_calls(both.jaxpr, [])]
    assert grids == [(B, 128 // kda.CHUNK, steps)] * 2


def test_the_forward_outside_a_gradient_writes_no_states(monkeypatch):
    """A call that did would merge with its remat replay's twin and keep
    every layer's states alive from the forward pass to the backward."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = inputs(128, 0.3)
    forward = jax.make_jaxpr(kda.chunk_kda)(*args)
    assert pallas_outputs(forward.jaxpr) == [1]  # o alone
    both = jax.make_jaxpr(jax.grad(lambda *a: kda.chunk_kda(*a).sum()))(*args)
    # o and the states; then the five cotangents
    assert pallas_outputs(both.jaxpr) == [2, 5]


def test_a_strong_decay_neither_overflows_nor_loses_the_state():
    """exp(-50) a step: every exponent the chunked form takes is <= 0."""
    q, k, v, g, beta = inputs(128, 1.0)
    g = jnp.full_like(g, -50.0).at[:, ::7].set(-1e-4)
    got = kda.chunk_kda(q, k, v, g, beta)
    assert bool(jnp.isfinite(got).all())
    want = recurrence(q, k, v, g, beta)
    # the running sum of g reaches -3000 in a chunk: its float32 rounding
    # (2e-4) is the exponent's
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4 * float(jnp.abs(want).max()))


def test_short_conv_is_causal_and_depthwise():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 10, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    y = np.asarray(kda.short_conv(x, w))
    xn, wn = np.asarray(x), np.asarray(w)
    for t in range(10):
        want = sum(wn[i] * xn[:, t - 3 + i] for i in range(4) if t - 3 + i >= 0)
        np.testing.assert_allclose(y[:, t], want, rtol=1e-5, atol=1e-6)
    # a later token changes no earlier output, a channel no other channel
    y2 = np.asarray(kda.short_conv(x.at[:, 7, 2].add(1.0), w))
    assert (y2[:, :7] == y[:, :7]).all()
    assert (np.delete(y2, 2, axis=2) == np.delete(y, 2, axis=2)).all()


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
