"""The gated delta rule of ``ray_tpu/ops/kda.py`` on the CPU: the chunked
function and its ``custom_vjp``, which take q and k raw and give o
normalised and gated, against the plain way (L2 norms, the token-by-token
recurrence, ``RMSNorm``, the gate's sigmoid), forward and every gradient, at
several sequence lengths and at a decay near 0 and near 1; the Pallas scan
kernels in interpret mode against the same, two heads a grid step and, where
the heads do not pair off, one; a head through the pair path against the
same head alone, bit for bit; rows of zeros; the weight's gradient over
batch rows; the short convolution and the gate beside it; the convolution
with its SiLU and v's rounding as one Pallas pass forward and one backward
(``conv_silu``) against ``silu(short_conv)`` and its gradients, across block
and tile edges, at the sequence's start, over batch rows, rounded to
bfloat16, and where a shape does not tile. The write strength
runs over (0, 1) and, as a configuration with negative eigenvalues doubles
it, over (0, 2): the kernels against the recurrence there, the state they
carry against the recurrence's own, what a cap at 1 or a doubling left out
would give, and the bfloat16 matmuls' distance from float32. The chunk's
inverse T = (I + A)^-1 is a function with a derivative rule of its own (-T^T
dT T^T): the rule against autodiff of the doubling, the T the forward kernel
writes under a gradient against the system's inverse, the backward kernel fed
that T against one that remakes it, and the matmuls a backward grid step
holds.
"""
import functools
import hashlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import RMSNorm
from ray_tpu.ops import kda

B, H, DK, DV = 2, 2, 32, 16
SCALE, RMS_EPS = DK ** -0.5, 1e-5
NAMES = "q k v g beta gate weight".split()
chunk_kda = functools.partial(kda.chunk_kda, scale=SCALE, rms_eps=RMS_EPS)


def fresh():
    """``chunk_kda`` as a new function object: ``jit`` and ``make_jaxpr`` keep
    their traces by the function, and which path a trace took (the kernels
    or ``lax.scan``) follows RAY_TPU_PALLAS_INTERPRET, which they do not
    see."""
    return lambda *a: chunk_kda(*a)


def gated_norm(o, gate, weight):
    """The mixer's way out of the recurrence before the kernels took it:
    ``RMSNorm`` over a head's channels, then the output gate."""
    normed = RMSNorm(RMS_EPS).apply({"params": {"scale": weight}}, o)
    return normed * jax.nn.sigmoid(gate)


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


@pytest.mark.parametrize("decay", [1e-3, 0.3, 30.0], ids=["near1", "mid", "near0"])
@pytest.mark.parametrize("t", [64, 100, 256])
def test_chunked_form_and_its_vjp_are_the_recurrence(t, decay):
    """The XLA form (``lax.scan`` over ``_head_chunk``, differentiated by
    JAX), a length that is no whole number of chunks among them."""
    compare(t, decay)


@pytest.mark.parametrize("heads", [2, 3], ids=["pair", "odd"])
@pytest.mark.parametrize("t,decay,beta_max", [
    (100, 0.3, 1.0), (256, 1e-3, 1.0), (192, 30.0, 1.0),
    # beta = 2 sigmoid, over (0, 2): a weak decay, where the chunk's system
    # is furthest from the identity, and a length with a padded chunk
    (256, 1e-3, 2.0), (100, 0.3, 2.0),
], ids=["100-0.3", "256-0.001", "192-30.0", "256-0.001-beta<2", "100-0.3-beta<2"])
def test_pallas_kernels_in_interpret_mode_are_the_recurrence(
        monkeypatch, t, decay, beta_max, heads):
    """The forward kernel and, under its ``custom_vjp``, the backward kernel
    that differentiates ``_head_chunk`` where it stands: two heads a grid
    step, and three heads one a step; the write strength in (0, 1) and in
    (0, 2)."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    compare(t, decay, heads, beta_max=beta_max)


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


def test_a_head_through_the_pair_path_is_the_head_alone_bit_for_bit(monkeypatch):
    """Stacked on another head's rows a head's sums gain exact zeros and
    nothing else: output and the gradients of four heads, two a grid step,
    equal those of the same call one head a step, and those of each head in
    a call of its own. (g's gradient leaves the kernel through one matmul
    over every head's lanes, and q's and k's through the normalisation's
    own, a row sum that the interpreter's backend fuses with its neighbours
    as the block's width lets it: against a one-head call these three agree
    to rounding, from either path. The weight is every head's: its gradient
    is the sum of theirs, in the order of the grid's steps.)"""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    *args, weight = inputs(192, 0.3, heads=4)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)

    def run(w, *a):
        loss = lambda *a: jnp.sum(chunk_kda(*a) * w)  # noqa: E731
        return chunk_kda(*a), *jax.grad(loss, argnums=range(7))(*a)

    names = ["o", *NAMES]
    paired = jax.jit(run)(w, *args, weight)
    d_weight = 0.0
    for h in range(4):
        alone = lambda x: x[:, :, h:h + 1]  # noqa: E731
        *got, d_weight_h = jax.jit(run)(alone(w), *map(alone, args), weight)
        d_weight = d_weight + d_weight_h
        for name, a, b in zip(names, paired, got):
            assert float(jnp.abs(b).max()) > 0, name
            if name in "qkg":
                np.testing.assert_allclose(
                    alone(a), b, rtol=0, atol=1e-6 * float(jnp.abs(b).max()))
            else:
                np.testing.assert_array_equal(alone(a), b, err_msg=f"head {h}: {name}")
    np.testing.assert_allclose(paired[-1], d_weight, rtol=1e-5)
    monkeypatch.setattr(kda, "_PAIR", 1)  # the same call, one head a step
    alone_a_step = jax.jit(lambda *a: run(*a))(w, *args, weight)  # traced anew
    for name, a, b in zip(names, paired, alone_a_step):
        if name == "weight":
            np.testing.assert_allclose(a, b, rtol=1e-5)
        elif name in "qk":  # the normalisation's own, as above
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * float(jnp.abs(b).max()))
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_row_of_zeros_in_q_or_k_gives_zeros_and_finite_gradients(monkeypatch, path):
    """The epsilons stand inside the roots: a token whose q is zero reads
    zero (and its RMSNorm gives zero), one whose k is zero writes nothing,
    and every gradient is finite and the plain way's."""
    if path == "pallas":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, *rest = inputs(128, 0.3)
    q, k = q.at[:, 5].set(0.0).at[:, 64].set(0.0), k.at[:, 9].set(0.0).at[:, 64].set(0.0)
    got = chunk_kda(q, k, *rest)
    assert not np.asarray(got[:, 5]).any() and not np.asarray(got[:, 64]).any()
    assert np.asarray(got[:, 9]).any()
    compare(128, 0.3, args=(q, k, *rest))


@pytest.mark.parametrize("stacked", [1, 2], ids=["one-head", "pair"])
def test_a_chunks_normalisations_are_l2norm_before_it_and_rmsnorm_and_the_gate_after(stacked):
    """``_normed_chunk``, which both kernels and the XLA form run, is
    ``_head_chunk`` of ``l2norm(q) * scale`` and ``l2norm(k)`` and then
    ``RMSNorm`` and the gate on its float32 o: value, the state, and the
    vector-Jacobian products of every operand."""
    r = np.random.default_rng(3)
    n = stacked * kda.CHUNK
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa: E731
    St, q, k, v = draw(stacked * DV, DK), draw(n, DK), draw(n, DK), draw(n, DV)
    beta = jax.nn.sigmoid(draw(n, 1))
    G = jnp.concatenate([jnp.cumsum(-jnp.abs(draw(kda.CHUNK, DK)) * 0.1, 0)
                         for _ in range(stacked)])
    last = jnp.concatenate([jnp.broadcast_to(x[-1:], (kda.CHUNK, DK))
                            for x in jnp.split(G, stacked)])
    last_dv = jnp.concatenate([jnp.broadcast_to(x[-1:], (DV, DK))
                               for x in jnp.split(G, stacked)])
    args = (St, q, k, v, beta, G, last, last_dv, draw(n, DV), 1.0 + 0.3 * draw(1, DV))

    def plain(St, q, k, v, beta, G, last, last_dv, gate, weight):
        St, o, _ = kda._head_chunk(St, kda.l2norm(q) * SCALE, kda.l2norm(k), v, beta,
                                   G, last, last_dv)
        return St, gated_norm(o, gate, weight[0])

    def fused(*a):
        return kda._normed_chunk(*a, norm=(SCALE, 1e-6, RMS_EPS))[:2]

    got, pull = jax.vjp(fused, *args)
    want, pull_plain = jax.vjp(plain, *args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    cot = (draw(*want[0].shape), draw(*want[1].shape))
    for i, (a, b) in enumerate(zip(pull(cot), pull_plain(cot))):
        assert float(jnp.abs(b).max()) > 0, i
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()), err_msg=str(i))


def test_the_weights_gradient_adds_up_over_batch_rows_and_grid_steps(monkeypatch):
    """The backward kernel adds the weight's cotangent up in a batch row's
    output block over that row's steps, and the rows are summed outside: the
    gradient of a batch of two is the sum of each row's in a call of its
    own, and the gate's of a row is that row's alone."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    *args, weight = inputs(192, 0.3)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)
    grad = jax.jit(jax.grad(
        lambda w, gate, weight, *a: jnp.sum(chunk_kda(*a, gate, weight) * w), (1, 2)))
    d_gate, d_weight = grad(w, args[5], weight, *args[:5])
    rows = [grad(w[b:b + 1], args[5][b:b + 1], weight, *(x[b:b + 1] for x in args[:5]))
            for b in range(B)]
    assert float(jnp.abs(rows[0][1]).max()) > 0 and float(jnp.abs(rows[1][1]).max()) > 0
    np.testing.assert_allclose(d_weight, rows[0][1] + rows[1][1], rtol=1e-5)
    for b in range(B):
        np.testing.assert_array_equal(d_gate[b:b + 1], rows[b][0])


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
    both = jax.make_jaxpr(jax.grad(lambda *a: chunk_kda(*a).sum()))(*args)
    grids = [eqn.params["grid_mapping"].grid for eqn in pallas_calls(both.jaxpr, [])]
    assert grids == [(B, 128 // kda.CHUNK, steps)] * 2


def test_the_forward_outside_a_gradient_writes_no_states(monkeypatch):
    """A call that did would merge with its remat replay's twin and keep
    every layer's states, and every chunk's inverse, alive from the forward
    pass to the backward."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = inputs(128, 0.3)
    forward = jax.make_jaxpr(fresh())(*args)
    assert pallas_outputs(forward.jaxpr) == [1]  # o alone
    both = jax.make_jaxpr(jax.grad(lambda *a: chunk_kda(*a).sum()))(*args)
    # o, the states and the inverses; then the seven cotangents
    assert pallas_outputs(both.jaxpr) == [3, 7]


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


def tokens_first(y):
    """[B, D / d, T, d], heads first, as [B, T, D]: channel h * d + c from [h, :, c]."""
    return y.transpose(0, 2, 1, 3).reshape(y.shape[0], y.shape[2], -1)


def heads_first(y, d):
    """``tokens_first`` undone (d None: nothing)."""
    return y if d is None else y.reshape(*y.shape[:2], -1, d).transpose(0, 2, 1, 3)


def conv_reference(x, w, dtype, heads=None):
    return heads_first(jax.nn.silu(kda.short_conv(x, w)).astype(dtype), heads)


def conv_inputs(batch, t, channels, dtype, seed=0, heads=None):
    """A projection, a filter and a cotangent of the output's dtype and
    layout."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    dy = jax.random.normal(keys[2], (batch, t, channels), jnp.float32).astype(dtype)
    return (jax.random.normal(keys[0], (batch, t, channels), jnp.float32),
            jax.random.uniform(keys[1], (4, channels), jnp.float32, -0.5, 0.5),
            heads_first(dy, heads))


def conv_and_gradients(fn, x, w, dy, **layout):
    y, vjp = jax.vjp(lambda x, w: fn(x, w, dy.dtype, **layout), x, w)
    return (y, *vjp(dy))


# (batch, tokens, channels, the output's dtype, the lanes of a head where the
# output lies heads first, the kernels' blocks or None): three blocks of 512
# rows and two of 128 lanes, every block eight tiles of 64 rows, so the halo
# crosses tile and block edges both ways; one tile of 16 rows, most of it the
# filter's reach from t < 0; two batch rows, over which and over whose blocks
# the filter's gradient adds up; v's rounding to bfloat16 (its cotangent
# arrives in bfloat16, 16 rows a sublane tile); and shapes that do not tile,
# in tokens and in channels. Heads first: Olmo-Hybrid's key heads, four of 96
# lanes to a block of 384 (a head's lanes begin inside a vreg), two blocks of
# rows and two of lanes; its value heads, two of 192 to a block, bfloat16 out
# and back, two batch rows of three blocks; heads of whole vregs; and 192
# channels, where no whole vregs are whole heads of 96.
CONV_CASES = {
    "three-blocks": (1, 1536, 256, jnp.float32, None, (512, 256, 64, True, 0)),
    "one-tile": (1, 16, 128, jnp.float32, None, (16, 128, 16, True, 0)),
    "batch-of-2": (2, 256, 128, jnp.float32, None, (256, 128, 64, True, 0)),
    "bfloat16-out": (2, 192, 128, jnp.bfloat16, None, (64, 128, 64, True, 0)),
    "tokens-do-not-tile": (2, 100, 128, jnp.float32, None, None),
    "lanes-do-not-tile": (2, 64, 96, jnp.bfloat16, None, None),
    "heads-of-96": (1, 1024, 768, jnp.float32, 96, (512, 384, 64, True, 96)),
    "heads-of-192-bfloat16": (2, 192, 384, jnp.bfloat16, 192, (64, 384, 64, True, 192)),
    "heads-of-128": (1, 256, 256, jnp.float32, 128, (256, 256, 64, True, 128)),
    "heads-do-not-tile": (2, 64, 192, jnp.float32, 96, None),
}


@pytest.mark.parametrize("case", sorted(CONV_CASES))
def test_the_fused_convolution_is_silu_of_short_conv_and_its_gradients(monkeypatch, case):
    """Under the interpreter ``conv_silu`` is the Pallas pass where the shape
    tiles and ``silu(short_conv(x, w))`` as XLA has it where it does not:
    the values and the gradients in x and in w, to float32's reassociation
    (the filter's gradient is a sum over every token, in another order).
    Told a head's lanes, the output and its cotangent lie [B, D / d, T, d]:
    ``silu(short_conv)`` transposed, either way."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    batch, t, channels, dtype, heads, blocks = CONV_CASES[case]
    x, w, dy = conv_inputs(batch, t, channels, dtype, heads=heads)
    assert kda._conv_blocks(x, w, heads) == blocks
    both = jax.make_jaxpr(
        lambda *a: conv_and_gradients(kda.conv_silu, *a, heads=heads))(x, w, dy)
    names = [eqn.params["jaxpr"].debug_info.func_name for eqn in pallas_calls(both.jaxpr, [])]
    assert names == (["_conv_fwd_kernel", "_conv_bwd_kernel"] if blocks else [])
    y, dx, dw = conv_and_gradients(kda.conv_silu, x, w, dy, heads=heads)
    y_ref, dx_ref, dw_ref = conv_and_gradients(conv_reference, x, w, dy, heads=heads)
    assert (y.dtype, dx.dtype, dw.dtype) == (dtype, jnp.float32, jnp.float32)
    assert y.shape == dy.shape == (
        (batch, channels // heads, t, heads) if heads else (batch, t, channels))
    if blocks is None:
        assert all(bool((a == b).all()) for a, b in ((y, y_ref), (dx, dx_ref), (dw, dw_ref)))
        return
    if dtype == jnp.bfloat16:  # one rounding, of float32 values an ulp apart at most
        assert float(jnp.mean(y != y_ref)) < 1e-3
    np.testing.assert_allclose(
        y.astype(jnp.float32), y_ref.astype(jnp.float32),
        rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-6)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dw, dw_ref, rtol=1e-5, atol=1e-5 * float(jnp.abs(dw_ref).max()))


@pytest.mark.parametrize("heads", [None, 64], ids=["tokens-first", "heads-of-64"])
def test_the_fused_convolution_is_causal_across_its_blocks_and_depthwise(monkeypatch, heads):
    """A bump at token 7 moves nothing before it and nothing after token 10,
    one at a block's last token moves the next block's first three (the
    halo), and neither moves another channel or batch row; the gradient in x
    reaches back as far and no further. Blocks of 32 rows in tiles of 16;
    heads first, two heads of 64 lanes to the block's 128, read back as they
    lie tokens first."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(kda, "_CONV_ROWS", 32)
    monkeypatch.setattr(kda, "_CONV_TILE", 16)
    x, w, _ = conv_inputs(2, 96, 128, jnp.float32, seed=1)
    assert kda._conv_blocks(x, w, heads) == (32, 128, 16, True, heads or 0)

    def conv(x):
        y = kda.conv_silu(x, w, heads=heads)
        return tokens_first(y) if heads else y

    y = np.asarray(conv(x))
    np.testing.assert_allclose(y, conv_reference(x, w, jnp.float32), rtol=1e-5, atol=1e-6)
    for token in (7, 15, 31, 95):
        moved = np.asarray(conv(x.at[1, token, 2].add(1.0))) != y
        assert moved[1, token:token + 4, 2].all()
        moved[1, token:token + 4, 2] = False
        assert not moved.any(), token
        # dy at tokens token .. token + 3 reaches x at token, and at no other
        reach = jax.grad(lambda x: conv(x)[1, token:token + 4, 2].sum())(x)
        reached = np.argwhere(np.asarray(reach) != 0)
        assert {tuple(at[[0, 2]]) for at in reached} == {(1, 2)}
        assert set(reached[:, 1]) == set(range(max(token - 3, 0), min(token + 4, 96)))


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


@pytest.mark.parametrize("t,decay", [(64, 0.3), (100, 1e-3), (192, 30.0)],
                         ids=["64-0.3", "100-0.001", "192-30.0"])
def test_the_scalar_decay_chunked_form_and_its_vjp_are_the_recurrence(t, decay):
    """The XLA form (``lax.scan`` over ``_normed_gdn_chunk``), beta over (0,
    2), a weak and a strong decay, a length that is no whole number of chunks."""
    gdn_compare(t, decay)


@pytest.mark.parametrize("heads", [2, 3], ids=["pair", "odd"])
@pytest.mark.parametrize("t,decay", [(100, 0.3), (128, 1e-3), (128, 30.0)],
                         ids=["100-0.3", "128-0.001", "128-30.0"])
def test_the_scalar_decay_kernels_in_interpret_mode_are_the_recurrence(
        monkeypatch, t, decay, heads):
    """``_gdn_fwd_kernel`` and, under the ``custom_vjp``, ``_gdn_bwd_kernel``:
    forward and all seven cotangents, two heads a grid step and, where they do
    not pair off, one; heads of 24/48 lanes."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    gdn_compare(t, decay, heads)


def test_v_the_gate_and_o_stay_tokens_first_where_a_steps_heads_are_whole_vregs(monkeypatch):
    """Two value heads of 64 lanes are one vreg side by side: v and the gate
    go into both kernels and o and their cotangents come out of them as [B,
    T, H * dv], as the convolution and the matmuls around the scan have
    them, and a grid step takes its two heads' lanes apart and puts them
    together in VMEM. The same recurrence, forward and all seven cotangents;
    at 48 lanes a head (every other case here) the three lie heads first, [B,
    H, T, dv], transposed by XLA."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(sys.modules[__name__], "GDV", 64)
    lie = kda._values_lie_tokens_first
    assert lie(2, 64) and not lie(2, 48) and lie(30, 192) and not lie(15, 192)
    gdn_compare(128, 0.3, heads=2)
    args = gdn_inputs(128, 0.3, heads=2)
    both = jax.make_jaxpr(jax.grad(lambda *a: chunk_gdn(*a).sum(), argnums=(2, 5)))(*args)
    forward, backward = pallas_calls(both.jaxpr, [])
    flat, heads_first = (B, 128, 2 * 64), (B, 2, 128, 64)
    assert [v.aval.shape for v in forward.invars].count(flat) == 2  # v, the gate
    assert forward.outvars[0].aval.shape == flat  # o
    assert [v.aval.shape for v in backward.invars].count(flat) == 3  # and do
    assert [v.aval.shape for v in backward.outvars].count(flat) == 2  # v's, the gate's
    for call in (forward, backward):
        assert heads_first not in [v.aval.shape for v in (*call.invars, *call.outvars)]


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_the_scalar_road_is_the_kda_road_fed_g_broadcast_over_channels(monkeypatch, path):
    """One function two ways: ``chunk_kda`` given the scalar on every channel
    and its sigmoid gate times the gate is SiLU's. (Six times the level
    products and dk times g's bytes: why the scalar has kernels of its own.)"""
    if path == "pallas":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v, g, beta, gate, weight = gdn_inputs(128, 0.3)
    got = jax.jit(lambda *a: chunk_gdn(*a))(q, k, v, g, beta, gate, weight)
    channels = jnp.broadcast_to(g[..., None], q.shape)
    want = kda.chunk_kda(q, k, v, channels, beta, gate, weight,
                         scale=GDK ** -0.5, rms_eps=RMS_EPS) * gate
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * float(jnp.abs(want).max()))


def test_the_scalar_kernels_take_one_decay_a_head_and_token(monkeypatch):
    """Forward (with its states and inverses under a gradient, o alone outside
    one) and backward, under names of their own, two heads a step; no operand
    or result of either is g on a head's channels: the decay and its cotangent
    are [B, H, T, 1]. q and k are one operand [B, 2, H, T, dk], and their
    cotangents one result."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = gdn_inputs(128, 0.3, heads=4)
    forward = jax.make_jaxpr(lambda *a: chunk_gdn(*a))(*args)
    assert pallas_outputs(forward.jaxpr) == [1]
    both = jax.make_jaxpr(jax.grad(lambda *a: chunk_gdn(*a).sum()))(*args)
    calls = pallas_calls(both.jaxpr, [])
    assert [len(eqn.invars) for eqn in calls] == [6, 9]
    assert [len(eqn.outvars) for eqn in calls] == [3, 6]
    assert [eqn.params["grid_mapping"].grid for eqn in calls] == [(B, 2, 2)] * 2
    names = [eqn.params["jaxpr"].debug_info.func_name for eqn in calls]
    assert names == ["_gdn_fwd_kernel", "_gdn_bwd_kernel"]
    for eqn in calls:
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
        assert shapes.count((B, 4, 128, 1)) == (2 if eqn is calls[0] else 4)  # g, beta (and theirs)
        assert shapes.count((B, 2, 4, 128, GDK)) == (1 if eqn is calls[0] else 2)
        assert (B, 4, 128, GDK) not in shapes and (B, 128, 4 * GDK) not in shapes
        # two heads of 48 lanes fill no vreg: v, the gate and o heads first
        assert shapes.count((B, 4, 128, GDV)) == (3 if eqn is calls[0] else 5)


def test_the_convolutions_heads_first_output_is_what_the_scalar_kernels_read(monkeypatch):
    """The mixer's road, projections to o: ``conv_silu(..., heads=dk)`` of the
    fused q-with-k projection, a reshape of its major extent, ``conv_silu`` of
    v's as it lies, ``chunk_gdn``. It is ``silu(short_conv)`` sliced into q
    and k, split into heads and transposed by XLA, then the same scan: o and
    the gradients in both projections, both filters and the four other
    operands. And no transposition of a q, k or v stands in its trace,
    forward or backward, nor (two heads of 64 lanes being a vreg) of the gate
    or o: only the decay and beta turn."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    heads, dk, dv, t = 2, 32, 64, 128
    r = np.random.default_rng(3)
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa: E731
    filt = lambda d: jnp.asarray(r.uniform(-0.5, 0.5, size=(4, d)), jnp.float32)  # noqa: E731
    operands = (
        draw(B, t, 2 * heads * dk), filt(2 * heads * dk), draw(B, t, heads * dv),
        filt(heads * dv), -0.3 * jnp.asarray(r.uniform(0.5, 1.5, size=(B, t, heads)), jnp.float32),
        2.0 * jax.nn.sigmoid(draw(B, t, heads)), draw(B, t, heads, dv), 1.0 + 0.3 * draw(dv))
    scan = functools.partial(kda.chunk_gdn, scale=dk ** -0.5, rms_eps=RMS_EPS)

    def by_the_kernels(qk, qk_filter, v, v_filter, *rest):
        qk = kda.conv_silu(qk, qk_filter, heads=dk).reshape(B, 2, heads, t, dk)
        return scan(qk, kda.conv_silu(v, v_filter).reshape(B, t, heads, dv), *rest)

    def by_xla(qk, qk_filter, v, v_filter, *rest):
        qk = conv_reference(qk, qk_filter, jnp.float32).reshape(B, t, 2, heads, dk)
        v = conv_reference(v, v_filter, jnp.float32).reshape(B, t, heads, dv)
        return scan(qk.transpose(0, 2, 3, 1, 4), v, *rest)

    w = draw(B, t, heads, dv)
    got, got_grads = jax.value_and_grad(
        lambda *a: jnp.sum(by_the_kernels(*a) * w), argnums=range(8))(*operands)
    want, want_grads = jax.value_and_grad(
        lambda *a: jnp.sum(by_xla(*a) * w), argnums=range(8))(*operands)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(got_grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4 * float(jnp.abs(b).max()))

    def transposed(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "transpose":
                found.append(eqn.invars[0].aval.shape)
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    transposed(sub, found)
        return found

    both = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(by_the_kernels(*a) * w), argnums=range(8)))(*operands)
    turned = transposed(both.jaxpr, [])
    assert turned and set(turned) <= {(B, t, heads), (B, heads, t)}, turned
    names = [eqn.params["jaxpr"].debug_info.func_name for eqn in pallas_calls(both.jaxpr, [])]
    assert sorted(names) == sorted(
        ["_conv_fwd_kernel"] * 2 + ["_gdn_fwd_kernel", "_gdn_bwd_kernel"]
        + ["_conv_bwd_kernel"] * 2)


def test_a_strong_scalar_decay_neither_overflows_nor_loses_the_state():
    """exp(-50) a step with a weak one every seventh: every exponent the
    chunk's decay matrix takes is masked to <= 0 before it is taken."""
    q, k, v, g, beta, gate, weight = gdn_inputs(128, 1.0)
    g = jnp.full_like(g, -50.0).at[:, ::7].set(-1e-4)
    got = chunk_gdn(q, k, v, g, beta, gate, weight)
    assert bool(jnp.isfinite(got).all())
    want = gdn_oracle(q, k, v, g, beta, gate, weight)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4 * float(jnp.abs(want).max()))


def test_the_convolutions_lanes_are_the_most_vregs_that_divide_the_channels(monkeypatch):
    """5,760 channels (Olmo-Hybrid's q with k, and its v) are 45 vregs, which
    no power of two above one divides: blocks of 384 lanes; 2,880 alone do not
    tile; 4,096 and 8,192 keep their 512."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    w = jax.ShapeDtypeStruct((4, 1), jnp.float32)
    lanes = lambda d: kda._conv_blocks(jax.ShapeDtypeStruct((1, 8192, d), jnp.float32), w)  # noqa: E731
    assert (lanes(5760).lanes, lanes(4096).lanes, lanes(8192).lanes, lanes(384).lanes) == (
        384, 512, 512, 384)
    assert lanes(2880) is None
    # whole heads too, where the output lies heads first: four of 96 or two of
    # 192 are the 384, 128 fill the 512, and 96 of 4,096 channels fit no block
    heads = lambda d, n: kda._conv_blocks(  # noqa: E731
        jax.ShapeDtypeStruct((1, 8192, d), jnp.float32), w, n)
    assert (heads(5760, 96).lanes, heads(5760, 192).lanes, heads(4096, 128).lanes) == (
        384, 384, 512)
    assert heads(4096, 96) is None
    x, wts, _ = conv_inputs(1, 64, 384, jnp.float32)
    np.testing.assert_allclose(
        kda.conv_silu(x, wts), conv_reference(x, wts, jnp.float32), rtol=1e-6, atol=1e-6)


# ------------------------------------- the fixed decay (``chunk_lightning``)
# Lightning attention's road: no write strength, no learned decay, no inverse;
# one slope a head, the chunk's decay matrix from it and the positions, chunks
# of 128 rows of one head, a sigmoid gate after o's RMSNorm. Against the
# token-by-token recurrence S_t = exp(-s) S_{t-1} + k_t v_t^T.
LD = 24


def lightning_slopes(heads, factor):
    """2^(-8 (h + 1) / H) times a layer's factor, as the model builds them."""
    return jnp.asarray(
        [2.0 ** (-8.0 * (h + 1) / heads) * factor for h in range(heads)], jnp.float32)


def lightning_inputs(t, heads, seed=0):
    r = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa: E731
    return (draw(B, t, heads, LD), draw(B, t, heads, LD), draw(B, t, heads, LD),
            draw(B, t, heads, LD), 1.0 + 0.3 * draw(LD))


def lightning_oracle(q, k, v, gate, weight, slopes):
    def one(q, k, v, s):  # one (batch, head): [T, d]
        def step(S, x):
            q, k, v = x
            S = jnp.exp(-s) * S + jnp.outer(k, v)
            return S, S.T @ q

        return jax.lax.scan(step, jnp.zeros((LD, LD)), (q, k, v))[1]

    heads = jax.vmap(one, in_axes=(1, 1, 1, 0), out_axes=1)
    with jax.default_matmul_precision("highest"):
        o = jax.vmap(heads, in_axes=(0, 0, 0, None))(q, k, v, slopes) * LD ** -0.5
    return gated_norm(o, gate, weight)


def lightning_compare(t, heads, factor):
    args = lightning_inputs(t, heads)
    slopes = lightning_slopes(heads, factor)
    run = lambda *a: kda.chunk_lightning(  # noqa: E731
        *a, slopes, scale=LD ** -0.5, rms_eps=RMS_EPS)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)
    want = lightning_oracle(*args, slopes)
    got = jax.jit(run)(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * float(jnp.abs(want).max()))
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(run(*a) * w), argnums=range(5)))(*args)
    wanted = jax.grad(
        lambda *a: jnp.sum(lightning_oracle(*a, slopes) * w), argnums=range(5))(*args)
    for name, a, b in zip("q k v gate weight".split(), grads, wanted):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-4 * float(jnp.abs(b).max()), err_msg=name)


# The layer's factor runs from 1 + 1e-5 (layer 0) to 1e-5 (the last layer):
# the strongest slope 0.84 a token, the weakest forgets nothing.
LIGHTNING_CASES = [(256, 2, 1.0), (300, 3, 1.0 - 3 / 31), (600, 3, 1e-5)]
LIGHTNING_IDS = ["256-pair-first", "300-odd-fourth", "600-odd-last"]


@pytest.mark.parametrize("t,heads,factor", LIGHTNING_CASES, ids=LIGHTNING_IDS)
def test_the_fixed_decay_chunked_form_and_its_vjp_are_the_recurrence(t, heads, factor):
    """The XLA form (``lax.scan`` over ``_lightning_chunk``): lengths that are
    no whole number of chunks, odd heads, slopes across the layer factor's
    range."""
    lightning_compare(t, heads, factor)


@pytest.mark.parametrize("t,heads,factor", LIGHTNING_CASES, ids=LIGHTNING_IDS)
def test_the_fixed_decay_kernels_in_interpret_mode_are_the_recurrence(
        monkeypatch, t, heads, factor):
    """``_lightning_fwd_kernel`` and, under the ``custom_vjp``,
    ``_lightning_bwd_kernel``: forward and all five cotangents. A bfloat16
    state would miss these by a hundred times the tolerance."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    lightning_compare(t, heads, factor)


def test_the_fixed_decay_kernels_carry_a_float32_state_and_take_no_learned_decay(monkeypatch):
    """Forward (with every chunk's first state under a gradient, o alone
    outside one) and backward under names of their own, a head a grid step
    over chunks of 256; the states are float32 [B, H, chunks, dv, dk]; no
    operand is a decay a token; the slopes get no gradient."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = lightning_inputs(512, 4)
    slopes = lightning_slopes(4, 1.0)
    run = lambda *a: kda.chunk_lightning(*a, scale=LD ** -0.5, rms_eps=RMS_EPS)  # noqa: E731
    forward = jax.make_jaxpr(run)(*args, slopes)
    assert pallas_outputs(forward.jaxpr) == [1]
    both = jax.make_jaxpr(jax.grad(lambda *a: run(*a).sum(), argnums=range(6)))(*args, slopes)
    calls = pallas_calls(both.jaxpr, [])
    assert [len(eqn.outvars) for eqn in calls] == [2, 5]
    assert [eqn.params["grid_mapping"].grid for eqn in calls] == [(B, 4, 2)] * 2
    names = [eqn.params["jaxpr"].debug_info.func_name for eqn in calls]
    assert names == ["_lightning_fwd_kernel", "_lightning_bwd_kernel"]
    states = calls[0].outvars[1].aval
    assert (states.shape, states.dtype) == ((B, 4, 2, LD, LD), jnp.float32)
    for eqn in calls:
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
        assert (B, 4, 512, 1) not in shapes and (B, 512, 4) not in shapes
    grads = jax.grad(lambda *a: run(*a).sum(), argnums=range(6))(*args, slopes)
    assert not np.asarray(grads[5]).any()


def test_a_strong_fixed_decay_neither_overflows_nor_loses_the_state():
    """exp(-40) a token beside a head that forgets nothing: every exponent is
    of a distance times a slope, never positive."""
    args = lightning_inputs(600, 2)
    slopes = jnp.asarray([40.0, 0.0], jnp.float32)
    got = kda.chunk_lightning(*args, slopes, scale=LD ** -0.5, rms_eps=RMS_EPS)
    assert bool(jnp.isfinite(got).all())
    want = lightning_oracle(*args, slopes)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4 * float(jnp.abs(want).max()))


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


def conv_calls_text(t, channels, dtype, biased):
    """What ``conv_silu`` and its gradient trace to at a shape, kernels and
    all: each ``pallas_call``'s kernel as a jaxpr, its grid, and every
    operand's and result's block, index map and array."""
    x = jax.ShapeDtypeStruct((1, t, channels), jnp.float32)
    w = jax.ShapeDtypeStruct((4, channels), jnp.float32)
    b = [jax.ShapeDtypeStruct((channels,), jnp.float32)] * biased
    dy = jax.ShapeDtypeStruct((1, t, channels), dtype)
    both = jax.make_jaxpr(lambda x, w, dy, *b: jax.vjp(
        lambda x, w, *b: kda.conv_silu(x, w, dtype, *b), x, w, *b)[1](dy))(x, w, dy, *b)
    text = []
    for eqn in pallas_calls(both.jaxpr, []):
        mapping = eqn.params["grid_mapping"]
        text += [str(eqn.params["jaxpr"]), str(mapping.grid)]
        text += [f"{m.block_shape} {m.index_map_jaxpr} {m.array_aval}"
                 for m in mapping.block_mappings]
    return "\n".join(text)


# Read by this same code at the parent of the PR that gave ``conv_silu`` its
# ``heads`` (commit 2399a98), at the widths of the cells that call it without:
# Kimi-Linear's q and k (b1 x s16384, 32 heads of 128, float32 out) and its v
# (bfloat16 out and back), Solar-Open2's (b1 x s4096, 64 heads of 128) and
# Granite's biased pass over x, B and C (b1 x s8192, 4,352 channels, bfloat16).
CONV_BEFORE = {
    "kimi-linear-q-and-k": ((16384, 4096, jnp.float32, 0), "61ec1ae74855cfa9"),
    "kimi-linear-v": ((16384, 4096, jnp.bfloat16, 0), "53a9bfa1e55431ea"),
    "solar-open2-q-and-k": ((4096, 8192, jnp.float32, 0), "937a2f889da98451"),
    "granite-xbc": ((8192, 4352, jnp.bfloat16, 1), "88c48436988f48c2"),
}


@pytest.mark.parametrize("name", sorted(CONV_BEFORE))
def test_without_heads_the_convolution_lowers_what_it_lowered(monkeypatch, name):
    """``heads=None`` changes no operand, block, index map or operation of
    either kernel: the forward and backward calls at the widths of the three
    cells that convolve tokens first are, as text, what they were before the
    output could lie heads first. And told a head's lanes the same shape
    traces to another text: the digest sees the layout."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    shape, before = CONV_BEFORE[name]
    text = conv_calls_text(*shape)
    assert hashlib.sha1(text.encode()).hexdigest()[:16] == before
    t, channels, dtype, biased = shape
    x = jax.ShapeDtypeStruct((1, t, channels), jnp.float32)
    w = jax.ShapeDtypeStruct((4, channels), jnp.float32)
    first = jax.make_jaxpr(lambda x, w: kda.conv_silu(x, w, dtype, heads=128))(x, w)
    (call,) = pallas_calls(first.jaxpr, [])
    assert str(call.params["jaxpr"]) not in text
    assert call.outvars[0].aval.shape == (1, channels // 128, t, 128)


# ------------------------------ the step-scaled scalar decay (``chunk_ssd``)
# Mamba-2's road: no delta rule and no inverse; a decay a head and token made
# from a step and the head's rate, the input scaled by the step, B and C one
# pair a token for every head, a skip a head; chunks of 256 rows, 8 heads a
# grid step. Against the token-by-token recurrence S_t = exp(dl A) S_{t-1} +
# dl u B^T, y_t = S_t C_t + D u_t.
SP, SN = 24, 40


def ssd_inputs(t, heads, seed=0, p=SP, n=SN, large_at=None):
    """u, the steps (log-uniform over [0.001, 0.1]; at ``large_at`` one token's
    are 30, which with a rate of 1 to 16 drives every decay there to ~0), A_log
    over [1, 16), B, C, D."""
    r = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(0.1), (B, t, heads))), jnp.float32)
    if large_at is not None:
        dt = dt.at[:, large_at].set(30.0)
    a_log = jnp.asarray(np.log(r.uniform(1.0, 16.0, heads)), jnp.float32)
    return (draw(B, t, heads, p), dt, a_log, draw(B, t, n), draw(B, t, n),
            1.0 + 0.3 * draw(heads))


def ssd_oracle(u, dt, a_log, Bm, Cm, D, state=lambda S: S):
    A = -jnp.exp(a_log)

    def one(u, dt, Bm, Cm):  # a batch row: [T, H, P], [T, H], [T, N]
        def token(S, x):
            u, dt, b, c = x
            S = state(jnp.exp(dt * A)[:, None, None] * S
                      + (dt[:, None] * u)[:, :, None] * b[None, None, :])
            return S, jnp.einsum("hpn,n->hp", S, c) + D[:, None] * u

        zero = jnp.zeros((u.shape[1], u.shape[2], Bm.shape[1]), jnp.float32)
        return jax.lax.scan(token, zero, (u, dt, Bm, Cm))[1]

    with jax.default_matmul_precision("highest"):
        return jax.vmap(one)(u, dt, Bm, Cm)


SSD_NAMES = "u dt A_log B C D".split()


def ssd_compare(args, road):
    assert kda.ssd_road(args[0].shape[3], args[3].shape[2]) == road
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[0].shape), jnp.float32)
    want = ssd_oracle(*args)
    got = jax.jit(lambda *a: kda.chunk_ssd(*a))(*args)
    assert got.shape == want.shape and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * float(jnp.abs(want).max()))
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda.chunk_ssd(*a) * w), argnums=range(6)))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(ssd_oracle(*a) * w), argnums=range(6))(*args)
    for name, a, b in zip(SSD_NAMES, grads, wanted):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-4 * float(jnp.abs(b).max()), err_msg=name)


# (tokens, heads, P, N, the token of the large step): a whole chunk of a whole
# group; no whole number of chunks and an odd head count under a group; two
# groups of heads of 64 over a state of 128 (two heads a tile of lanes, as the
# Granite cell has them) with the large step inside the second chunk; heads
# of 32 (four a tile).
SSD_CASES = {
    "256-group": (256, 8, SP, SN, None),
    "300-odd-heads": (300, 3, SP, SN, 130),
    "600-two-groups-of-64": (600, 10, 64, 128, 400),
    "300-heads-of-32": (300, 5, 32, 32, 7),
}


@pytest.mark.parametrize("case", ["300-odd-heads", "600-two-groups-of-64"])
def test_the_ssd_chunked_form_and_its_vjp_are_the_recurrence(case):
    """The XLA road (``lax.scan`` over ``_ssd_chunk``, the function the
    kernels run): the value and the gradients of u, the steps, A_log, B, C
    and D."""
    t, heads, p, n, large_at = SSD_CASES[case]
    ssd_compare(ssd_inputs(t, heads, p=p, n=n, large_at=large_at), "xla")


@pytest.mark.parametrize("case", sorted(SSD_CASES))
def test_the_ssd_kernels_in_interpret_mode_are_the_recurrence(monkeypatch, case):
    """``_ssd_fwd_kernel`` and, under the ``custom_vjp``, ``_ssd_bwd_kernel``:
    forward and all six cotangents, B's and C's added up over a chunk's
    groups, the rates' and the skips' over a batch row's steps."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    t, heads, p, n, large_at = SSD_CASES[case]
    ssd_compare(ssd_inputs(t, heads, p=p, n=n, large_at=large_at), "pallas")


def test_the_ssd_kernels_share_one_score_matrix_and_carry_a_float32_state(monkeypatch):
    """Forward (with every chunk's first states under a gradient, y alone
    outside one) and backward under names of their own, 8 heads a grid step
    over chunks of 256; B and C go in as one [B, T, N] pair, not one a head;
    the states are float32 [B, chunks, groups, tiles, N, lanes]; the steps go
    in as rows a head, four bytes a head and token."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    heads, t = 16, 512
    args = ssd_inputs(t, heads, p=64, n=128)
    forward = jax.make_jaxpr(kda.chunk_ssd)(*args)
    assert pallas_outputs(forward.jaxpr) == [1]
    both = jax.make_jaxpr(jax.grad(lambda *a: kda.chunk_ssd(*a).sum(), argnums=range(6)))(*args)
    calls = pallas_calls(both.jaxpr, [])
    assert [len(eqn.outvars) for eqn in calls] == [2, 6]
    assert [eqn.params["grid_mapping"].grid for eqn in calls] == [(B, 2, 2)] * 2
    names = [eqn.params["jaxpr"].debug_info.func_name for eqn in calls]
    assert names == ["_ssd_fwd_kernel", "_ssd_bwd_kernel"]
    states = calls[0].outvars[1].aval
    assert (states.shape, states.dtype) == ((B, 2, 2, 4, 128, 128), jnp.float32)
    for eqn in calls:
        shapes = [v.aval.shape for v in eqn.invars]
        assert shapes.count((B, t, 128)) == 2 and (B, t, heads, 128) not in shapes
        assert (B, 2, 8, t) in shapes and (B, t, heads * 64) in shapes


def test_ssd_state_is_float32():
    """The recurrence with its state rounded to bfloat16 after every token
    lies a hundred times further from the float32 recurrence than the chunked
    form does: a program that carried a bfloat16 state would miss the
    comparisons above by as much."""
    args = ssd_inputs(600, 3, large_at=None)
    want = ssd_oracle(*args)
    scale = float(jnp.abs(want).max())
    ours = float(jnp.abs(kda.chunk_ssd(*args) - want).max()) / scale
    rounded = ssd_oracle(*args, state=lambda S: jax.lax.reduce_precision(
        S, exponent_bits=8, mantissa_bits=7))
    theirs = float(jnp.abs(rounded - want).max()) / scale
    assert ours < 2e-5 and theirs > 100 * ours


def test_a_large_step_neither_overflows_nor_loses_what_follows():
    """A step of 30 at a rate of 1 to 16 is a decay of exp(-30) to exp(-480):
    the state is gone there, every exponent is a difference of running sums
    taken before it is exponentiated and never positive, and what is written
    after it is read as the recurrence reads it."""
    args = ssd_inputs(600, 3, large_at=300)
    got = kda.chunk_ssd(*args)
    assert bool(jnp.isfinite(got).all())
    want = ssd_oracle(*args)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4 * float(jnp.abs(want).max()))
    # what lies before the large step reaches nothing after it
    moved = kda.chunk_ssd(args[0].at[:, :300].multiply(2.0), *args[1:])
    np.testing.assert_allclose(moved[:, 301:], got[:, 301:], rtol=1e-5, atol=1e-5)
