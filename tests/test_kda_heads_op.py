"""The gated delta rule of ``ray_tpu/ops/kda.py`` on the CPU: how heads share a
grid step and what surrounds the scan: a head through the pair path is the
head alone, rows of zeros, the normalisations before and after a chunk, the
weight's gradient over batch rows and grid steps, the stacked heads' masks,
the grid's steps, and no states written outside a gradient
(``tests/test_kda_op.py`` says what the rule is held to and names the family's
files; ``tests/kda_recurrence.py`` has the recurrence and the comparison).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

from kda_cases import (
    B, DK, DV, NAMES, RMS_EPS, SCALE, gated_norm, pallas_calls, pallas_outputs,
)
from kda_recurrence import chunk_kda, compare, fresh, inputs


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
