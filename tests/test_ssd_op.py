"""The step-scaled scalar decay (``chunk_ssd``) of ``ray_tpu/ops/kda.py`` on the
CPU: the state-space duality's road against its token-by-token recurrence, the
chunked form and the Pallas kernels interpreted.

One of the six kernel families of ``ray_tpu/ops/kda.py``, a test file each
(ROADMAP C15's seams: the module's split moves one test file with each
family).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

from kda_cases import B, pallas_calls, pallas_outputs


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
