"""The fixed decay (``chunk_lightning``) of ``ray_tpu/ops/kda.py`` on the CPU:
Lightning attention's road against its token-by-token recurrence, the chunked
form and the Pallas kernels interpreted.

One of the six kernel families of ``ray_tpu/ops/kda.py``, a test file each
(ROADMAP C15's seams: the module's split moves one test file with each
family).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

from kda_cases import B, RMS_EPS, gated_norm, pallas_calls, pallas_outputs


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
