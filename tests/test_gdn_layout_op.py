"""The scalar decay (``chunk_gdn``) of ``ray_tpu/ops/kda.py`` on the CPU, where
its operands lie and what it shares: v, the gate and o tokens first, the KDA
road fed the decay broadcast over channels, one decay a head and token, the
convolution's heads-first output as the kernels read it, and a strong decay
(``tests/test_gdn_op.py`` has the road against its recurrence;
``tests/gdn_cases.py`` what the two share).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

import gdn_cases
from gdn_cases import GDK, GDV, chunk_gdn, gdn_compare, gdn_inputs, gdn_oracle
from kda_cases import B, RMS_EPS, conv_reference, pallas_calls, pallas_outputs


def test_v_the_gate_and_o_stay_tokens_first_where_a_steps_heads_are_whole_vregs(monkeypatch):
    """Two value heads of 64 lanes are one vreg side by side: v and the gate
    go into both kernels and o and their cotangents come out of them as [B,
    T, H * dv], as the convolution and the matmuls around the scan have
    them, and a grid step takes its two heads' lanes apart and puts them
    together in VMEM. The same recurrence, forward and all seven cotangents;
    at 48 lanes a head (every other case here) the three lie heads first, [B,
    H, T, dv], transposed by XLA."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(gdn_cases, "GDV", 64)  # where the inputs and the road read it
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
