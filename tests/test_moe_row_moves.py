"""The ``gmm`` dispatch's row moves: they are the scatters they replace, the
layers off the gather road lower to what they did, and no row is scatter-added
on it (``tests/test_moe_models.py`` has the layer and its dispatch branches).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _routing(case, rng):
    """(experts, expert of each (token, k) pair [S, K], dtype) of a case."""
    if case == "top1":
        return 4, rng.randint(0, 4, (24, 1)), jnp.float32
    if case == "one_expert_takes_every_token":
        return 4, np.full((24, 2), 2), jnp.float32
    # 8 of 64, the experts from 40 on chosen by no token; the same in
    # bfloat16, held to a float32 oracle.
    picks = np.stack([rng.permutation(40)[:8] for _ in range(32)])
    return 64, picks, jnp.bfloat16 if case == "bfloat16" else jnp.float32


@pytest.mark.parametrize("case", [
    "top1", "top8_of_64_with_experts_empty", "one_expert_takes_every_token",
    "bfloat16",
])
def test_gmm_row_moves_are_the_scatters_they_replace(case):
    """`_rows_to_slots` and `_slots_to_rows`, forward and every gradient,
    against the plain gather and `.at[].add` over the sorted pairs that
    the "gmm" branch moved its rows by (the "ragged" branch still does):
    the index maps there are built as that branch built them."""
    from ray_tpu.models.mixtral import (
        _pair_slots, _rows_to_slots, _slots_to_rows,
    )
    from ray_tpu.ops.gmm import aligned_group_layout

    rng = np.random.RandomState(7)
    E, picks, dtype = _routing(case, rng)
    (S, K), N, D = picks.shape, picks.size, 16
    order, dst, _, m_pad = aligned_group_layout(
        jnp.asarray(picks.reshape(N), jnp.int32), E, block_m=8
    )
    slot_of_pair, pair_of_slot = _pair_slots(order, dst, m_pad, K)

    tok_sorted = (jnp.arange(N, dtype=jnp.int32) // K)[order]
    inv = jnp.full((m_pad,), N, jnp.int32).at[dst].set(jnp.arange(N))
    src_tok = jnp.concatenate([tok_sorted, jnp.full((1,), S, jnp.int32)])[inv]
    padding = np.asarray(inv) == N
    assert padding.sum() == m_pad - N > 0

    def dispatch_oracle(x2):
        return jnp.concatenate([x2, jnp.zeros((1, D), x2.dtype)])[src_tok]

    def combine_oracle(eo, gates):
        pair_out = eo[dst] * gates.reshape(N)[order][:, None]
        return jnp.zeros((S, D), eo.dtype).at[tok_sorted].add(pair_out)

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), dtype)

    # Padding slots hold noise, in the expert outputs and the cotangents:
    # neither pass may read them.
    x2, eo, d_lhs, d_out = draw(S, D), draw(m_pad, D), draw(m_pad, D), draw(S, D)
    gates = jnp.asarray(rng.rand(S, K), dtype)

    def f32(*arrays):
        return [a.astype(jnp.float32) for a in arrays]

    lhs, pull_x = jax.vjp(lambda x: _rows_to_slots(x, slot_of_pair, pair_of_slot), x2)
    out, pull_eo = jax.vjp(
        lambda e, g: _slots_to_rows(e, g, slot_of_pair, pair_of_slot), eo, gates
    )
    got = [lhs, *pull_x(d_lhs), out, *pull_eo(d_out)]
    assert [a.dtype for a in got] == [dtype] * 5
    want_lhs, pull_x = jax.vjp(dispatch_oracle, *f32(x2))
    want_out, pull_eo = jax.vjp(combine_oracle, *f32(eo, gates))
    want = [want_lhs, *pull_x(*f32(d_lhs)), want_out, *pull_eo(*f32(d_out))]

    # A sum of K or D products rounds once to the dtype: half a unit in
    # bfloat16's eighth bit, and float32's own noise.
    rtol = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6
    for name, a, b in zip(
        ("lhs", "d_x", "out", "d_eo", "d_gates"), f32(*got), want
    ):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-6, err_msg=name)
    d_eo = np.asarray(got[3].astype(jnp.float32))
    assert (d_eo[padding] == 0).all() and (d_eo[~padding] != 0).any()
    assert (np.asarray(lhs.astype(jnp.float32))[padding] == 0).all()


# ------------------------------------------- a held share's two roads
#
# One expert layer, forward and backward, at a router of 16 experts, top-4,
# over 256 tokens of 32: the whole layer, and a rank's quarter by either
# road (``held_rows``).


def _layer_step(**over):
    """(the layer's loss and gradients as a function, its arguments' shapes)."""
    from ray_tpu.models.mixtral import MixtralConfig, MoELayer

    layer = MoELayer(MixtralConfig(
        hidden_size=32, intermediate_size=64, num_experts=16,
        num_experts_per_tok=4, num_shared_experts=1, router_score="sigmoid",
        moe_dispatch="gmm", dtype=jnp.bfloat16, **over,
    ))
    x = jax.ShapeDtypeStruct((1, 256, 32), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)

    def step(params, x):
        return layer.apply(params, x).astype(jnp.float32).sum()

    return jax.value_and_grad(step, (0, 1)), (params, x)


# sha1 of the lowered text (without the counters JAX gives its private
# functions), read by this code at the parent of the PR that gave the
# "gather" road ``_held_ffn``'s slot-side loops (commit 6ab58fe): a layer that
# walks (Kimi-Linear's and sarvam's) and a layer that holds every expert
# (OLMoE's) lower to the text they lowered to, so their steps cannot have
# moved with it.
LAYER_TEXTS_BEFORE = {
    "walk": (dict(experts_held=(4, 8), held_rows="walk"), "c3f6f0628800c651"),
    "whole": ({}, "119ae5fb0bbf48d7"),
}


@pytest.mark.parametrize("name", sorted(LAYER_TEXTS_BEFORE))
def test_the_layers_off_the_gather_road_lower_to_what_they_did(name, monkeypatch):
    import hashlib
    import re

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    over, before = LAYER_TEXTS_BEFORE[name]
    step, shapes = _layer_step(**over)
    text = jax.jit(step).lower(*shapes).as_text()
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
    assert hashlib.sha1(text.encode()).hexdigest()[:16] == before


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner)


@pytest.mark.parametrize("held_rows, row_adds", [("gather", 0), ("walk", 2)])
def test_no_row_is_scatter_added_on_the_gather_road(held_rows, row_adds, monkeypatch):
    """A scatter-add into [., 32] arrays (rows of tokens or of slots): the
    walk's two, forward into the result and backward into x's gradient, and
    none where rows are gathered; there the traced step holds two calls of
    the kernel over tokens (``ops.gmm.pairs_summed``), forward and backward,
    and no gather of every pair's row, [256, 4, 32], on either road."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    step, shapes = _layer_step(experts_held=(4, 8), held_rows=held_rows)
    eqns = list(_equations(jax.make_jaxpr(step)(*shapes).jaxpr))
    adds = [e for e in eqns if e.primitive.name == "scatter-add"
            and e.outvars[0].aval.shape[-1:] == (32,)]
    assert len(adds) == row_adds
    whole = [e for e in eqns if e.primitive.name == "gather"
             and e.outvars[0].aval.shape == (256, 4, 32)]
    assert not whole
    over_tokens = [e for e in eqns if e.primitive.name == "pallas_call"
                   and e.params["jaxpr"].debug_info.func_name == "_pairs_summed_kernel"]
    assert len(over_tokens) == (2 if held_rows == "gather" else 0)
    for call in over_tokens:
        assert call.outvars[0].aval.shape == (256, 32)
