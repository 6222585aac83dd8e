"""Kimi-Linear's architecture through the program's models, on the CPU: a rank's
share is the whole layer's bounds at the cost of its pairs, and is refused
outside the ``gmm`` dispatch (``tests/test_kimi_linear_shares.py`` has the
shares' sum; ``tests/test_kimi_linear_model.py`` the model against its
reference).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kimi_linear_cases import (  # noqa: F401 - fixtures
    expert_layer, interpret, whole_layer,
)


# ------------------------------- the share at the cost of the pairs here


def whole_bound_ffn(x2, gates, w_gate, w_up, w_down, pair_of_slot, tile_group,
                    tiles_used, slot_of_pair=None):
    """``_held_ffn``'s result the plain way, as the layer computed it before
    its cost followed the pairs that are here: every row move a gather over
    the whole bound, every tile of the layout computed. A pair held
    elsewhere reads the layout's last slot, which is padding: a zero row."""
    from ray_tpu.models.mixtral import _rows_to_slots, _slots_to_rows
    from ray_tpu.ops.gmm import gmm

    (S, K), m_pad = gates.shape, pair_of_slot.shape[0]
    slot_of_pair = jnp.full((S * K + 1,), m_pad - 1, jnp.int32).at[
        pair_of_slot].set(jnp.arange(m_pad, dtype=jnp.int32))[:-1].reshape(S, K)
    lhs = _rows_to_slots(x2, slot_of_pair, pair_of_slot)
    h, u = gmm(lhs, w_gate, tile_group), gmm(lhs, w_up, tile_group)
    eo = gmm(jax.nn.silu(h) * u, w_down, tile_group)
    return _slots_to_rows(eo, gates, slot_of_pair, pair_of_slot)


def routing(case, cfg, params, held):
    """(parameters, tokens) that route as ``case`` says to a rank holding
    experts ``held`` of sixteen, four a token."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 192, 32))
    bias = np.zeros(16, np.float32)
    if case == "none-here":
        bias[:4] = 10.0
    elif case == "every-pair-here":
        bias[held[0]:held[1]] = 10.0
    elif case == "zipf":
        # A frequent token sends all its copies to the same four experts.
        ids = np.minimum(rng.zipf(1.3, size=(2, 192)), 40) - 1
        x = rng.normal(size=(40, 32))[ids]
    return {**params, "router_bias": jnp.asarray(bias)}, jnp.asarray(x, jnp.float32)


@pytest.mark.parametrize("window", [1, 3])
@pytest.mark.parametrize(
    "case", ["none-here", "even-share", "zipf", "every-pair-here"])
def test_the_share_is_the_whole_bounds_at_the_cost_of_its_pairs(
        whole_layer, monkeypatch, case, window):
    """The held layer, output and every gradient (tokens, gates, the three
    expert matrices, the router), against the same layer with its row moves
    over the whole bound, at four routings and two window sizes; every row
    past ``tiles_used`` holds NaN before its consumer runs, so a reader
    that strays past the used tiles fails."""
    from ray_tpu.models import mixtral
    from ray_tpu.ops import gmm as G

    cfg, params, _ = whole_layer
    held = (4, 8)
    params, x = routing(case, cfg, params, held)
    layer, _ = expert_layer(held)
    mine = {**params, **{k: params[k][held[0]:held[1]]
                         for k in ("w_gate", "w_up", "w_down")}}

    calls = []
    held_ffn, gmm_rows = mixtral._held_ffn, G._gmm_pallas

    def poisoned(lhs, rhs, tile_group, block_m, transpose_rhs=False,
                 tiles_used=None):
        out = gmm_rows(lhs, rhs, tile_group, block_m, transpose_rhs, tiles_used)
        if tiles_used is None:
            return out
        past = jnp.arange(out.shape[0])[:, None] >= tiles_used[0] * block_m
        return jnp.where(past, jnp.nan, out)

    def watched(*args):
        calls.append(args)
        return held_ffn(*args)

    monkeypatch.setattr(mixtral, "_WINDOW", window)
    monkeypatch.setattr(mixtral, "_held_ffn", watched)
    monkeypatch.setattr(G, "_gmm_pallas", poisoned)
    monkeypatch.setattr(
        G, "unwritten", lambda shape, dtype, after: jnp.full(shape, jnp.nan, dtype))
    w = jnp.asarray(np.random.default_rng(6).normal(size=x.shape), jnp.float32)

    def loss(p, x):
        return (layer.apply({"params": p}, x) * w).sum()

    layer.apply({"params": mine}, x)
    args = calls[0]
    out, grads = jax.value_and_grad(loss, (0, 1))(mine, x)
    got, pull = jax.vjp(lambda *a: held_ffn(*a, *args[5:]), *args[:5])
    got = (got, *pull(w.reshape(got.shape)))

    # the plain form of the same layer, and of the same call
    monkeypatch.setattr(mixtral, "_held_ffn", whole_bound_ffn)
    monkeypatch.setattr(G, "_gmm_pallas", gmm_rows)
    want_out, want_grads = jax.value_and_grad(loss, (0, 1))(mine, x)
    want, pull = jax.vjp(lambda *a: whole_bound_ffn(*a, *args[5:]), *args[:5])
    want = (want, *pull(w.reshape(want.shape)))

    pair_of_slot, tile_group, tiles_used = (np.asarray(a) for a in args[5:8])
    pairs, tiles = x.shape[0] * x.shape[1] * 4, int(tiles_used[0])
    here = int((pair_of_slot < pairs).sum())
    assert not (pair_of_slot[tiles * 128:] < pairs).any()
    assert pair_of_slot.shape[0] % (window * 128) == 0
    assert {"none-here": here == 0, "every-pair-here": here == pairs}.get(
        case, 0 < here < pairs)
    assert 4 <= tiles <= -(-here // 128) + 4  # an expert pads by a tile at most

    assert np.isfinite(float(out))
    np.testing.assert_allclose(out, want_out, rtol=1e-5)
    names = ("out", "d_x", "d_gates", "d_w_gate", "d_w_up", "d_w_down")
    for name, a, b in zip(names, got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    flat = lambda g: jax.tree_util.tree_leaves_with_path(g)  # noqa: E731
    for (path, a), (_, b) in zip(flat(grads), flat(want_grads)):
        name = jax.tree_util.keystr(path)
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    moved = {jax.tree_util.keystr(p) for p, a in flat(grads[0]) if np.asarray(a).any()}
    assert "['shared']['up_proj']['kernel']" in moved
    for name in ("['router']['kernel']", "['w_gate']", "['w_up']", "['w_down']"):
        assert (name in moved) == (here > 0), name


def test_a_share_is_refused_outside_the_gmm_dispatch():
    layer, _ = expert_layer((0, 4), moe_dispatch="capacity")
    with pytest.raises(ValueError, match="experts_held"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 32)))
