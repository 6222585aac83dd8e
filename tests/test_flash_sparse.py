"""Block-sparse top-k attention of ``ray_tpu/ops/attention.py`` on the CPU:
``flash_attention(..., blocks=...)`` through the three sparse kernels in
interpret mode and through the XLA road, against a gather-and-softmax a row
(each row's chosen keys gathered, one softmax over them), forward and the
gradients of q, k and v; rows whose sets differ inside one tile, groups whose
sets differ, a row with fewer than ``topk`` visible blocks, a tile no row
chose, a length that is no whole number of tiles; K and V at their own heads;
and ``select_blocks``: the forced blocks, the count, ties to the lower index,
no gradient through the choice."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as A

B, H, G, D, BLOCK = 1, 4, 2, 32, 16
SEL = dict(block_size=BLOCK, topk=4, window=32, init_blocks=1, kernel_size=8,
           kernel_stride=4)


def pallas_calls(jaxpr, found):
    """Every pallas_call equation in a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            pallas_calls(sub, found)
    return found


def inputs(t, seed=0, heads=H, groups=G):
    r = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa: E731
    return draw(B, heads, t, D), draw(B, groups, t, D), draw(B, groups, t, D)


def gather_and_softmax(q, k, v, blocks, block=BLOCK):
    """Row by row: the keys up to the row's own in its chosen blocks."""
    q, k, v, blocks = (np.asarray(x, np.float64) for x in (q, k, v, blocks))
    _, heads, t, d = q.shape
    group = heads // k.shape[1]
    o = np.zeros((1, heads, t, v.shape[-1]))
    for h in range(heads):
        g = h // group
        for i in range(t):
            keys = [j for j in range(i + 1) if blocks[0, g, i, j // block]]
            s = q[0, h, i] @ k[0, g, keys].T / np.sqrt(d)
            p = np.exp(s - s.max())
            o[0, h, i] = (p / p.sum()) @ v[0, g, keys]
    return o


def gather_loss(q, k, v, blocks, w, block=BLOCK):
    """The same in jax.numpy, for its gradients: a masked softmax per row is
    the gather's softmax."""
    t = q.shape[2]
    seen = jnp.repeat(blocks, block, axis=-1)[..., :t] & (
        jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])
    group = q.shape[1] // k.shape[1]
    kk, vv = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhtd,bhsd->bhts", q, kk) / jnp.sqrt(q.shape[-1])
    s = jnp.where(jnp.repeat(seen, group, axis=1), s, -jnp.inf)
    return (jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1), vv) * w).sum()


def compare(t, blocks, seed=0, heads=H, groups=G):
    q, k, v = inputs(t, seed, heads, groups)
    w = jnp.cos(jnp.arange(t * D, dtype=jnp.float32).reshape(t, D) * 0.37)
    run = lambda q, k, v: A.flash_attention(q, k, v, blocks=blocks, block_size=BLOCK)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = jax.jit(run)(q, k, v)
        np.testing.assert_allclose(got, gather_and_softmax(q, k, v, blocks), atol=2e-5)
        grads = jax.jit(jax.grad(lambda *a: (run(*a) * w).sum(), argnums=(0, 1, 2)))(q, k, v)
        wanted = jax.grad(gather_loss, argnums=(0, 1, 2))(q, k, v, blocks, w)
    for name, a, b in zip("qkv", grads, wanted):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()), err_msg=name)


@pytest.fixture(params=["xla", "kernels"])
def road(request, monkeypatch):
    if request.param == "kernels":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    return request.param


@pytest.mark.parametrize("t", [256, 300], ids=["whole-tiles", "padded"])
def test_the_sparse_roads_are_a_gather_and_softmax_a_row(road, t):
    """The selection's own sets: rows of one tile choose differently, the two
    groups choose differently, the first rows see fewer than topk blocks."""
    q, k, _ = inputs(t)
    blocks = A.select_blocks(q, k, **SEL, rows=128)
    chosen = np.asarray(blocks)[0]
    assert (chosen[0] != chosen[1]).any()  # group from group
    assert (chosen[0, 130] != chosen[0, 200]).any()  # row from row inside a tile
    assert chosen[0, 20].sum() == 2 < SEL["topk"]  # fewer visible than topk
    compare(t, blocks)


def test_rows_of_one_tile_with_disjoint_sets_and_a_tile_no_row_chose(road):
    """Hand-made sets: even rows take block 0 and their own, odd rows the
    block before their own and their own; blocks 2 to 5 are chosen by no row
    past them, so a whole key tile of a row tile is skipped."""
    t = 256
    own = np.arange(t) // BLOCK
    blocks = np.zeros((B, G, t, t // BLOCK), bool)
    rows = np.arange(t)
    blocks[0, :, rows, own] = True
    blocks[0, 0, rows[::2], 0] = True
    blocks[0, 0, rows[1::2], np.maximum(own[1::2] - 1, 0)] = True
    blocks[0, 1, rows, np.maximum(own - 2, 0)] = True
    compare(t, jnp.asarray(blocks))


def test_six_heads_over_three_groups_keep_k_and_v_at_three(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    t = 256
    q, k, v = inputs(t, 2, heads=6, groups=3)
    blocks = A.select_blocks(q, k, **SEL)
    compare(t, blocks, 2, heads=6, groups=3)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: A.flash_attention(*a, blocks=blocks, block_size=BLOCK).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    calls = pallas_calls(jaxpr.jaxpr, [])
    assert [eqn.params["jaxpr"].debug_info.func_name for eqn in calls] == [
        "_sparse_fwd_kernel", "_bwd_dkv_sparse_kernel", "_bwd_dq_sparse_kernel"]
    for eqn in calls:
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
        # q's six heads beside K and V at their own three; no [T, T] operand
        assert (6, t, D) in shapes and (3, t, D) in shapes
        assert not any(s[-2:] == (t, t) for s in shapes)
    # dK and dV leave their kernel at three heads, summed over a group inside
    assert [v.aval.shape for v in calls[1].outvars] == [(3, t, D)] * 2
    assert f",{t},{t}]" not in str(jaxpr)


def test_select_blocks_forces_counts_and_breaks_ties_downwards():
    t = 256
    q, k, _ = inputs(t, 3)
    chosen = np.asarray(A.select_blocks(q, k, **SEL, rows=64))[0]
    own = np.arange(t) // BLOCK
    np.testing.assert_array_equal(
        chosen.sum(-1), np.minimum(own + 1, SEL["topk"])[None].repeat(G, 0))
    rows = np.arange(t)
    assert chosen[:, rows, own].all() and chosen[:, :, 0].all()
    assert chosen[:, rows[BLOCK:], own[BLOCK:] - 1].all()  # window 32: two blocks
    # keys all alike: every compressed key scores alike, every block ties, and
    # the one free choice of a row is the lowest block that is not forced
    flat = np.asarray(A.select_blocks(q, jnp.ones_like(k), **SEL))[0]
    for i in (100, 255):
        assert list(np.flatnonzero(flat[0, i])) == [0, 1, own[i] - 1, own[i]]


def test_no_gradient_passes_through_the_choice():
    t = 128
    q, k, v = inputs(t, 4)
    score = lambda q, k: A.select_blocks(q, k, **SEL).astype(jnp.float32).sum()  # noqa: E731
    gq, gk = jax.grad(score, argnums=(0, 1))(q, k)
    assert not np.asarray(gq).any() and not np.asarray(gk).any()

    def through(q, k, v):
        return A.flash_attention(
            q, k, v, blocks=A.select_blocks(q, k, **SEL), block_size=BLOCK).sum()

    blocks = A.select_blocks(q, k, **SEL)
    fixed = lambda q, k, v: A.flash_attention(q, k, v, blocks=blocks, block_size=BLOCK).sum()  # noqa: E731
    for a, b in zip(jax.grad(through, argnums=(0, 1, 2))(q, k, v),
                    jax.grad(fixed, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("change,message", [
    (dict(causal=False), "causal"), (dict(window=64), "window"),
    (dict(block_size=None), "block_size")])
def test_blocks_are_causal_self_attentions_alone(change, message):
    q, k, v = inputs(128)
    blocks = A.select_blocks(q, k, **SEL)
    with pytest.raises(ValueError, match=message):
        A.flash_attention(q, k, v, **{"blocks": blocks, "block_size": BLOCK, **change})
