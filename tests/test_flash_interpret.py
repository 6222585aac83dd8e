"""Pallas flash kernels vs the XLA oracle, on CPU via interpret mode.

The kernels normally run only on real TPU; interpret mode executes the
same kernel code (including the causal block-skip control flow added
for long-context perf) bit-accurately on CPU, so CI covers fwd+bwd
numerics without a chip.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention_reference, flash_attention


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    # Scoped per-test so interpret mode never leaks into later-collected
    # test modules (which must exercise the compiled path on real TPU).
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


@pytest.mark.parametrize(
    "tq,tk,bq,bk,causal",
    [
        (512, 512, 128, 128, True),   # 4x4 grid: skip logic active
        (512, 512, 128, 256, True),   # uneven q/k blocks across diagonal
        (384, 512, 128, 128, True),   # tq != tk (kv-cache decode chunk)
        (512, 512, 128, 128, False),  # no skipping path
        (500, 500, 128, 128, True),   # padded tails
    ],
)
def test_flash_fwd_bwd_matches_reference(tq, tk, bq, bk, causal):
    B, H, D = 1, 2, 64
    q = _rand((B, H, tq, D), 0)
    k = _rand((B, H, tk, D), 1)
    v = _rand((B, H, tk, D), 2)

    def f_flash(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk
        ).sum()

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=causal).sum()

    o_flash = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    o_ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(o_flash), np.asarray(o_ref), atol=2e-3, rtol=2e-3
    )

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3
        )


def test_flash_gqa_heads():
    B, H, HKV, T, D = 1, 4, 2, 256, 64
    q = _rand((B, H, T, D), 3)
    k = _rand((B, HKV, T, D), 4)
    v = _rand((B, HKV, T, D), 5)
    o_flash = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    o_ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(o_flash), np.asarray(o_ref), atol=2e-3, rtol=2e-3
    )


def test_causal_rejects_more_queries_than_keys():
    q = _rand((1, 2, 256, 64), 6)
    k = _rand((1, 2, 128, 64), 7)
    v = _rand((1, 2, 128, 64), 8)
    with pytest.raises(ValueError, match="Tq <= Tk"):
        flash_attention(q, k, v, causal=True)


# ------------------------------------------------ a tile's position (PR 46)
# Each grid step of the three causal kernels does what its tile's position
# needs: no mask on an interior tile, the masked body on an edge tile, no
# arithmetic and no copy on a dead one. Which is which follows from the
# shapes alone.

# (tq, tk, block_q, block_k, causal)
TILINGS = [
    (512, 512, 128, 128, True),    # square: 6 interior, 4 edge, 6 dead
    (384, 512, 128, 128, True),    # tq < tk: the ends aligned
    (256, 512, 256, 128, True),    # tq < tk, block_q != block_k, nothing dead
    (512, 512, 128, 256, True),    # keys the larger block
    (512, 512, 256, 128, True),    # rows the larger block
    (500, 500, 128, 128, True),    # tq and tk no multiples of the block
    (300, 428, 128, 128, True),    # both, and tq < tk
    (128, 128, 128, 128, True),    # a single tile, an edge one
    (256, 384, 128, 128, False),   # non-causal: every tile interior
    (300, 300, 128, 128, False),   # non-causal, padded keys: the last an edge
]
# the benchmark's cells (ISSUE 46): (interior, edge, dead) a head
CELLS = [
    ((16384, 16384, 1024, 1024, True), (120, 16, 120)),  # long16k, Laguna's full layers
    ((4096, 4096, 1024, 1024, True), (6, 4, 6)),    # pretrain-4k, -mtp-4k, dropless-4k
    ((2048, 2048, 1024, 1024, True), (1, 2, 1)),    # short2k
    ((512, 512, 1024, 1024, True), (0, 1, 0)),      # sft512
    ((2048, 2048, 512, 512, True), (6, 4, 6)),      # the ring's diagonal block
    ((2048, 2048, 512, 512, False), (16, 0, 0)),    # each rotated one
]


def _brute_force(tq, tk, bq, bk, causal):
    """Every tile's class from the [tq, tk] mask itself."""
    from ray_tpu.ops.attention import _visible

    seen = np.asarray(_visible(tq, tk, None)) if causal else np.ones((tq, tk), bool)
    bq, bk = min(bq, tq), min(bk, tk)
    classes = {}
    for i in range(-(-tq // bq)):
        for j in range(-(-tk // bk)):
            tile = seen[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            whole = (j + 1) * bk <= tk  # no padded key
            classes[i, j] = ("interior" if whole and tile.all()
                             else "edge" if tile.any() else "dead")
    return classes


@pytest.mark.parametrize("tq,tk,bq,bk,causal", TILINGS)
def test_causal_tiles_match_a_brute_force_mask(tq, tk, bq, bk, causal):
    from ray_tpu.ops import attention

    classes = _brute_force(tq, tk, bq, bk, causal)
    count = lambda c: sum(v == c for v in classes.values())  # noqa: E731
    assert attention.causal_tiles(tq, tk, bq, bk, causal) == (
        count("interior"), count("edge"), count("dead"))
    # the index maps' clamps name the row's last and the column's first
    # live block: a dead step's copy is the one already there
    tile = dict(causal=causal, block_q=min(bq, tq), block_k=min(bk, tk),
                seq_q=tq, seq_k=tk)
    rows = sorted({i for i, _ in classes})
    cols = sorted({j for _, j in classes})
    for i in rows:
        live = [j for j in cols if classes[i, j] != "dead"]
        assert int(attention._last_live_key(jnp.int32(i), **tile)) == live[-1]
        assert live == cols[:len(live)]  # a prefix: the clamp skips no live tile
    for j in cols:
        live = [i for i in rows if classes[i, j] != "dead"]
        assert int(attention._first_live_row(jnp.int32(j), **tile)) == live[0]
        assert live == rows[-len(live):]


@pytest.mark.parametrize("shape,want", CELLS)
def test_causal_tiles_of_the_benchmarks_cells(shape, want):
    from ray_tpu.ops.attention import causal_tiles

    assert causal_tiles(*shape) == want


def _operands(tq, tk, d, d_v, dtype, bh=2):
    make = lambda n, *shape: _rand(shape, n).astype(dtype)  # noqa: E731
    return (make(10, bh, tq, d), make(11, bh, tk, d), make(12, bh, tk, d_v),
            make(13, bh, tq, d_v))


def _masked_everywhere(monkeypatch):
    """The parent's kernels: every live tile through the masked body."""
    from ray_tpu.ops import attention

    real = attention._tile_class

    def no_interior(i, j, **tile):
        live, interior = real(i, j, **tile)
        return live, interior & False

    monkeypatch.setattr(attention, "_tile_class", no_interior)


HEAD_DIMS = [(128, 128), (192, 128), (64, 64)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("d,d_v", HEAD_DIMS)
@pytest.mark.parametrize("tq,tk,bq,bk,causal", TILINGS)
def test_forward_is_bit_for_bit_the_masked_everywhere_result(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v, dtype):
    """Masking an all-true tile is the identity. (The scale is a power of
    two: XLA's CPU simplifier, which compiles the interpreter's branches,
    moves another scale into the dot's operand in one body and not the
    other, 1e-7 apart; Mosaic does not, and the chip's agreement at
    d ** -0.5 is in PERF.md §6, PR 46.)"""
    from ray_tpu.ops.attention import _flash_fwd_pallas

    q, k, v, _ = _operands(tq, tk, d, d_v, dtype)
    static = dict(causal=causal, sm_scale=0.125, block_q=bq, block_k=bk)
    o, lse = _flash_fwd_pallas(q, k, v, **static)
    with monkeypatch.context() as m:
        _masked_everywhere(m)
        o_masked, lse_masked = _flash_fwd_pallas(q, k, v, **static)
    assert bool((o == o_masked).all()) and bool((lse == lse_masked).all())
    assert o.shape == (2, tq, d_v) and bool(jnp.isfinite(lse).all())


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5), (jnp.bfloat16, 0.03)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d,d_v", HEAD_DIMS)
@pytest.mark.parametrize("tq,tk,bq,bk,causal", TILINGS)
def test_gradients_match_the_xla_block_backward(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v, dtype, atol):
    """(dq, dk, dv) of the kernels (dK/dV: two bodies, p and ds to the MXU
    in the inputs' dtype; dQ: the masked body on every live tile) against
    ``_block_bwd``'s XLA mathematics on the same (o, lse)."""
    from ray_tpu.ops.attention import _block_bwd, _block_fwd

    q, k, v, do = _operands(tq, tk, d, d_v, dtype)
    static = (causal, d ** -0.5, bq, bk)
    o, lse = _block_fwd(q, k, v, *static)
    got = _block_bwd(q, k, v, o, lse, do, *static)
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")  # below: the XLA road
    want = _block_bwd(q, k, v, o, lse, do, *static)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=atol, rtol=atol, err_msg=name)


@pytest.mark.parametrize("d,d_v", HEAD_DIMS)
@pytest.mark.parametrize("tq,tk,bq,bk,causal", TILINGS)
def test_float32_gradients_are_bit_for_bit_the_masked_everywhere_result(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v):
    """In float32 "the inputs' dtype" casts nothing and an interior tile's
    mask is the identity, so the backward is the parent's to the bit. (A
    power-of-two scale, as in the forward's case above.)"""
    from ray_tpu.ops.attention import _flash_bwd_pallas, _flash_fwd_pallas

    q, k, v, do = _operands(tq, tk, d, d_v, jnp.float32)
    static = dict(causal=causal, sm_scale=0.125, block_q=bq, block_k=bk)
    o, lse = _flash_fwd_pallas(q, k, v, **static)
    got = _flash_bwd_pallas(q, k, v, o, lse, do, **static)
    with monkeypatch.context() as m:
        _masked_everywhere(m)
        want = _flash_bwd_pallas(q, k, v, o, lse, do, **static)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert bool((a == b).all()), name
