"""What the interpreted flash kernels' test files share: the interpreter's
switch, the tilings and head widths every product runs over, the operands, the
parent's masked-everywhere kernels, and the three products' bodies (the
forward bit for bit, the gradients against XLA's, the float32 gradients bit
for bit), which ``tests/test_flash_tiles_*.py`` and
``tests/test_flash_tile_gradients_*.py`` each run at one pair of head widths.
A plain module: a piece imports what it reads by name.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    # Scoped per-test so interpret mode never leaks into later-collected
    # test modules (which must exercise the compiled path on real TPU).
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


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
    (1024, 1024, 1024, 1024, True),  # one tile of the cells' size: dv before ds
]


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


@functools.lru_cache(maxsize=None)
def float32_forward(tq, tk, bq, bk, causal, d, d_v):
    """(o, lse) of ``_operands`` in float32 through ``_forward_call`` at the
    power-of-two scale, the kernels as they are: one value for the forward's
    case that holds it to the masked-everywhere result and the gradients' case
    that starts from it."""
    from ray_tpu.ops.attention import _causal_mask, _forward_call

    q, k, v, _ = _operands(tq, tk, d, d_v, jnp.float32)
    return _forward_call(_causal_mask(q, k, v, causal, bq, bk), q, k, v, 0.125)


def forward_is_bit_for_bit_the_masked_everywhere_result(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v, dtype):
    """Masking an all-true tile is the identity. (The scale is a power of
    two: XLA's CPU simplifier, which compiles the interpreter's branches,
    moves another scale into the dot's operand in one body and not the
    other, 1e-7 apart; Mosaic does not, and the chip's agreement at
    d ** -0.5 is in PERF.md §6, PR 46.)"""
    from ray_tpu.ops.attention import _causal_mask, _forward_call

    q, k, v, _ = _operands(tq, tk, d, d_v, dtype)
    forward = lambda: _forward_call(  # noqa: E731
        _causal_mask(q, k, v, causal, bq, bk), q, k, v, 0.125)
    o, lse = (float32_forward(tq, tk, bq, bk, causal, d, d_v)
              if dtype == jnp.float32 else forward())
    with monkeypatch.context() as m:
        _masked_everywhere(m)
        o_masked, lse_masked = forward()
    assert bool((o == o_masked).all()) and bool((lse == lse_masked).all())
    assert o.shape == (2, tq, d_v) and bool(jnp.isfinite(lse).all())


def gradients_match_the_xla_block_backward(
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


def float32_gradients_are_bit_for_bit_the_masked_everywhere_result(
        monkeypatch, tq, tk, bq, bk, causal, d, d_v):
    """In float32 "the inputs' dtype" casts nothing and an interior tile's
    mask is the identity, so the backward is the parent's to the bit. (A
    power-of-two scale, as in the forward's case above.)"""
    from ray_tpu.ops.attention import _backward_call, _causal_mask

    q, k, v, do = _operands(tq, tk, d, d_v, jnp.float32)
    mask = lambda: _causal_mask(q, k, v, causal, bq, bk)  # noqa: E731
    o, lse = float32_forward(tq, tk, bq, bk, causal, d, d_v)
    got = _backward_call(mask(), q, k, v, o, lse, do, 0.125)
    with monkeypatch.context() as m:
        _masked_everywhere(m)
        want = _backward_call(mask(), q, k, v, o, lse, do, 0.125)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert bool((a == b).all()), name
