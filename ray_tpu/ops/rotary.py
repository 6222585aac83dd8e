"""Rotary embeddings as one pass over q or k as the kernels take them.

``rotate`` is ``models/llama.py`` ``_rope`` with one read of x [B, H, T, D]
in its own dtype, float32 products and sums in registers, one rounding and
one write. The arithmetic is ``_rope``'s (x1 cos - x2 sin, x2 cos + x1 sin),
with the two halves of a head met by a lane rotation and not by a split:
channel i meets channel i + half as ``roll(x) * table``, against float32
tables [B, T, D] that carry the sine's sign, the amplitude, and 1 / 0 on the
lanes a partial rotation leaves alone. A whole head is one rotation by D / 2
(either way round a head is the same move); a part of a head is two, by half
and by D - half, each against a sine table that is zero where the other's is
not.

Heads first in and out, because that is how q and k already lie: XLA writes
a projection's [B, T, H, D] heads first straight out of its matmul where a
transposition follows, and ``flash_attention`` and the scan kernels inside
``chunk_lightning`` take it so. A kernel that read the projection's [B, T,
H * D] view instead was handed a transposing copy of the whole array before
every call (AOT compiles for v5e, PERF.md §6, PR 57): the view is other
tiles than the array.

The tables come from ``_rope``'s own XLA expression (positions x freqs in
float32, cos, sin, times the amplitude), made once a call inside the jitted
entry and read once a block of rows: the heads are the grid's innermost
axis, so the tables' block stands while a row block's heads pass.

A rotation's transpose is the rotation back: the cotangent's pass is the
same kernel against the tables with the sines' sign turned. It keeps
positions and freqs, nothing of x.

Which road a call takes follows from what ``rotate`` can observe
(``rotary_road``): the kernel on a TPU (or under the interpreter) where a
head is whole vregs of lanes, the length is whole blocks of rows and the
ambient mesh splits none of batch, sequence and heads; ``_rope``, as the
mixers had it, everywhere else.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.mesh import logical_axis_shards
from . import attention as _attention
from .kda import _largest

F32 = jnp.float32
# A block is ROWS rows of HEADS heads (1 MiB of bfloat16 at 128 lanes a
# head, a head's rows one run of 64 KiB); the kernel walks it _TILE rows at a
# time with a tile of the tables in registers across the heads. A test or a
# sweep sets them by ``monkeypatch.setattr``. PERF.md §6, PR 57, has the
# sweep on the chip: 16 heads a step is what counts (4: half the rate).
ROWS, HEADS, _TILE = 256, 16, 32
_VMEM_LIMIT = 64 * 2**20


class _Turn(NamedTuple):
    """A call's static part. ``rows`` and ``heads``: a block's.
    ``interpret``: the interpreter runs the kernel (the jitted entry keeps
    its traces by it)."""
    leading: bool
    amplitude: float
    rows: int
    heads: int
    interpret: bool


def _blocks(shape) -> Optional[tuple]:
    """(rows, heads) of a block over x [B, H, T, D], or None where T is not
    whole blocks of at least a bfloat16 tile's 16 rows."""
    rows = _largest(ROWS, 16, shape[2])
    if not rows:
        return None
    heads = max(g for g in range(1, min(HEADS, shape[1]) + 1) if not shape[1] % g)
    return rows, heads


def rotary_road(shape, freqs) -> str:
    """"kernel" or "xla": the road ``rotate`` takes for x of ``shape`` [B,
    H, T, D] turned by ``freqs``, on this platform and under the ambient
    mesh. Nothing runs."""
    fits = (
        (_attention._on_tpu() or _attention._interpret())
        and shape[3] % 128 == 0 and 0 < 2 * freqs.shape[0] <= shape[3]
        and _blocks(shape) is not None
        and all(logical_axis_shards(axis) == 1 for axis in ("batch", "seq", "heads"))
    )
    return "kernel" if fits else "xla"


def _tables(positions, freqs, width: int, turn: _Turn, back: bool):
    """([1 + n, B, T, width] float32, the n lane rotations): the table x is
    times, then the one each ``roll(x, shift)`` is times. ``back`` turns the
    sines' sign: the rotation back, which is the cotangent's."""
    half = freqs.shape[0]
    angles = positions[:, :, None].astype(F32) * freqs  # [B, T, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if turn.amplitude != 1.0:
        cos, sin = cos * turn.amplitude, sin * turn.amplitude
    if back:
        sin = -sin
    rest = width - 2 * half
    before = 0 if turn.leading else rest

    def lanes(first, second, fill):
        return jnp.pad(jnp.concatenate([first, second], -1),
                       ((0, 0), (0, 0), (before, rest - before)), constant_values=fill)

    if not rest:
        # roll(x, half)[i] is x[i - half] and x[i + half] alike.
        return jnp.stack([lanes(cos, cos, 1.0), lanes(-sin, sin, 0.0)]), (half,)
    zero = jnp.zeros_like(sin)
    # roll(x, width - half)[i] is x[i + half], roll(x, half)[i] is x[i - half].
    return (jnp.stack([lanes(cos, cos, 1.0), lanes(-sin, zero, 0.0), lanes(zero, sin, 0.0)]),
            (width - half, half))


def _rotary_kernel(x_ref, tab_ref, o_ref, *, shifts, tile, roll):
    def one(i, carry):
        at = pl.ds(pl.multiple_of(i * tile, tile), tile)
        tabs = [tab_ref[j, 0, at, :] for j in range(1 + len(shifts))]
        for h in range(x_ref.shape[1]):
            x = x_ref[0, h, at, :].astype(F32)
            y = x * tabs[0]
            for shift, tab in zip(shifts, tabs[1:]):
                y = y + roll(x, shift) * tab
            o_ref[0, h, at, :] = y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[2] // tile, one, None)


def _xla_roll(x, shift):
    return jnp.roll(x, shift, 1)


def _tpu_roll(x, shift):
    return pltpu.roll(x, shift, 1)


# One jitted entry, so that a step's text holds a Mosaic body once a (shape,
# part of a head, direction) and not once a call site: a layer turns q and
# k, forward, replayed and backward, and the layers are unrolled.


@functools.partial(jax.jit, static_argnums=(3, 4))
def _turned(x, positions, freqs, turn: _Turn, back: bool):
    """x [B, H, T, D] turned; or, ``back``, a cotangent turned back."""
    b, h, t, d = x.shape
    rows, heads = turn.rows, turn.heads
    tables, shifts = _tables(positions, freqs, d, turn, back)
    # The grid: batch, a sequence's blocks of rows, a row block's heads.
    block = pl.BlockSpec((1, heads, rows, d), lambda i, j, k: (i, k, j, 0))
    return pl.pallas_call(
        functools.partial(
            _rotary_kernel, shifts=shifts, tile=min(rows, _TILE),
            roll=_xla_roll if turn.interpret else _tpu_roll),
        grid=(b, t // rows, h // heads),
        in_specs=[block, pl.BlockSpec((tables.shape[0], 1, rows, d),
                                      lambda i, j, k: (0, i, j, 0))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        # XLA schedules around a call by what it is told here.
        cost_estimate=pl.CostEstimate(
            flops=(1 + 2 * len(shifts)) * x.size, transcendentals=0,
            bytes_accessed=2 * x.size * x.dtype.itemsize + tables.size * 4),
        interpret=turn.interpret,
    )(x, tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate_pallas(x, positions, freqs, turn):
    return _turned(x, positions, freqs, turn, False)


def _rotate_fwd(x, positions, freqs, turn):
    return _turned(x, positions, freqs, turn, False), (positions, freqs)


def _rotate_bwd(turn, residuals, g):
    # freqs is a table of the configuration's, not a weight: no cotangent.
    return _turned(g, *residuals, turn, True), None, None


_rotate_pallas.defvjp(_rotate_fwd, _rotate_bwd)


def rotate(x, positions, freqs, *, leading: bool = False, amplitude: float = 1.0):
    """``models/llama.py`` ``_rope``, argument for argument: x [B, H, T, D]
    (a projection writes it so where a transposition asks it to), positions
    [B, T]. One Pallas pass where ``rotary_road`` says "kernel", ``_rope``
    elsewhere."""
    if rotary_road(x.shape, freqs) == "kernel":
        return _rotate_pallas(x, positions, jnp.asarray(freqs, F32), _Turn(
            leading, float(amplitude), *_blocks(x.shape), _attention._interpret()))
    from ..models.llama import _rope  # it imports this package

    return _rope(x, positions, freqs, leading=leading, amplitude=amplitude)
