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


# Latent attention's q and k (``models/mla.py``), each from the projection's
# output to the array ``flash_attention`` reads in one pass, and each
# cotangent back in one. A head is ``nope`` lanes that never turn (whole
# vregs) and then ``pe`` lanes that may (64: half a vreg); what the mixer did
# in XLA around ``_rope`` happens in registers, in its order:
#
# - the RMSNorm over a head's nope + pe channels with a learned weight
#   (``cfg.qk_head_norm``; ``eps`` is None where the layer has none), float32
#   from the projection's rounding to the one rounding at the end;
# - ``_rope``'s rotation of the pe lanes (``cfg.mla_rope``; ``freqs`` is None
#   where nothing turns) against ``_tables``' float32 tables for a whole
#   64-wide head: channel i meets channel i + 32 by a [64, 64] matmul of 0s
#   and 1s on the values as they lie (``_swapped``: the MXU is idle here and
#   the XLU is what a pass of this kind waits for; PERF.md §6, PR 59);
# - k's assembly: a head's nope lanes are the first of ``kv_b_proj``'s
#   output [B, H, T, nope + dv] (a block reads those lanes alone), its pe
#   lanes the one shared part [B, T, pe] read once a row block (the heads are
#   the grid's innermost axis), normed by the head's own rsqrt: no
#   ``broadcast_to`` and no ``concatenate`` is made in HBM. v's lanes leave by
#   the same pass, so that the pass back hands ``kv_b_proj``'s backward
#   matmuls one whole array and XLA neither slices nor pads.
#
# The pass back turns the cotangent back (the sines' sign turned), then the
# norm's transpose from the projection's output read again (dn = g w; dx =
# r (dn - n mean(dn n)) with n the normed value before its weight), the
# weights' gradients as a grid step's partial sums [tile, width] that XLA
# adds up, and k's shared lanes' cotangent summed over the heads in the
# output block that stands while a row block's heads pass.


# The lanes of a head that may turn: half a vreg, which is what the kernels
# below are written for (``latent_road`` sends any other width to XLA).
_PE = 64


class _Fuse(NamedTuple):
    """A latent call's static part. ``eps``: the norm's, None for no norm.
    ``turns``: the pe lanes rotate. ``rows``, ``heads``, ``interpret``: as
    ``_Turn``'s."""
    eps: Optional[float]
    turns: bool
    rows: int
    heads: int
    interpret: bool


def latent_road(q_shape, kv_shape, pe: int, *, norms: bool, turns: bool) -> str:
    """"kernel" or "xla": the road ``latent_qkv`` takes for q [B, H, T,
    nope + pe] and kv [B, H, T, nope + dv] of a layer that ``norms`` a head,
    ``turns`` its pe lanes, both or neither, on this platform and under the
    ambient mesh. Nothing runs."""
    nope = q_shape[3] - pe
    fits = (
        (_attention._on_tpu() or _attention._interpret())
        and (norms or turns)
        and pe == _PE and nope > 0 and nope % 128 == 0 and kv_shape[3] == 2 * nope
        and _blocks(q_shape) is not None
        and all(logical_axis_shards(axis) == 1 for axis in ("batch", "seq", "heads"))
    )
    return "kernel" if fits else "xla"


def _swap_matrix(dtype):
    """[64, 64] of 0 and 1 in ``dtype``: x @ it is x with its two halves of
    32 lanes changed over."""
    i = jax.lax.broadcasted_iota(jnp.int32, (_PE, _PE), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (_PE, _PE), 1)
    return ((i + _PE // 2) % _PE == j).astype(dtype)


def _swapped(raw, swap):
    """raw [tile, 64], as it lies in its own dtype, with its two halves
    changed over, in float32: a matmul against ``_swap_matrix``, which is
    exact on bfloat16 values in one pass of the MXU, where a lane rotation
    of half a vreg is a concatenation and a roll of the XLU a head (the
    norm's rsqrt is a row's scalar and its weight a lane's, so they come
    after it)."""
    exact = jax.lax.Precision.HIGHEST if raw.dtype == F32 else None
    return jnp.dot(raw, swap, precision=exact, preferred_element_type=F32)


def _row_mean(x, width):
    return jnp.sum(x, -1, keepdims=True) / width


def _split_refs(refs, fuse: _Fuse, n_in: int):
    """(the n_in operands, the weight's ref or None, the tables' or None, the
    outputs) of a latent kernel's refs."""
    refs = list(refs)
    operands, refs = refs[:n_in], refs[n_in:]
    w_ref = refs.pop(0) if fuse.eps is not None else None
    tab_ref = refs.pop(0) if fuse.turns else None
    return operands, w_ref, tab_ref, refs


def _tiles(ref, tile, body, carry=None):
    """``body(at, carry)`` over a block's tiles of rows."""
    return jax.lax.fori_loop(
        0, ref.shape[-2] // tile,
        lambda i, c: body(pl.ds(pl.multiple_of(i * tile, tile), tile), c), carry)


def _turn(p, raw, tabs, swap, scale=None):
    """p [tile, 64] float32 turned against a tile of the tables (cos, sin).
    ``raw`` is what p was made of, before any scaling by a row's scalar or a
    lane's weight: its swapped lanes are times ``scale`` instead."""
    other = _swapped(raw, swap)
    return p * tabs[0] + (other if scale is None else other * scale) * tabs[1]


def _tables_tile(tab_ref, at):
    return None if tab_ref is None else (tab_ref[0, 0, at, :], tab_ref[1, 0, at, :])


def _latent_q_kernel(*refs, fuse, tile):
    (x_ref,), w_ref, tab_ref, (o_ref,) = _split_refs(refs, fuse, 1)
    width = x_ref.shape[3]
    nope = width - _PE
    swap = _swap_matrix(x_ref.dtype)

    def one(at, carry):
        tabs = _tables_tile(tab_ref, at)
        for h in range(x_ref.shape[1]):
            raw = x_ref[0, h, at, nope:]
            if fuse.eps is None:
                o_ref[0, h, at, :nope] = x_ref[0, h, at, :nope]
                o_ref[0, h, at, nope:] = _turn(
                    raw.astype(F32), raw, tabs, swap).astype(o_ref.dtype)
                continue
            x = x_ref[0, h, at, :].astype(F32)
            r = jax.lax.rsqrt(_row_mean(x * x, width) + fuse.eps)
            x = x * r * w_ref[0:1, :]
            if not fuse.turns:
                o_ref[0, h, at, :] = x.astype(o_ref.dtype)
                continue
            o_ref[0, h, at, :nope] = x[:, :nope].astype(o_ref.dtype)
            o_ref[0, h, at, nope:] = _turn(
                x[:, nope:], raw, tabs, swap, r * w_ref[1:2, nope:]).astype(o_ref.dtype)
        return carry

    _tiles(x_ref, tile, one)


def _latent_k_kernel(*refs, fuse, tile):
    (a_ref, v_ref, p_ref), w_ref, tab_ref, (k_ref, vo_ref) = _split_refs(refs, fuse, 3)
    nope = a_ref.shape[3]
    width = nope + _PE
    swap = _swap_matrix(p_ref.dtype)

    def one(at, carry):
        # The shared lanes' weight and rotation once a tile: a head's rsqrt is
        # a row's scalar, so it comes after them.
        raw = p_ref[0, at, :]
        p = raw.astype(F32)
        if fuse.eps is not None:
            p_squares = jnp.sum(p * p, -1, keepdims=True)
            p = p * w_ref[0:1, nope:]
        if fuse.turns:
            p = _turn(p, raw, _tables_tile(tab_ref, at), swap,
                      None if fuse.eps is None else w_ref[1:2, nope:])
        shared = p.astype(k_ref.dtype)
        for h in range(a_ref.shape[1]):
            if fuse.eps is None:
                k_ref[0, h, at, :nope] = a_ref[0, h, at, :]
                k_ref[0, h, at, nope:] = shared
                continue
            a = a_ref[0, h, at, :].astype(F32)
            r = jax.lax.rsqrt(
                (jnp.sum(a * a, -1, keepdims=True) + p_squares) / width + fuse.eps)
            k_ref[0, h, at, :nope] = (a * r * w_ref[0:1, :nope]).astype(k_ref.dtype)
            k_ref[0, h, at, nope:] = (p * r).astype(k_ref.dtype)
        return carry

    _tiles(a_ref, tile, one)
    vo_ref[...] = v_ref[...]


def _norm_back(g, n, r, w_ref, width):
    """The norm's transpose for one head's tile, [tile, width] each: g the
    cotangent of the normed and weighted value, n the normed value before its
    weight. (dx, the weight's gradient before its sum over rows)."""
    dn = g * w_ref[0:1, :]
    return r * (dn - n * _row_mean(dn * n, width)), g * n


def _no_sums(fuse, tile, width):
    return None if fuse.eps is None else jnp.zeros((tile, width), F32)


def _latent_q_back_kernel(*refs, fuse, tile):
    # The projection's output is read again only for the norm's transpose.
    (g_ref, *x_ref), w_ref, tab_ref, (dx_ref, *dw_ref) = _split_refs(
        refs, fuse, 1 if fuse.eps is None else 2)
    width = g_ref.shape[3]
    nope = width - _PE
    swap = _swap_matrix(g_ref.dtype)

    def one(at, sums):
        tabs = _tables_tile(tab_ref, at)
        for h in range(g_ref.shape[1]):
            raw = g_ref[0, h, at, nope:]
            gp = raw.astype(F32)
            if fuse.turns:
                gp = _turn(gp, raw, tabs, swap)
            if fuse.eps is None:
                dx_ref[0, h, at, :nope] = g_ref[0, h, at, :nope]
                dx_ref[0, h, at, nope:] = gp.astype(dx_ref.dtype)
                continue
            g = jnp.concatenate([g_ref[0, h, at, :nope].astype(F32), gp], -1)
            x = x_ref[0][0, h, at, :].astype(F32)
            r = jax.lax.rsqrt(_row_mean(x * x, width) + fuse.eps)
            dx, dw = _norm_back(g, x * r, r, w_ref, width)
            dx_ref[0, h, at, :] = dx.astype(dx_ref.dtype)
            sums = sums + dw
        return sums

    sums = _tiles(g_ref, tile, one, _no_sums(fuse, tile, width))
    if fuse.eps is not None:
        dw_ref[0][0, 0, 0] = sums


def _latent_k_back_kernel(*refs, fuse, tile):
    (g_ref, gv_ref, *read_again), w_ref, tab_ref, (dkv_ref, dp_ref, *dw_ref) = _split_refs(
        refs, fuse, 2 if fuse.eps is None else 4)
    nope = gv_ref.shape[3]
    width = nope + _PE
    swap = _swap_matrix(g_ref.dtype)

    # The shared part's block stands while a row block's heads pass.
    @pl.when(pl.program_id(2) == 0)
    def _():
        dp_ref[...] = jnp.zeros(dp_ref.shape, F32)

    def one(at, sums):
        tabs = _tables_tile(tab_ref, at)
        dp = jnp.zeros((tile, _PE), F32)
        if fuse.eps is not None:
            a_ref, p_ref = read_again
            p = p_ref[0, at, :].astype(F32)
            p_squares = jnp.sum(p * p, -1, keepdims=True)
        for h in range(g_ref.shape[1]):
            raw = g_ref[0, h, at, nope:]
            gp = raw.astype(F32)
            if fuse.turns:
                gp = _turn(gp, raw, tabs, swap)
            if fuse.eps is None:
                dkv_ref[0, h, at, :nope] = g_ref[0, h, at, :nope]
                dp = dp + gp
                continue
            g = jnp.concatenate([g_ref[0, h, at, :nope].astype(F32), gp], -1)
            a = a_ref[0, h, at, :].astype(F32)
            r = jax.lax.rsqrt(
                (jnp.sum(a * a, -1, keepdims=True) + p_squares) / width + fuse.eps)
            dx, dw = _norm_back(g, jnp.concatenate([a, p], -1) * r, r, w_ref, width)
            dkv_ref[0, h, at, :nope] = dx[:, :nope].astype(dkv_ref.dtype)
            dp = dp + dx[:, nope:]
            sums = sums + dw
        dp_ref[0, at, :] += dp
        return sums

    sums = _tiles(g_ref, tile, one, _no_sums(fuse, tile, width))
    if fuse.eps is not None:
        dw_ref[0][0, 0, 0] = sums
    dkv_ref[:, :, :, nope:] = gv_ref[...]


def _latent_call(kernel, fuse: _Fuse, shape, operands, w, positions, freqs, back, outs):
    """One latent kernel over the grid (batch, blocks of rows, a row block's
    heads). ``operands`` and ``outs`` are (array or shape, its block, its
    index map) and ``shape`` is q's or k's [B, H, T, width]."""
    b, h, t, width = shape
    rows, heads = fuse.rows, fuse.heads
    tile = min(rows, _TILE)
    arrays = [x for x, _, _ in operands]
    specs = [pl.BlockSpec(block, at) for _, block, at in operands]
    if fuse.eps is not None:
        # The weight, and the weight as the swapped lanes meet it.
        arrays.append(jnp.stack([w, jnp.concatenate([w[:-_PE], jnp.roll(w[-_PE:], _PE // 2)])]))
        specs.append(pl.BlockSpec((2, width), lambda i, j, k: (0, 0)))
    if fuse.turns:
        tables, _ = _tables(positions, freqs, _PE,
                            _Turn(False, 1.0, rows, heads, fuse.interpret), back)
        arrays.append(tables)
        specs.append(pl.BlockSpec((2, 1, rows, _PE), lambda i, j, k: (0, i, j, 0)))
    if back and fuse.eps is not None:
        steps = (b, t // rows, h // heads)
        outs = [*outs, (jax.ShapeDtypeStruct((*steps, tile, width), F32),
                        (1, 1, 1, tile, width), lambda i, j, k: (i, j, k, 0, 0))]
    moved = sum(x.size * x.dtype.itemsize for x in arrays) + sum(
        o.size * o.dtype.itemsize for o, _, _ in outs)
    return pl.pallas_call(
        functools.partial(kernel, fuse=fuse, tile=tile),
        grid=(b, t // rows, h // heads),
        in_specs=specs,
        out_specs=[pl.BlockSpec(block, at) for _, block, at in outs],
        out_shape=[o for o, _, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=(12 if back else 6) * b * h * t * width,
            transcendentals=b * h * t if fuse.eps is not None else 0,
            bytes_accessed=moved),
        interpret=fuse.interpret,
    )(*arrays)


def _head_blocks(fuse: _Fuse, *lanes):
    """(block, index map) of [B, H, T, .] arrays read ``lanes`` = (width,
    which block of that width along the lanes) at a time."""
    return [((1, fuse.heads, fuse.rows, width), lambda i, j, k, n=n: (i, k, j, n))
            for width, n in lanes]


def _row_block(fuse: _Fuse):
    """(block, index map) of the shared part [B, T, 64]."""
    return (1, fuse.rows, _PE), lambda i, j, k: (i, j, 0)


# One jitted entry a kernel, as ``_turned``: a step's text holds a Mosaic body
# once a (shape, kind of layer) and not once a layer.


@functools.partial(jax.jit, static_argnums=(4,))
def _latent_q_forward(x, w, positions, freqs, fuse: _Fuse):
    (whole,) = _head_blocks(fuse, (x.shape[3], 0))
    (q,) = _latent_call(_latent_q_kernel, fuse, x.shape, [(x, *whole)], w, positions, freqs,
                        False, [(jax.ShapeDtypeStruct(x.shape, x.dtype), *whole)])
    return q


@functools.partial(jax.jit, static_argnums=(5,))
def _latent_q_backward(g, x, w, positions, freqs, fuse: _Fuse):
    """(dx, dw or None). ``x``: the projection's output where the layer
    norms, None elsewhere."""
    (whole,) = _head_blocks(fuse, (g.shape[3], 0))
    read_again = [] if x is None else [(x, *whole)]
    dx, *dw = _latent_call(_latent_q_back_kernel, fuse, g.shape, [(g, *whole), *read_again], w,
                           positions, freqs, True,
                           [(jax.ShapeDtypeStruct(g.shape, g.dtype), *whole)])
    return dx, dw[0].sum((0, 1, 2, 3)) if dw else None


@functools.partial(jax.jit, static_argnums=(5,))
def _latent_k_forward(kv, shared, w, positions, freqs, fuse: _Fuse):
    """(k [B, H, T, nope + 64], v [B, H, T, nope]) of kv [B, H, T, 2 nope]
    and the shared part [B, T, 64]."""
    b, h, t, nope = *kv.shape[:3], kv.shape[3] // 2
    first, second, whole = _head_blocks(fuse, (nope, 0), (nope, 1), (nope + _PE, 0))
    return _latent_call(
        _latent_k_kernel, fuse, (b, h, t, nope + _PE),
        [(kv, *first), (kv, *second), (shared, *_row_block(fuse))], w, positions, freqs, False,
        [(jax.ShapeDtypeStruct((b, h, t, nope + _PE), kv.dtype), *whole),
         (jax.ShapeDtypeStruct((b, h, t, nope), kv.dtype), *first)])


@functools.partial(jax.jit, static_argnums=(7,))
def _latent_k_backward(g, gv, kv, shared, w, positions, freqs, fuse: _Fuse):
    """(dkv, the shared part's cotangent, dw or None). ``kv``, ``shared``:
    the forward's operands where the layer norms, None elsewhere."""
    b, h, t, nope = gv.shape
    first, whole, both = _head_blocks(fuse, (nope, 0), (nope + _PE, 0), (2 * nope, 0))
    read_again = [] if kv is None else [(kv, *first), (shared, *_row_block(fuse))]
    dkv, dshared, *dw = _latent_call(
        _latent_k_back_kernel, fuse, g.shape, [(g, *whole), (gv, *first), *read_again], w,
        positions, freqs, True,
        [(jax.ShapeDtypeStruct((b, h, t, 2 * nope), g.dtype), *both),
         (jax.ShapeDtypeStruct((b, t, _PE), F32), *_row_block(fuse))])
    return dkv, dshared.astype(g.dtype), dw[0].sum((0, 1, 2, 3)) if dw else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _latent_q_pallas(x, w, positions, freqs, fuse):
    return _latent_q_forward(x, w, positions, freqs, fuse)


def _latent_q_fwd(x, w, positions, freqs, fuse):
    # Kept for the pass back: the projection's output where the norm's
    # transpose reads it again, nothing of it elsewhere.
    kept = None if fuse.eps is None else x
    return _latent_q_forward(x, w, positions, freqs, fuse), (kept, w, positions, freqs)


def _latent_q_bwd(fuse, residuals, g):
    dx, dw = _latent_q_backward(g, *residuals, fuse)
    return dx, dw, None, None


_latent_q_pallas.defvjp(_latent_q_fwd, _latent_q_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _latent_k_pallas(kv, shared, w, positions, freqs, fuse):
    return _latent_k_forward(kv, shared, w, positions, freqs, fuse)


def _latent_k_fwd(kv, shared, w, positions, freqs, fuse):
    kept = (None, None) if fuse.eps is None else (kv, shared)
    return (_latent_k_forward(kv, shared, w, positions, freqs, fuse),
            (*kept, w, positions, freqs))


def _latent_k_bwd(fuse, residuals, cotangents):
    dkv, dshared, dw = _latent_k_backward(*cotangents, *residuals, fuse)
    return dkv, dshared, dw, None, None


_latent_k_pallas.defvjp(_latent_k_fwd, _latent_k_bwd)


def latent_qkv(q, kv, shared, positions, freqs, q_weight, k_weight, eps):
    """(q, k, v) as ``flash_attention`` takes them, of ``models/mla.py``'s
    projections heads first: q [B, H, T, nope + 64], kv [B, H, T, 2 nope]
    (k's nope lanes | v) and the one shared key part [B, T, 64]. ``freqs``:
    ``_rope``'s table for the 64 lanes, None where nothing turns.
    ``q_weight``, ``k_weight``: the per-head norms' [nope + 64], None (both)
    where the layer has none; ``eps`` theirs. For a call whose
    ``latent_road`` is "kernel"."""
    norms = q_weight is not None
    fuse = _Fuse(float(eps) if norms else None, freqs is not None,
                 *_blocks(q.shape), _attention._interpret())
    if fuse.turns:
        freqs = jnp.asarray(freqs, F32)
    if norms:
        q_weight, k_weight = q_weight.astype(F32), k_weight.astype(F32)
    k, v = _latent_k_pallas(kv, shared, k_weight, positions, freqs, fuse)
    return _latent_q_pallas(q, q_weight, positions, freqs, fuse), k, v
