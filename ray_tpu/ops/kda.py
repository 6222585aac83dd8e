"""Kimi Delta Attention (KDA): a gated delta rule with per-channel decay.

Per head, with a float32 state S in R^{dk x dv}:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``a_t = exp(g_t)`` in (0, 1]^dk is the decay and ``b_t`` the write
strength, whose range is the configuration's: (0, 1) where the mixer takes a
sigmoid (Kimi-Linear), (0, 2) where it doubles it (``kda_allow_neg_eigval``,
Solar-Open2: I - b k k^T then has the eigenvalue 1 - b in (-1, 1) along a
unit k). Nothing here assumes b < 1: with unit keys the entries of the
chunk's system are at most b in size, so up to 2, its inverse by doubling is
exact at every level for any strictly lower-triangular A, and what the
rounding of X to the matmuls' dtype at five levels costs grows with b by
under a factor of two (PERF.md, Findings, PR 48; tests/test_kda_op.py holds
the kernels to the recurrence over (0, 2)). ``chunk_kda`` computes it in
chunks of 64 tokens: inside a chunk the rule is a unit lower-triangular
system (the WY form of the products of Householder-like factors), between
chunks the state is carried.

Who normalises: this file. The mixer's three normalisations over a head's
channels are sums over the lanes of a row the scan already holds, so they
are taken there and not as passes over [T, H * d] arrays in HBM
(``_normed_chunk``): q and k arrive raw and float32, as the mixer's
convolution and SiLU leave them, and become ``l2norm(q) * scale`` and
``l2norm(k)`` in float32 with one rounding to the matmuls' dtype; o leaves
through its RMSNorm and the output gate's sigmoid while it is float32 and
is rounded once, as it is stored. The gradients are those of the raw q and
k, of the gate and of the norm's weight.

Who convolves: this file too. The mixer's causal depthwise convolution of
four taps over each of q's, k's and v's float32 projections, the SiLU after
it and, for v, the rounding to the matmuls' dtype are ``conv_silu``: on a
TPU one Pallas pass over a projection forward (``_conv_fwd_kernel``: a block
of rows with the 8 rows before it, the taps added in ``short_conv``'s order,
one store in the dtype asked for, float32 for q and k) and one backward
(``_conv_bwd_kernel``: it makes the pre-activation again from the projection,
which with the filter is all it keeps, writes the projection's cotangent once
and adds the filter's up in float32 over batch and time), each behind a
jitted entry. They are bound by their bytes: XLA's form, four shifted slices
of a padded copy differentiated tap by tap, moved 6.4 times the backward's.
``short_conv`` with ``jax.nn.silu`` is the reference they are tested against
and the path where there is no TPU or the shape does not tile.

Who lays out: the kernel that produces, for the kernel that reads. The
convolution's output lies as the projection does, [B, T, D], unless the
caller says what its next kernel reads: ``conv_silu(..., heads=d)`` writes [B,
D / d, T, d], a head at a time, and takes the cotangent there. ``chunk_gdn``'s
kernels need that of q and k (a key head of 96 lanes is no whole number of
vregs, nor are a grid step's two, so a block must be whole in its last
extent), and between two Pallas calls XLA fuses nothing: the slice of q from
k, the transposed copy and the reshape copy it made for each, and as many
back, were a tenth of a Gated DeltaNet mixer's time. A convolution block's
lanes are whole heads (384: four of 96), so the forward tile's store is one
lane slice a head and the backward's tile is put together from a load a head,
in VMEM; q and k, one projection and one convolution, stay one array [B, 2,
H, T, dk] through the scan and back. Where a grid step's heads are whole
vregs side by side a kernel reads [B, T, H * d] as it lies and takes the
heads apart itself: ``chunk_kda`` and ``chunk_ssd`` (a BlockSpec picks a
step's heads' lanes), and ``chunk_gdn`` for v, the gate and o (two value
heads of 192 lanes are three vregs), so their convolutions are called
without ``heads``.

Inside a chunk, with G the running sum of g inside it:

    A[t, s]   = b_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])      s < t
    Aqk[t, s] =     sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])      s <= t
    T = (I + A)^-1,  W = T (b k exp(G)),  U0 = T (b v)

No exponent is ever positive, however strong the decay: entry (t, s) is
computed at the level b in {1, 2, .., 32} at which t and s lie in the two
halves of one block of 2b rows, as exp(G_t - r) * exp(r - G_s) with r the
running sum at the first row of t's half, which lies between them. Each
level is one masked [64, dk] x [dk, 64] matmul. G is one lower-triangular
0/1 matrix times g, for every head at once (``_sum_matrix``); the running
sum at the first row of every row's block of 2, 4, .., 64 rows follows from
G in six steps of a sublane roll and a select (row t takes row t - s where
bit s of t is set), so no level costs a matmul of its own. The inverse
doubles the same way: the inverse X of the block diagonal at block size b
gives that of 2b as X - X E X, E the blocks the doubling takes in (E X E =
0), exact at every level, so there is no row-by-row substitution.

Between chunks, with S the state at the chunk's start:

    U = U0 - W S,   O = (q exp(G)) S + Aqk U,
    S' = Diag(exp(G_last)) S + (k exp(G_last - G))^T U

One function of 2-D values, ``_head_chunk``, is a chunk from the state at
its start to the state at its end, of one head or of P heads stacked on
rows: [P * 64, dk], [P * 64, dv], [P * 64, 1]. Over P * 64 rows the level
masks are block-diagonal over heads (a block of 2b <= 64 rows never spans
two), so A, Aqk, every E and X and T are, their cross-head blocks exact
zeros, and the same twelve level matmuls and ten of the inverse serve P
heads, each head's numbers the sums they are alone plus zeros. Only the
state's three products are a head's own: its 64 rows against its [dv, dk]
state.

On a TPU the forward kernel walks the grid (batch, chunk, heads / P) with
every head's state in VMEM, in float32, and P = 128 / 64 = 2 heads a step
where the heads pair off (else one): a step takes the pair's lanes of q, k,
v and of the running sums and stacks them on rows, whole vregs where dk and
dv are multiples of 128. The reason is the MXU's tile of 128 x 128: a 64-row
product fills a quarter of it and costs its whole latency, and the ten
matmuls of the inverse each wait for the one before; at one head a step
they were 11 of a 19 ms call at 16k tokens and 32 heads of 128, at two 7 of
14. (Four heads side by side in a step, as four chains for the scheduler to
interleave, did not overlap: a chain's latency is not hidden, it is shared.)
At a chunk's first step the kernel takes G for all heads in one matmul.
Called under a gradient it also writes the state at every chunk's start (256
chunks x 32 heads x 64 KiB = 512 MiB a layer at 16k tokens) and every
chunk's T as it multiplied with it, in the matmuls' dtype, a pair's two [64,
64] diagonal blocks side by side on 128 lanes (8 KiB a head and chunk, 64
MiB a layer there: an eighth of the states'); the decoder's remat policy
keeps both from the forward pass to the layer's backward, so that the replay
does not run this kernel again. The backward kernel walks the chunks in
reverse with the states' cotangents in VMEM and differentiates
``_normed_chunk`` of the stacked pair where it stands (``jax.vjp`` inside
the kernel, from the saved states: a replay of one chunk's twelve level
products, its normalisations and its products with T and the state, nothing
of a chunk's interior but T ever in HBM). The inverse is not replayed and
not differentiated link by link: ``_unit_lower_inverse`` is handed the T the
forward wrote, bit for bit what the doubling would remake, and its own rule
gives A's cotangent as -T^T dT T^T, two matmuls where the chain's ten and
their twenty gradients stood, exact for the inverse at that T (autodiff gave
the gradient of the rounded chain: they differ by the order of one rounding
to the matmuls' dtype). The kernel then takes G's cotangent back to g's in
one matmul; the cotangent of the norm's weight adds up over a batch row's
steps in the row's output block. Elsewhere the same function runs under
``lax.scan``, one head a call, and JAX differentiates it, the inverse by the
same rule. Matmul operands are v's dtype (bfloat16 in the models),
accumulation, the normalisations, the running sums and the state float32.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention

CHUNK = 64
_LEVELS = (1, 2, 4, 8, 16, 32)
# Heads a grid step of the Pallas kernels stacks on rows: the MXU's 128
# rows over a chunk's.
_PAIR = 128 // CHUNK
F32 = jnp.float32
# What a kernel may take of a v5e core's 128 MiB of VMEM (Mosaic's default
# lets it use 16): the running sums of every head are 4 MiB at 32 heads of
# 128, their cotangents as much, the states 2 MiB; twice each at 64 heads,
# where g's block of every head is 2 MiB more and its cotangent's another.
_VMEM_LIMIT = 64 * 2**20


def short_conv(x, w):
    """Causal depthwise convolution over time, in float32. x [B, T, D]; w
    [K, D], tap K - 1 on the current token: y_t = sum_i w[i] x_{t - (K - 1)
    + i}."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(F32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * w[i].astype(F32) for i in range(taps))


# ------------------------------------------- the convolution as one pass
# A block is [_CONV_ROWS, _CONV_LANES] of a [B, T, D] array (less where T or
# D is less), worked through _CONV_TILE rows at a time so that a tile's
# values stay in registers; its halo is the float32 sublane tile of 8 rows
# before it (and, in the backward pass, after it) under a BlockSpec of its
# own over the same array.
# Inside the kernels a select is ``lax.select`` and SiLU's sigmoid
# ``lax.logistic``: ``jnp.where`` and ``jax.nn.sigmoid`` are jitted, their
# traces kept from the kernel traced first with its frames, and a profile's
# reader names a kernel by the first ``.._kernel`` among its module's strings.
_CONV_HALO = 8
_CONV_ROWS, _CONV_LANES, _CONV_TILE = 512, 512, 64


def _largest(most: int, least: int, n: int) -> int:
    """The largest of most, most / 2, .., least that divides n, else 0."""
    while most >= least and n % most:
        most //= 2
    return most if most >= least else 0


class _ConvBlocks(NamedTuple):
    """A call's static part: a block's rows and lanes, the rows of a tile
    inside it, whether the interpreter runs the kernels (the jitted entries
    keep their traces by it), and the lanes of a head where the output lies
    heads first (0: it lies as x does)."""
    rows: int
    lanes: int
    tile: int
    interpret: bool
    heads: int = 0


def _conv_blocks(x, w, heads=None):
    """The convolution kernels' blocks over x [B, T, D] under a filter w [K,
    D], or None where the shape does not tile or there is neither a TPU nor
    the interpreter: whole 16-row tiles of a bfloat16 output in T, whole
    vregs of lanes in D that are whole heads of ``heads`` lanes where the
    output lies heads first, and a filter that reaches no further back than
    the halo."""
    interpret = _attention._interpret()
    if not (_attention._on_tpu() or interpret):
        return None
    rows = _largest(_CONV_ROWS, 16, x.shape[1])
    # The most whole vregs of lanes, up to _CONV_LANES, that divide D: 512 of
    # 4,096 or 8,192 channels, 384 of 5,760 (45 vregs, which no power of two
    # above one divides: four heads of 96 lanes, two of 192).
    lanes = next((n for n in range(_CONV_LANES, 0, -128)
                  if not x.shape[2] % n and not n % (heads or n)), 0)
    if not rows or not lanes or w.shape[0] - 1 > _CONV_HALO:
        return None
    return _ConvBlocks(rows, lanes, min(rows, _CONV_TILE), interpret, heads or 0)


def _taps(ext, w, roll):
    """[the float32 ``ext`` moved down by K - 1 - i rows, for each tap i]:
    where row r of ``ext`` is token t, row r of entry i is token t - (K - 1)
    + i, the one tap i multiplies. The rows that wrap lie in the halo."""
    taps = w.shape[0]
    return [roll(ext, taps - 1 - i) if i < taps - 1 else ext for i in range(taps)]


def _add_up(terms):
    """The terms added in their order (``sum`` without its leading 0)."""
    return functools.reduce(lambda a, b: a + b, terms)


def _conv_of(shifted, w):
    """sum_i w[i] x_{t - (K - 1) + i} in ``short_conv``'s order."""
    return _add_up(x * w[i:i + 1] for i, x in enumerate(shifted))


def _sublanes(dtype) -> int:
    """Rows of a sublane tile: 8 of float32, 16 of bfloat16."""
    return _CONV_HALO * 4 // jnp.dtype(dtype).itemsize


def _read(ref, at):
    """Rows ``at`` of a block as [rows, lanes] float32: of [1, rows, lanes] as
    they lie; of [1, lanes / d, rows, d], heads first, each head's d lanes
    beside the last's (whole vregs where d is a multiple of 128, else lane
    rotations and selects in VMEM)."""
    if len(ref.shape) == 3:
        return ref[0, at, :].astype(F32)
    return jnp.concatenate(
        [ref[0, j, at, :].astype(F32) for j in range(ref.shape[1])], axis=1)


def _write(ref, at, y):
    """``_read`` undone, rounded once to the block's dtype: a store a head
    where the block lies heads first."""
    if len(ref.shape) == 3:
        ref[0, at, :] = y.astype(ref.dtype)
        return
    d = ref.shape[3]
    for j in range(ref.shape[1]):
        ref[0, j, at, :] = y[:, j * d:(j + 1) * d].astype(ref.dtype)


def _edge(ref, at_edge):
    """A halo block as float32, zeros where it lies outside the sequence
    (its index is clamped there, to rows of the sequence's own)."""
    rows = _read(ref, slice(None))
    return jax.lax.select(at_edge, jnp.zeros_like(rows), rows)


def _rows_before(x_ref, edge, i, tile):
    """The 8 rows before tile ``i`` of the block ``x_ref`` [1, rows, lanes]
    of float32: the block's own, or before its first ``edge``."""
    lo = pl.multiple_of(jnp.maximum(i * tile - _CONV_HALO, 0), _CONV_HALO)
    return jax.lax.select(i == 0, edge, x_ref[0, pl.ds(lo, _CONV_HALO), :])


def _rows_after(x_ref, edge, i, tile):
    """The 8 rows after tile ``i`` of the block ``x_ref`` as float32: the
    first of the block's next sublane tile, or after its last ``edge``."""
    n, rows = _sublanes(x_ref.dtype), x_ref.shape[-2]
    hi = pl.multiple_of(jnp.minimum((i + 1) * tile, rows - n), n)
    own = _read(x_ref, pl.ds(hi, n))[:_CONV_HALO]
    return jax.lax.select(i == rows // tile - 1, edge, own)


def _conv_fwd_kernel(x_ref, before_ref, w_ref, *rest, tile, roll):
    # rest: (the bias, the output) or the output alone.
    b_ref, y_ref = rest if len(rest) == 2 else (None, *rest)
    w = w_ref[...].astype(F32)
    before = _edge(before_ref, pl.program_id(2) == 0)

    def one(i, carry):
        at = pl.ds(pl.multiple_of(i * tile, tile), tile)
        ext = jnp.concatenate([_rows_before(x_ref, before, i, tile), x_ref[0, at, :]])
        c = _conv_of(_taps(ext, w, roll), w)[_CONV_HALO:]
        if b_ref is not None:
            c = c + b_ref[...].astype(F32)
        _write(y_ref, at, c * jax.lax.logistic(c))
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // tile, one, None)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                     *rest, tile, roll):
    # rest: (the bias, dx, dw, the bias's cotangent) or (dx, dw).
    b_ref, dx_ref, dw_ref, db_ref = rest if len(rest) == 4 else (None, *rest, None)
    taps = w_ref.shape[0]
    w = w_ref[...].astype(F32)
    first, last = pl.program_id(2) == 0, pl.program_id(2) == pl.num_programs(2) - 1
    before, after = _edge(before_ref, first), _edge(after_ref, last)
    dy_after = _edge(dy_after_ref, last)[:_CONV_HALO]

    # The filter's gradient adds up over the batch and a sequence's blocks
    # in its output block, which stays in VMEM while the block's index (the
    # lanes) stands: 8 rows a tap, which the caller sums.
    @pl.when((pl.program_id(1) == 0) & first)
    def _init():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)
        if db_ref is not None:
            db_ref[...] = jnp.zeros(db_ref.shape, F32)

    def one(i, carry):
        at = pl.ds(pl.multiple_of(i * tile, tile), tile)
        ext = jnp.concatenate([_rows_before(x_ref, before, i, tile), x_ref[0, at, :],
                               _rows_after(x_ref, after, i, tile)])
        # The pre-activation again, on the tile's rows and the 8 after them.
        shifted = [x[_CONV_HALO:] for x in _taps(ext, w, roll)]
        c = _conv_of(shifted, w)
        if b_ref is not None:
            c = c + b_ref[...].astype(F32)
        s = jax.lax.logistic(c)
        dy = jnp.concatenate([_read(dy_ref, at), _rows_after(dy_ref, dy_after, i, tile)])
        dz = dy * (s * (1.0 + c * (1.0 - s)))
        # dx_s = sum_i w[i] dz_{s + (K - 1) - i}: dz moved up, the rows that
        # wrap among the 8 after the tile.
        dx_ref[0, at, :] = _add_up(
            (roll(dz, tap - (taps - 1)) if tap < taps - 1 else dz)[:tile] * w[tap:tap + 1]
            for tap in range(taps))
        for tap, x in enumerate(shifted):
            prod = dz[:tile] * x[:tile]
            dw_ref[pl.ds(tap * _CONV_HALO, _CONV_HALO), :] += _add_up(
                prod[r:r + _CONV_HALO] for r in range(0, tile, _CONV_HALO))
        if db_ref is not None:
            # The bias's gradient is dz's own sum, where the filter's is.
            db_ref[...] += _add_up(
                dz[r:r + _CONV_HALO] for r in range(0, tile, _CONV_HALO))
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // tile, one, None)


def _conv_specs(x, blocks):
    """Over x [B, T, D] and the grid (lanes, batch, a sequence's blocks): the
    grid, a block's BlockSpec, that of the 8 rows before it, and ``after``,
    which gives that of the sublane tile after it in an array of a dtype
    (either clamped into the sequence at its ends); then a filter's, of
    ``rows`` rows. ``block`` and ``after`` told ``heads``, the lanes d of a
    head, are those of an array that lies [B, D / d, T, d]: the same rows of
    the block's lanes / d heads, whole in the last extent."""
    rows, lanes = blocks.rows, blocks.lanes
    grid = (x.shape[2] // lanes, x.shape[0], x.shape[1] // rows)

    def spec(n, at, heads=0):
        if heads:
            return pl.BlockSpec((1, lanes // heads, n, heads),
                                lambda l, b, t: (b, l, at(t), 0))
        return pl.BlockSpec((1, n, lanes), lambda l, b, t: (b, at(t), l))

    def block(heads=0):
        return spec(rows, lambda t: t, heads)

    before = spec(_CONV_HALO, lambda t: jnp.maximum(t * (rows // _CONV_HALO) - 1, 0))

    def after(dtype, heads=0):
        n = _sublanes(dtype)
        return spec(
            n, lambda t: jnp.minimum((t + 1) * (rows // n), x.shape[1] // n - 1), heads)

    def filt(rows):
        return pl.BlockSpec((rows, lanes), lambda l, b, t: (0, l))

    return grid, block, before, after, filt


def _conv_call(kernel, blocks, semantics=("parallel", "arbitrary", "arbitrary"), **kwargs):
    # The interpreter runs a kernel's body as XLA does.
    return pl.pallas_call(
        functools.partial(kernel, tile=blocks.tile,
                          roll=_xla_roll if blocks.interpret else _tpu_roll),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=blocks.interpret, **kwargs)


# One jitted entry a kernel, so that a step's text holds a body once a dtype
# (twice a forward one under remat, whose partial evaluation copies the
# entry's jaxpr) and not a Mosaic lowering a call site: a KDA layer convolves
# three projections, forward, replayed and backward.


# ``b`` is the bias [1, D] or None; with None a call's operands, its kernel's
# body and so its lowered text are what they were before there was one. So
# with ``blocks.heads``: at 0 the output and its cotangent lie as x does, and
# the call is what it was before an output could lie heads first.


@functools.partial(jax.jit, static_argnums=(2, 3))
def _conv_forward(x, w, dtype, blocks, b=None):
    grid, block, before, _, filt = _conv_specs(x, blocks)
    bias = [] if b is None else [b]
    batch, t, width = x.shape
    d = blocks.heads
    return _conv_call(
        _conv_fwd_kernel, blocks, grid=grid,
        in_specs=[block(), before, filt(w.shape[0])] + [filt(1)] * len(bias),
        out_specs=block(d), out_shape=jax.ShapeDtypeStruct(
            (batch, width // d, t, d) if d else x.shape, dtype),
    )(x, x, w, *bias)


@functools.partial(jax.jit, static_argnums=3)
def _conv_backward(x, w, dy, blocks, b=None):
    grid, block, before, after, filt = _conv_specs(x, blocks)
    taps, d = w.shape[0], blocks.heads
    bias = [] if b is None else [b]
    dx, dw, *db = _conv_call(
        _conv_bwd_kernel, blocks, grid=grid,
        in_specs=[block(), before, after(x.dtype), block(d), after(dy.dtype, d), filt(taps)]
        + [filt(1)] * len(bias),
        out_specs=[block(), filt(taps * _CONV_HALO)] + [filt(_CONV_HALO)] * len(bias),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((taps * _CONV_HALO, x.shape[2]), F32)]
        + [jax.ShapeDtypeStruct((_CONV_HALO, x.shape[2]), F32)] * len(bias),
    )(x, x, x, dy, dy, w, *bias)
    dw = dw.reshape(taps, _CONV_HALO, -1).sum(1).astype(w.dtype)
    return (dx, dw) if b is None else (dx, dw, db[0].sum(0, keepdims=True).astype(b.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv_silu_pallas(x, w, dtype, blocks, b=None):
    return _conv_forward(x, w, dtype, blocks, b)


def _conv_silu_fwd(x, w, dtype, blocks, b=None):
    return _conv_forward(x, w, dtype, blocks, b), (x, w, b)


def _conv_silu_bwd(dtype, blocks, residuals, dy):
    x, w, b = residuals
    grads = _conv_backward(x, w, dy, blocks, b)
    return grads if b is not None else (*grads, None)


_conv_silu_pallas.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(x, w, dtype=F32, bias=None, heads=None):
    """``silu(short_conv(x, w) + bias)`` rounded once to ``dtype``: x [B, T,
    D] float32, w [K, D], ``bias`` [D] or None (no term). [B, T, D] where
    ``heads`` is None; told the lanes d of a head, the layout its caller's
    next kernel reads, [B, D / d, T, d]: channel h * d + c at [h, :, c]. On a
    TPU (or under the interpreter) where the shape tiles it is one Pallas pass
    over x forward and one over x and the cotangent backward, which makes the
    pre-activation again from x (the residuals are x, w and the bias) and adds
    the filter's gradient, and the bias's beside it, up in float32; the passes
    write the output and read its cotangent a head at a time where they lie
    heads first, so no transposed copy stands between this kernel and the
    next. Elsewhere it is what this line says (and a transposition), for XLA
    to differentiate."""
    blocks = _conv_blocks(x, w, heads)
    if blocks is None:
        c = short_conv(x, w)
        if bias is not None:
            c = c + bias.astype(F32)
        y = jax.nn.silu(c).astype(dtype)
        if heads:
            y = y.reshape(*y.shape[:2], -1, heads).transpose(0, 2, 1, 3)
        return y
    return _conv_silu_pallas(x.astype(F32), w, jnp.dtype(dtype), blocks,
                             None if bias is None else bias[None])


def l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis, in float32."""
    xf = x.astype(F32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)


def kda_gate(f, a_log, dt_bias):
    """The per-channel log-decay g = -exp(A_log_h) softplus(f + dt_bias),
    float32. f [B, T, H, dk]; a_log [H]; dt_bias [H, dk]."""
    soft = jax.nn.softplus(f.astype(F32) + dt_bias.astype(F32))
    return -jnp.exp(a_log.astype(F32))[:, None] * soft


def _dot(a, b, contract):
    precision = jax.lax.Precision.HIGHEST if a.dtype == F32 else None
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=F32,
        precision=precision,
    )


def _nn(a, b):  # a @ b
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):  # a @ b^T
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):  # a^T @ b
    return _dot(a, b, ((0,), (0,)))


# ------------------------------------------------------- the running sums
def _sum_matrix(dv: int) -> np.ndarray:
    """[(2 * CHUNK + dv), CHUNK] of 0 and 1: the lower triangle with its
    diagonal, then ones. Times g [CHUNK, .] its row blocks are G (the
    running sum of g inside the chunk), G_last on CHUNK rows (beside G) and
    G_last on ``dv`` rows (what scales the state, which lies [dv, dk])."""
    t = np.arange(CHUNK)[:, None]
    j = np.arange(CHUNK)[None, :]
    return np.concatenate(
        [j <= t, np.ones((CHUNK + dv, CHUNK), bool)]
    ).astype(np.float32)


def _tpu_roll(x, shift):
    """``jnp.roll(x, shift, 0)`` inside a compiled kernel: a sublane
    rotation, with the opposite rotation as its transpose."""
    return _rotate(x, shift % x.shape[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rotate(x, shift):
    return pltpu.roll(x, shift, 0)


def _rotate_fwd(x, shift):
    return _rotate(x, shift), None


def _rotate_bwd(shift, _, g):
    return (pltpu.roll(g, (g.shape[0] - shift) % g.shape[0], 0),)


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def _xla_roll(x, shift):
    return jnp.roll(x, shift, 0)


def _split3(x):
    """x as three bfloat16 terms whose sum is x to float32's precision: a
    0/1 matrix times each is exact on the MXU in one pass."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(F32)).astype(jnp.bfloat16)


def _exact_matmul(m, x, contract):
    """m (0/1, bfloat16) contracted with float32 x, exact to float32."""
    return sum(
        jax.lax.dot_general(m, part, (contract, ((), ())),
                            preferred_element_type=F32)
        for part in _split3(x)
    )


# ------------------------------------------- one chunk of the stacked heads


def _rows_cols(n):
    """The row's and the column's index at every entry of an [n, n] matrix."""
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _masks(n):
    """{b: [n, n] bool}: row t and column s lie in one block of 2b rows, t
    in its second half and s in its first (over the six levels, the strict
    lower triangle of every CHUNK x CHUNK diagonal block once), and the
    diagonal. A block of 2b <= CHUNK rows never spans two of the heads
    stacked on the n rows, so every mask is block-diagonal over heads."""
    row, col = _rows_cols(n)
    masks = {}
    for level, b in enumerate(_LEVELS):
        same = (row >> (level + 1)) == (col >> (level + 1))
        masks[b] = same & (((row >> level) & 1) == 1) & (((col >> level) & 1) == 0)
    return masks, row == col


def _same_head(row, col):
    """Rows and columns of stacked heads: of one head's CHUNK."""
    return row // CHUNK == col // CHUNK


def _heads_of(x, p):
    """The p row blocks of x, one a stacked head."""
    r = x.shape[0] // p
    return [x[i * r:(i + 1) * r] for i in range(p)]


def _per_head(dot, a, b, p):
    """``dot`` of each stacked head's rows of a with its rows of b, the
    results stacked again (p = 1: ``dot(a, b)``, nothing sliced or joined)."""
    return jnp.concatenate(
        [dot(x, y) for x, y in zip(_heads_of(a, p), _heads_of(b, p))])


def _doubling(dt, A, masks, eye):
    """(I + A)^-1 of a strictly lower-triangular A [n, n] float32 by
    doubling, in ``dt``: at block size 1 the block diagonal is I, and the
    inverse X of the block diagonal at block size b gives that of 2b as X -
    X E X, operands rounded to ``dt`` at every level."""
    X = jnp.where(eye, 1.0, 0.0) - jnp.where(masks[1], A, 0.0)
    for b in _LEVELS[1:]:
        E = jnp.where(masks[b], A, 0.0).astype(dt)
        X = X - _nn(X.astype(dt), _nn(E, X.astype(dt)).astype(dt))
    return X.astype(dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _unit_lower_inverse(dt, A, masks, eye, T):
    """T = (I + A)^-1 in ``dt``, of the strict lower triangle of every
    head's CHUNK x CHUNK diagonal block of A: ``_doubling``, or ``T`` itself
    where the caller holds the value already (the backward kernel, which
    reads what the forward kernel multiplied with). Either way its
    derivative is the inverse's own and not the chain's: from T (I + A) = I,
    dT = -T dA T, so A's cotangent is -T^T dT T^T, two matmuls that wait for
    nothing, exact for whatever A gave T. Under autodiff the ten matmuls of
    the doubling cost twenty more, most of them waiting for the one before."""
    return _doubling(dt, A, masks, eye) if T is None else T


def _unit_lower_inverse_fwd(dt, A, masks, eye, T):
    T = _unit_lower_inverse(dt, A, masks, eye, T)
    return T, T


def _unit_lower_inverse_bwd(dt, T, dT):
    row, col = _rows_cols(T.shape[0])
    # The cross-head blocks of T^T are exact zeros, so a head's numbers are
    # the sums they are alone; the mask makes what lies outside the entries
    # T was made from an exact zero whatever dT holds there.
    lower = (row > col) & _same_head(row, col)
    dA = _nt(_tn(T, dT.astype(dt)).astype(dt), T)
    return jnp.where(lower, -dA, 0.0), None, None, None


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _head_chunk(St, q, k, v, beta, G, last, last_dv, roll=_xla_roll, T=None):
    """A chunk of P heads stacked on rows (P = 1: of one head). St [P * dv,
    dk] float32, the states at its start, each transposed so that the decay
    scales lanes; q, k [P * C, dk]; v [P * C, dv]; beta [P * C, 1] float32;
    G [P * C, dk] the running sum of g, ``last`` its last row on C rows a
    head and ``last_dv`` on dv rows a head; ``T`` the chunk's inverse where
    the caller has it (else it is made here). -> (the states at its end, o
    [P * C, dv], T [P * C, P * C] in the matmuls' dtype).

    Every [P * C, P * C] matrix below (A, Aqk, each E and X, T) is
    block-diagonal over heads, its cross-head blocks exact zeros: the masks
    are, a roll by b < C brings a row of another head only to rows whose
    select takes the other value, and a product of block-diagonal matrices
    is one. So a head's numbers are the sums they are alone, plus zeros.
    Only the state's three products are a head's own."""
    dt = q.dtype
    n = q.shape[0]
    p = n // CHUNK
    masks, eye = _masks(n)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    kf, qf = k.astype(F32), q.astype(F32)
    A = Aqk = jnp.zeros((n, n), F32)
    start = G  # G at the first row of every row's block of b rows
    for level, b in enumerate(_LEVELS):
        second = ((rows >> level) & 1) == 1
        to_row = jnp.exp(G - start) if b > 1 else 1.0
        # For a row of a first half: from it to the first row of the second
        # half. (The rows of a second half are masked out as columns.)
        ahead = jnp.where(second, 0.0, jnp.minimum(roll(start, -b) - G, 0.0))
        ks = (kf * jnp.exp(ahead)).astype(dt)
        A = A + jnp.where(masks[b], _nt((kf * to_row).astype(dt), ks), 0.0)
        Aqk = Aqk + jnp.where(masks[b], _nt((qf * to_row).astype(dt), ks), 0.0)
        start = jnp.where(second, roll(start, b), start)
    Aqk = Aqk + jnp.where(eye, _nt(q, k), 0.0)
    A = A * beta
    T = _unit_lower_inverse(dt, A, masks, eye, T)
    w = _nn(T, (kf * jnp.exp(G) * beta).astype(dt)).astype(dt)
    u0 = _nn(T, (v.astype(F32) * beta).astype(dt))
    sd = St.astype(dt)
    u = (u0 - _per_head(_nt, w, sd, p)).astype(dt)
    o = _per_head(_nt, (qf * jnp.exp(G)).astype(dt), sd, p) + _nn(Aqk.astype(dt), u)
    kd = (kf * jnp.exp(last - G)).astype(dt)
    return jnp.exp(last_dv) * St + _per_head(_tn, u, kd, p), o, T


def _normed_qk(q, k, dt, scale, l2_eps):
    """The raw float32 q and k of a chunk as the products take them:
    ``l2norm(q) * scale`` and ``l2norm(k)``, each rounded once to ``dt``."""
    return (l2norm(q, l2_eps) * scale).astype(dt), l2norm(k, l2_eps).astype(dt)


def _rms_normed(o, rms_eps):
    """The float32 o of a chunk over its root mean square, a row (a head's
    channels of one token) at a time."""
    return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + rms_eps)


def _normed_chunk(St, q, k, v, beta, G, last, last_dv, gate, weight, *, norm,
                  roll=_xla_roll, T=None):
    """``_head_chunk`` between the mixer's normalisations, each over a row
    (a head's channels of one token) in float32. q and k come as the
    convolution and SiLU leave them: L2-normalised, q scaled, then the one
    rounding to the matmuls' dtype, which is v's. o leaves through the
    per-head RMSNorm (``weight`` [1, dv] float32) and the output gate (``gate`` [P *
    C, dv], before its sigmoid), still float32. ``norm`` is (q's scale, the
    L2 norm's epsilon, the RMSNorm's). Differentiated as one function, so
    the cotangents are those of the raw q and k, of the gate and of the
    weight."""
    scale, l2_eps, rms_eps = norm
    q, k = _normed_qk(q, k, v.dtype, scale, l2_eps)
    St, o, T = _head_chunk(St, q, k, v, beta, G, last, last_dv, roll, T)
    return St, _rms_normed(o, rms_eps) * weight * jax.nn.sigmoid(gate.astype(F32)), T


# ------------------------------------------------------------ Pallas kernels
# Grid (batch, chunk, heads // P), the heads innermost and P of them a
# step: every head's state stays in VMEM over a sequence's chunks, and the
# running sums of all heads are taken once a chunk, at its first step.


def _stack(x, p):
    """[r, p * d] -> [p * r, d]: p heads from lanes to rows, whole vregs
    where d is a multiple of 128."""
    d = x.shape[1] // p
    return jnp.concatenate([x[:, i * d:(i + 1) * d] for i in range(p)])


def _unstack(x, p):
    """[p * r, d] -> [r, p * d]: ``_stack`` undone."""
    return jnp.concatenate(_heads_of(x, p), axis=1)


def _step_sums(d_scr, p, dk, dv):
    """Step ``program_id(2)``'s G, G_last on CHUNK rows and G_last on dv
    rows: row blocks of its heads' ``p * dk`` lanes in the [2 * CHUNK + dv,
    H * dk] scratch of running sums, as index tuples."""
    lanes = pl.ds(pl.multiple_of(pl.program_id(2) * p * dk, p * dk), p * dk)
    return [(pl.ds(0, CHUNK), lanes), (pl.ds(CHUNK, CHUNK), lanes),
            (pl.ds(2 * CHUNK, dv), lanes)]


def _roll_here():
    """The interpreter runs a kernel's body as XLA does."""
    return _xla_roll if _attention._interpret() else _tpu_roll


def _take_sums(m_ref, g_ref, d_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_scr[...] = _exact_matmul(m_ref[...], g_ref[0], ((1,), (0,)))


def _stacked(refs, beta_ref, d_scr, sums, p):
    """A step's operands of ``_head_chunk`` after the states: q, k, v from
    ``refs``, beta and the three running sums, its p heads on rows."""
    return (*(_stack(ref[0], p) for ref in refs),
            jnp.concatenate([beta_ref[0, i] for i in range(p)]),
            *(_stack(d_scr[at], p) for at in sums))


def _diagonal(T, p):
    """[p * C, p * C] block-diagonal over heads -> [C, p * C], the heads'
    diagonal blocks side by side on lanes: the sum of T's row blocks, each
    entry one of T's plus exact zeros."""
    return sum(_heads_of(T, p)) if p > 1 else T


def _block_diagonal(D, p):
    """``_diagonal`` undone: D on every head's rows, zeros between heads."""
    if p == 1:
        return D
    same = _same_head(*_rows_cols(p * CHUNK))
    return jnp.where(same, jnp.concatenate([D] * p), jnp.zeros((), D.dtype))


def _kda_fwd_kernel(m_ref, g_ref, q_ref, k_ref, v_ref, beta_ref, gate_ref,
                    w_ref, o_ref, *rest, norm):
    # rest: (the states' and the inverses' outputs, the two scratches) or the
    # scratches alone.
    s_ref, t_ref, d_scr, st_scr = rest if len(rest) == 4 else (None, None, *rest)
    step, p = pl.program_id(2), beta_ref.shape[1]
    dv, dk = st_scr.shape[1] // p, st_scr.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        st_scr[step] = jnp.zeros((p * dv, dk), F32)

    _take_sums(m_ref, g_ref, d_scr)
    St = st_scr[step]
    if s_ref is not None:
        s_ref[0, 0] = _unstack(St, p)
    st_scr[step], o, T = _normed_chunk(
        St, *_stacked((q_ref, k_ref, v_ref), beta_ref, d_scr,
                      _step_sums(d_scr, p, dk, dv), p),
        _stack(gate_ref[0], p), w_ref[...], norm=norm, roll=_roll_here(),
    )
    o_ref[0] = _unstack(o, p).astype(o_ref.dtype)
    if t_ref is not None:
        t_ref[0, 0, 0] = _diagonal(T, p)


def _kda_bwd_kernel(m_ref, mt_ref, g_ref, q_ref, k_ref, v_ref, beta_ref,
                    gate_ref, w_ref, s_ref, t_ref, do_ref, dq_ref, dk_ref,
                    dv_ref, dbeta_ref, dg_ref, dgate_ref, dw_ref, d_scr,
                    dd_scr, dst_scr, *, norm):
    step, p = pl.program_id(2), beta_ref.shape[1]
    dv, dk = dst_scr.shape[1] // p, dst_scr.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dst_scr[step] = jnp.zeros((p * dv, dk), F32)

    # The weight's cotangent adds up over a batch row's steps in its output
    # block, which stays in VMEM while the block's index (the row) stands.
    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _init_dw():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    _take_sums(m_ref, g_ref, d_scr)
    sums = _step_sums(d_scr, p, dk, dv)
    chunk = functools.partial(_normed_chunk, norm=norm, roll=_roll_here(),
                              T=_block_diagonal(t_ref[0, 0, 0], p))
    _, vjp = jax.vjp(
        lambda *operands: chunk(*operands)[:2],
        _stack(s_ref[0, 0], p),
        *_stacked((q_ref, k_ref, v_ref), beta_ref, d_scr, sums, p),
        _stack(gate_ref[0], p), w_ref[...],
    )
    dst, dq, dk_, dv_, dbeta, *dsums, dgate, dw = vjp(
        (dst_scr[step], _stack(do_ref[0], p).astype(F32))
    )
    dst_scr[step] = dst
    dq_ref[0] = _unstack(dq, p).astype(dq_ref.dtype)
    dk_ref[0] = _unstack(dk_, p).astype(dk_ref.dtype)
    dv_ref[0] = _unstack(dv_, p).astype(dv_ref.dtype)
    dgate_ref[0] = _unstack(dgate, p).astype(dgate_ref.dtype)
    dw_ref[0] += dw
    for i, part in enumerate(_heads_of(dbeta, p)):
        dbeta_ref[0, i] = part
    for at, ds in zip(sums, dsums):
        dd_scr[at] = _unstack(ds, p)

    @pl.when(step == pl.num_programs(2) - 1)
    def _dg():
        dg_ref[0] = _exact_matmul(mt_ref[...], dd_scr[...], ((1,), (0,)))


def _heads_a_step(heads):
    """Two where the heads pair off (a [128, .] operand fills the MXU's
    rows), else one."""
    return 1 if heads % _PAIR else _PAIR


def _specs(heads, dk, dv, chunk_of):
    """BlockSpecs over grid (batch, step, heads // p), ``chunk_of(step)`` the
    chunk a step works on. Rows lie [B, T, H * d]: a block is one chunk's
    rows of p heads' lanes, or of all heads' (g, whose running sums are
    taken for all heads at once). beta lies [B, H, T, 1], the states [B, N,
    dv, H * dk] and the chunks' inverses [B, N, H / p, CHUNK, p * CHUNK], a
    step's block whole in its last two extents whatever p is. With them the
    grid's last extent (``steps``) and the scratch that holds every head's
    state, p heads stacked a step."""
    p = _heads_a_step(heads)

    def rows(d):
        return pl.BlockSpec((1, CHUNK, p * d), lambda b, n, h: (b, chunk_of(n), h))

    whole = lambda *shape: pl.BlockSpec(shape, lambda b, n, h: (0,) * len(shape))  # noqa: E731
    return {
        "k": rows(dk), "v": rows(dv),
        "g": pl.BlockSpec((1, CHUNK, heads * dk),
                          lambda b, n, h: (b, chunk_of(n), 0)),
        "beta": pl.BlockSpec((1, p, CHUNK, 1),
                             lambda b, n, h: (b, h, chunk_of(n), 0)),
        "state": pl.BlockSpec((1, 1, dv, p * dk),
                              lambda b, n, h: (b, chunk_of(n), 0, h)),
        "inverse": pl.BlockSpec((1, 1, 1, CHUNK, p * CHUNK),
                                lambda b, n, h: (b, chunk_of(n), h, 0, 0)),
        "m": whole(2 * CHUNK + dv, CHUNK), "mt": whole(CHUNK, 2 * CHUNK + dv),
        "weight": whole(1, dv),
        "dweight": pl.BlockSpec((1, 1, dv), lambda b, n, h: (b, 0, 0)),
        "steps": heads // p,
        "states": pltpu.VMEM((heads // p, p * dv, dk), F32),
    }


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


# Every scan kernel's call below stands behind ``kernel_entry``: a model's
# layers of one mixer are one shape, and each calls its forward kernel in the
# forward pass and in the replay and its backward kernel, whose body
# differentiates a chunk where it stands, once. A delta-rule trace follows
# how its heads pair and how the backward kernel takes the inverse.
def _pairing():
    return (_PAIR, _block_diagonal)


@_attention.kernel_entry("heads", "norm", "states", reads=_pairing)
def _forward_pallas(q, k, v, g, beta, gate, weight, heads, norm, states):
    batch, t, _ = q.shape
    dk, dv, n = q.shape[2] // heads, v.shape[2] // heads, t // CHUNK
    s = _specs(heads, dk, dv, lambda i: i)
    m = jnp.asarray(_sum_matrix(dv), jnp.bfloat16)
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, norm=norm),
        grid=(batch, n, s["steps"]),
        in_specs=[s["m"], s["g"], s["k"], s["k"], s["v"], s["beta"], s["v"],
                  s["weight"]],
        out_specs=[s["v"], s["state"], s["inverse"]][:1 + 2 * states],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((batch, n, dv, heads * dk), F32),
            jax.ShapeDtypeStruct(
                (batch, n, s["steps"], CHUNK, _heads_a_step(heads) * CHUNK), v.dtype),
        ][:1 + 2 * states],
        scratch_shapes=[pltpu.VMEM((m.shape[0], heads * dk), F32), s["states"]],
        compiler_params=_params(),
        interpret=_attention._interpret(),
    )(m, g, q, k, v, beta, gate, weight)


@_attention.kernel_entry("heads", "norm", reads=_pairing)
def _backward_pallas(q, k, v, g, beta, gate, weight, states, inverses, do,
                     heads, norm):
    batch, t, _ = q.shape
    dk, dv, n = q.shape[2] // heads, v.shape[2] // heads, t // CHUNK
    s = _specs(heads, dk, dv, lambda i: n - 1 - i)
    m = _sum_matrix(dv)
    sums = pltpu.VMEM((m.shape[0], heads * dk), F32)
    return pl.pallas_call(
        functools.partial(_kda_bwd_kernel, norm=norm),
        grid=(batch, n, s["steps"]),
        in_specs=[s["m"], s["mt"], s["g"], s["k"], s["k"], s["v"], s["beta"],
                  s["v"], s["weight"], s["state"], s["inverse"], s["v"]],
        out_specs=[s["k"], s["k"], s["v"], s["beta"], s["g"], s["v"],
                   s["dweight"]],
        out_shape=[
            *(jax.ShapeDtypeStruct(x.shape, x.dtype)
              for x in (q, k, v, beta, g, gate)),
            jax.ShapeDtypeStruct((batch, *weight.shape), F32),
        ],
        scratch_shapes=[sums, sums, s["states"]],
        compiler_params=_params(),
        interpret=_attention._interpret(),
    )(jnp.asarray(m, jnp.bfloat16), jnp.asarray(m.T, jnp.bfloat16),
      g, q, k, v, beta, gate, weight, states, inverses, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _kda_pallas(q, k, v, g, beta, gate, weight, heads, norm):
    """The gated, normalised o [B, T, H * dv] from q, k, g [B, T, H * dk], v
    and the gate [B, T, H * dv], beta [B, H, T, 1] and the norm's weight [1,
    dv], T a whole number of chunks; q and k raw, ``norm`` as
    ``_normed_chunk`` takes it.

    Called outside a gradient it writes neither the states nor the chunks'
    inverses, because nobody reads them. Under a gradient the forward rule
    writes both and names them and o (``checkpoint_name``): a remat policy
    that keeps the three names (models/llama.py REPLAY_KEEPS) holds a
    layer's 512 MiB of states, 64 MiB of inverses and 128 MiB of o (at 16k
    tokens and 32 heads of 128) from the forward pass to its backward and
    its replay runs no forward kernel; one that lacks any of them runs the
    kernel again in the replay, and the states and inverses live for that
    layer's backward pass alone."""
    return _forward_pallas(q, k, v, g, beta, gate, weight, heads, norm,
                           states=False)[0]


def _kda_pallas_fwd(q, k, v, g, beta, gate, weight, heads, norm):
    o, states, inverses = _forward_pallas(q, k, v, g, beta, gate, weight, heads,
                                          norm, states=True)
    # Named for the remat policy (models/llama.py REPLAY_KEEPS): the
    # backward reads the states and the inverses, and o is kept with them
    # because a replay that has to make o runs this kernel whatever else it
    # holds.
    o, states = checkpoint_name(o, "kda_o"), checkpoint_name(states, "kda_states")
    inverses = checkpoint_name(inverses, "kda_t")
    return o, (q, k, v, g, beta, gate, weight, states, inverses)


def _kda_pallas_bwd(heads, norm, residuals, do):
    dq, dk, dv, dbeta, dg, dgate, dw = _backward_pallas(
        *residuals, do.astype(residuals[2].dtype), heads, norm
    )
    return dq, dk, dv, dg, dbeta, dgate, dw.sum(0)


_kda_pallas.defvjp(_kda_pallas_fwd, _kda_pallas_bwd)


def _kda_xla(q, k, v, g, beta, gate, weight, heads, norm):
    """The same function of the same layouts under ``lax.scan``, for JAX to
    differentiate: where there is no TPU."""
    batch, t, _ = q.shape
    n = t // CHUNK
    dv = v.shape[2] // heads
    m = jnp.asarray(_sum_matrix(dv))

    def chunks(x):  # [B, T, H * d] -> [N, B, H, C, d]
        return x.reshape(batch, n, CHUNK, heads, -1).transpose(1, 0, 3, 2, 4)

    sums = jnp.einsum("rc,nbhcd->nbhrd", m, chunks(g),
                      precision=jax.lax.Precision.HIGHEST)
    beta = beta.reshape(batch, heads, n, CHUNK, 1).transpose(2, 0, 1, 3, 4)

    def one(St, q, k, v, beta, d, gate):
        return _normed_chunk(St, q, k, v, beta, d[:CHUNK], d[CHUNK:2 * CHUNK],
                             d[2 * CHUNK:], gate, weight, norm=norm)[:2]

    def step(St, chunk):
        return jax.vmap(jax.vmap(one))(St, *chunk)

    start = jnp.zeros((batch, heads, dv, q.shape[2] // heads), F32)
    _, o = jax.lax.scan(
        step, start, (chunks(q), chunks(k), chunks(v), beta, sums, chunks(gate)))
    return o.transpose(1, 0, 3, 2, 4).reshape(batch, t, heads * dv).astype(v.dtype)


def chunk_kda(q, k, v, g, beta, gate, weight, *, scale, rms_eps, l2_eps=1e-6):
    """A KDA mixer from its convolutions' outputs to its output projection's
    input: the recurrence at the top of this file, chunked, of ``l2norm(q,
    l2_eps) * scale`` and ``l2norm(k, l2_eps)``, then o's RMSNorm over a
    head's channels (``weight`` [dv], ``rms_eps`` inside the root) times
    ``sigmoid(gate)``. Nobody normalises outside: q, k [B, T, H, dk] come
    raw, as the convolution and SiLU leave them (float32), and the kernels
    normalise the blocks they load, in float32, with one rounding to v's
    dtype, the matmuls'; o is normalised and gated where the forward kernel
    holds it in float32 and rounded once, to v's dtype, as it is stored. v,
    gate [B, T, H, dv], the gate before its sigmoid; g [B, T, H, dk] float32
    log-decay (<= 0); beta [B, T, H] in (0, 1) or, where the mixer doubles
    its sigmoid, in (0, 2). Returns [B, T, H, dv].
    Differentiable in all seven; the cotangents of q and k are those of the
    raw ones."""
    batch, t, heads, _ = q.shape
    pad = -t % CHUNK
    if pad:
        # Padding tokens write nothing (beta 0) and decay nothing (g 0).
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v, g, gate = (jnp.pad(x, widths) for x in (q, k, v, g, gate))
        beta = jnp.pad(beta, widths[:3])
    flat = lambda x: x.reshape(batch, t + pad, -1)  # noqa: E731
    beta = beta.astype(F32).transpose(0, 2, 1)[..., None]  # [B, H, T', 1]
    run = _kda_pallas if _attention._on_tpu() or _attention._interpret() else _kda_xla
    o = run(flat(q), flat(k), flat(v), flat(g.astype(F32)), beta, flat(gate),
            weight.astype(F32)[None], heads, (scale, l2_eps, rms_eps))
    return o.reshape(batch, t + pad, heads, -1)[:, :t]


# ------------------------------------------- the scalar decay (Gated DeltaNet)
# The same rule with one log-decay g a head and token where KDA has one a
# channel, and key and value heads of widths of their own (dk != dv):
#
#     S_t = (I - b_t k_t k_t^T) e^{g_t} S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t
#
# With a scalar the chunk's decays are one [C, C] matrix a head, exp(G_t -
# G_s) for s <= t with G the running sum of g inside the chunk: the exponent
# is a difference of two sums of non-positive terms, masked to zero above the
# diagonal before it is taken, so it is never positive and A and Aqk are one
# product each (k k^T, q k^T) times that matrix, where the per-channel form
# needs six level products each. g, its running sum and its cotangent stay
# [B, H, T, 1]: no copy of the decay over a head's channels exists in HBM.
#
# Layout: every operand lies [B, H, T, d], a grid step's block p heads' chunk
# whole in its last extent, so that a head dim that fills no vreg (96) or one
# and a half (192) tiles without a lane slice; the mixer's [B, T, H, d] are
# transposed around the call. The running sum is six sublane rolls and adds
# on a lane-broadcast copy of g, its row form one transposition; nothing of
# it is a matmul. Everything after the decays is ``_head_chunk``'s: the
# inverse by ``_unit_lower_inverse`` (handed T in the backward kernel), W, U0,
# and the state's three products a head.
_LANES = 128


def _running_sums(g, p, roll, chunk=CHUNK):
    """g [p * C, 1] float32, p heads' chunk of log-decays on rows -> (G [p *
    C, _LANES], the running sum inside each head's chunk on every lane; tot,
    a list of p [1, _LANES]: each head's sum over the chunk). A g of _LANES
    columns is as many sequences, one a lane (``_ssd_chunk``: a lane a head).
    ``chunk`` is C, a power of two."""
    n = g.shape[0]
    gb = jnp.broadcast_to(g, (n, _LANES))
    at = jax.lax.broadcasted_iota(jnp.int32, (n, _LANES), 0) % chunk
    G = gb
    for b in (1 << level for level in range(chunk.bit_length() - 1)):
        G = G + jnp.where(at >= b, roll(G, b), 0.0)
    return G, [jnp.sum(part, 0, keepdims=True) for part in _heads_of(gb, p)]


def _row_form(G, n):
    """G [n, _LANES], every lane of a row the same -> [n, n] with entry (t,
    s) = G[s]: one transposition of a [_LANES, _LANES] tile."""
    tile = jnp.concatenate([G] * (_LANES // n)) if n < _LANES else G
    return tile.T[:n, :n]


def _lanes(x, d):
    """x [r, _LANES], every lane of a row the same, as [r, d]."""
    if d <= _LANES:
        return x[:, :d]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], d))


def _rows_of(tot, rows, lanes):
    """The heads' [1, _LANES] values, each on ``rows`` rows, stacked."""
    return jnp.concatenate(
        [jnp.broadcast_to(_lanes(t, lanes), (rows, lanes)) for t in tot])


def _gdn_head_chunk(St, q, k, v, beta, g, roll=_xla_roll, T=None):
    """``_head_chunk`` with a scalar decay: St [P * dv, dk] float32, q, k [P *
    C, dk], v [P * C, dv], beta and g [P * C, 1] float32, g the log-decays
    themselves. -> (the states at the chunk's end, o [P * C, dv], T)."""
    dt = q.dtype
    n, dk = q.shape
    p = n // CHUNK
    dv = v.shape[1]
    masks, eye = _masks(n)
    row, col = _rows_cols(n)
    lower = (row >= col) & _same_head(row, col)
    G, tot = _running_sums(g, p, roll)
    # exp(G_t - G_s) for s <= t of one head: the exponent is zero elsewhere
    # before it is taken, and non-positive where it stands.
    Gn = _lanes(G, n)
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, jnp.minimum(Gn - _row_form(G, n), 0.0), 0.0)), 0.0)
    kf, qf = k.astype(F32), q.astype(F32)
    A = jnp.where(eye, 0.0, _nt(k, k) * decay) * beta
    Aqk = _nt(q, k) * decay
    T = _unit_lower_inverse(dt, A, masks, eye, T)
    to_row = jnp.exp(_lanes(G, dk))
    w = _nn(T, (kf * to_row * beta).astype(dt)).astype(dt)
    u0 = _nn(T, (v.astype(F32) * beta).astype(dt))
    sd = St.astype(dt)
    u = (u0 - _per_head(_nt, w, sd, p)).astype(dt)
    o = _per_head(_nt, (qf * to_row).astype(dt), sd, p) + _nn(Aqk.astype(dt), u)
    # From a row to the chunk's end: the sum of the g after it.
    ahead = jnp.minimum(_rows_of(tot, CHUNK, dk) - _lanes(G, dk), 0.0)
    kd = (kf * jnp.exp(ahead)).astype(dt)
    return jnp.exp(_rows_of(tot, dv, dk)) * St + _per_head(_tn, u, kd, p), o, T


def _normed_gdn_chunk(St, q, k, v, beta, g, gate, weight, *, norm,
                      roll=_xla_roll, T=None):
    """``_gdn_head_chunk`` between the mixer's normalisations, as
    ``_normed_chunk`` has them, with the Gated DeltaNet's output gate: o
    leaves through the per-head RMSNorm times SiLU(gate)."""
    scale, l2_eps, rms_eps = norm
    q, k = _normed_qk(q, k, v.dtype, scale, l2_eps)
    St, o, T = _gdn_head_chunk(St, q, k, v, beta, g, roll, T)
    return St, _rms_normed(o, rms_eps) * weight * jax.nn.silu(gate.astype(F32)), T


def _heads_on_rows(ref, *lead):
    """A block [1, .., p, r, d] of p heads -> [p * r, d], the heads on rows:
    at the leading indices ``lead``, zeros where none are given."""
    lead = lead or (0,) * (len(ref.shape) - 3)
    return jnp.concatenate([ref[(*lead, i)] for i in range(ref.shape[-3])])


def _write_heads(ref, x, *lead):
    """``_heads_on_rows`` undone into a block [1, .., p, r, d]."""
    lead = lead or (0,) * (len(ref.shape) - 3)
    for i, part in enumerate(_heads_of(x, ref.shape[-3])):
        ref[(*lead, i)] = part.astype(ref.dtype)


def _lanes_on_rows(ref, p):
    """``_heads_on_rows`` of a block that lies tokens first, [1, r, p * d],
    the p heads side by side on lanes; of a block [1, p, r, d] as it is."""
    if len(ref.shape) == 4:
        return _heads_on_rows(ref)
    x = ref[0]
    d = x.shape[1] // p
    return jnp.concatenate([x[:, i * d:(i + 1) * d] for i in range(p)])


def _write_lanes(ref, x, p):
    """``_lanes_on_rows`` undone, into either block."""
    if len(ref.shape) == 4:
        return _write_heads(ref, x)
    ref[0] = jnp.concatenate(_heads_of(x, p), axis=1).astype(ref.dtype)


def _gdn_operands(qk_ref, v_ref, beta_ref, g_ref, gate_ref):
    """A step's blocks as ``_normed_gdn_chunk`` takes them, heads on rows: q
    and k from the one block [1, 2, p, C, dk] of both, beta and g from theirs
    [1, p, C, 1], v and the gate from theirs, which may lie tokens first."""
    p = beta_ref.shape[1]
    return (_heads_on_rows(qk_ref, 0, 0), _heads_on_rows(qk_ref, 0, 1),
            _lanes_on_rows(v_ref, p), _heads_on_rows(beta_ref),
            _heads_on_rows(g_ref), _lanes_on_rows(gate_ref, p))


def _gdn_fwd_kernel(g_ref, qk_ref, v_ref, beta_ref, gate_ref, w_ref,
                    o_ref, *rest, norm):
    # rest: (the states' and the inverses' outputs, the scratch) or the
    # scratch alone.
    s_ref, t_ref, st_scr = rest if len(rest) == 3 else (None, None, *rest)
    step, p = pl.program_id(2), beta_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        st_scr[step] = jnp.zeros(st_scr.shape[1:], F32)

    St = st_scr[step]
    if s_ref is not None:
        _write_heads(s_ref, St)
    st_scr[step], o, T = _normed_gdn_chunk(
        St, *_gdn_operands(qk_ref, v_ref, beta_ref, g_ref, gate_ref),
        w_ref[...], norm=norm, roll=_roll_here(),
    )
    _write_lanes(o_ref, o, p)
    if t_ref is not None:
        t_ref[0, 0, 0] = _diagonal(T, p)


def _gdn_bwd_kernel(g_ref, qk_ref, v_ref, beta_ref, gate_ref, w_ref,
                    s_ref, t_ref, do_ref, dqk_ref, dv_ref, dbeta_ref,
                    dg_ref, dgate_ref, dw_ref, dst_scr, *, norm):
    step, p = pl.program_id(2), beta_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dst_scr[step] = jnp.zeros(dst_scr.shape[1:], F32)

    # The weight's cotangent adds up over a batch row's steps in its output
    # block, which stays in VMEM while the block's index (the row) stands.
    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _init_dw():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    chunk = functools.partial(_normed_gdn_chunk, norm=norm, roll=_roll_here(),
                              T=_block_diagonal(t_ref[0, 0, 0], p))
    _, vjp = jax.vjp(
        lambda *operands: chunk(*operands)[:2],
        _heads_on_rows(s_ref),
        *_gdn_operands(qk_ref, v_ref, beta_ref, g_ref, gate_ref),
        w_ref[...],
    )
    dst_scr[step], dq, dk_, dv_, dbeta, dg, dgate, dw = vjp(
        (dst_scr[step], _lanes_on_rows(do_ref, p).astype(F32))
    )
    _write_heads(dqk_ref, dq, 0, 0)
    _write_heads(dqk_ref, dk_, 0, 1)
    _write_heads(dbeta_ref, dbeta)
    _write_heads(dg_ref, dg)
    _write_lanes(dv_ref, dv_, p)
    _write_lanes(dgate_ref, dgate, p)
    dw_ref[0] += dw


def _values_lie_tokens_first(heads, dv):
    """Whether v, the gate and o (and their cotangents) go through the scan's
    kernels as [B, T, H * dv], as the convolution and the matmuls that write
    and read them have them: where a grid step's heads are whole vregs of
    lanes side by side (two of 192 are three). A block [64, 384] lies in HBM
    and in VMEM as it is; two of [64, 192] are padded to 256 lanes each."""
    return (_heads_a_step(heads) * dv) % _LANES == 0


def _gdn_specs(heads, dk, dv, chunk_of, tokens_first=False):
    """BlockSpecs over grid (batch, step, heads // p), as ``_specs``, for
    operands that lie [B, H, T, d]: a block is p heads' chunk, whole in its
    last extent (d, or 1 for beta and g). q and k are one array [B, 2, H, T,
    dk], as their one convolution writes them, and one block of both. v, the
    gate and o lie [B, H, T, dv] too or, ``tokens_first``, [B, T, H * dv]: a
    block is a chunk's rows of the p heads' lanes. The states lie [B, N, H,
    dv, dk]; the chunks' inverses and the norm's weight are ``_specs``'s."""
    p = _heads_a_step(heads)
    shared = _specs(heads, dk, dv, chunk_of)

    def rows(d):
        return pl.BlockSpec((1, p, CHUNK, d), lambda b, n, h: (b, h, chunk_of(n), 0))

    return {
        "qk": pl.BlockSpec((1, 2, p, CHUNK, dk),
                           lambda b, n, h: (b, 0, h, chunk_of(n), 0)),
        "scalar": rows(1),
        "v": pl.BlockSpec((1, CHUNK, p * dv), lambda b, n, h: (b, chunk_of(n), h))
        if tokens_first else rows(dv),
        "state": pl.BlockSpec((1, 1, p, dv, dk),
                              lambda b, n, h: (b, chunk_of(n), h, 0, 0)),
        "inverse": shared["inverse"], "weight": shared["weight"],
        "dweight": shared["dweight"], "steps": shared["steps"],
        "states": shared["states"],
    }


@_attention.kernel_entry("norm", "states", reads=_pairing)
def _gdn_forward_pallas(qk, v, g, beta, gate, weight, norm, states):
    batch, _, heads, t, dk = qk.shape
    dv, n = weight.shape[1], t // CHUNK
    s = _gdn_specs(heads, dk, dv, lambda i: i, v.ndim == 3)
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, norm=norm),
        grid=(batch, n, s["steps"]),
        in_specs=[s["scalar"], s["qk"], s["v"], s["scalar"], s["v"], s["weight"]],
        out_specs=[s["v"], s["state"], s["inverse"]][:1 + 2 * states],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((batch, n, heads, dv, dk), F32),
            jax.ShapeDtypeStruct(
                (batch, n, s["steps"], CHUNK, _heads_a_step(heads) * CHUNK), v.dtype),
        ][:1 + 2 * states],
        scratch_shapes=[s["states"]],
        compiler_params=_params(),
        interpret=_attention._interpret(),
    )(g, qk, v, beta, gate, weight)


@_attention.kernel_entry("norm", reads=_pairing)
def _gdn_backward_pallas(qk, v, g, beta, gate, weight, states, inverses, do,
                         norm):
    batch, _, heads, t, dk = qk.shape
    dv, n = weight.shape[1], t // CHUNK
    s = _gdn_specs(heads, dk, dv, lambda i: n - 1 - i, v.ndim == 3)
    return pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, norm=norm),
        grid=(batch, n, s["steps"]),
        in_specs=[s["scalar"], s["qk"], s["v"], s["scalar"], s["v"],
                  s["weight"], s["state"], s["inverse"], s["v"]],
        out_specs=[s["qk"], s["v"], s["scalar"], s["scalar"], s["v"],
                   s["dweight"]],
        out_shape=[
            *(jax.ShapeDtypeStruct(x.shape, x.dtype)
              for x in (qk, v, beta, g, gate)),
            jax.ShapeDtypeStruct((batch, *weight.shape), F32),
        ],
        scratch_shapes=[s["states"]],
        compiler_params=_params(),
        interpret=_attention._interpret(),
    )(g, qk, v, beta, gate, weight, states, inverses, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gdn_pallas(qk, v, g, beta, gate, weight, norm):
    """The gated, normalised o from q and k [B, 2, H, T, dk] raw, g and beta
    [B, H, T, 1], the norm's weight [1, dv], and v and the gate, which o lies
    as: both [B, H, T, dv] or both, tokens first, [B, T, H * dv]; T a whole
    number of chunks. As ``_kda_pallas``: outside a gradient neither states
    nor inverses are written; under one the forward rule writes and names
    both and o (``gdn_o``, ``gdn_states``, ``gdn_t``: models/llama.py
    REPLAY_KEEPS), so that a replay which keeps the three runs no forward
    kernel."""
    return _gdn_forward_pallas(qk, v, g, beta, gate, weight, norm, states=False)[0]


def _gdn_pallas_fwd(qk, v, g, beta, gate, weight, norm):
    o, states, inverses = _gdn_forward_pallas(qk, v, g, beta, gate, weight,
                                              norm, states=True)
    o, states = checkpoint_name(o, "gdn_o"), checkpoint_name(states, "gdn_states")
    inverses = checkpoint_name(inverses, "gdn_t")
    return o, (qk, v, g, beta, gate, weight, states, inverses)


def _gdn_pallas_bwd(norm, residuals, do):
    dqk, dv, dbeta, dg, dgate, dw = _gdn_backward_pallas(
        *residuals, do.astype(residuals[1].dtype), norm
    )
    return dqk, dv, dg, dbeta, dgate, dw.sum(0)


_gdn_pallas.defvjp(_gdn_pallas_fwd, _gdn_pallas_bwd)


def _gdn_xla(qk, v, g, beta, gate, weight, norm):
    """The same function of the same layouts under ``lax.scan``, one head a
    call, for JAX to differentiate: where there is no TPU."""
    q, k = qk[:, 0], qk[:, 1]
    batch, heads, t, dk = q.shape

    def chunks(x):  # [B, H, T, d] -> [N, B, H, C, d]
        return jnp.moveaxis(x.reshape(batch, heads, t // CHUNK, CHUNK, -1), 2, 0)

    def one(St, *operands):
        return _normed_gdn_chunk(St, *operands, weight, norm=norm)[:2]

    def step(St, chunk):
        return jax.vmap(jax.vmap(one))(St, *chunk)

    _, o = jax.lax.scan(
        step, jnp.zeros((batch, heads, v.shape[3], dk), F32),
        tuple(chunks(x) for x in (q, k, v, beta, g, gate)))
    return jnp.moveaxis(o, 0, 2).reshape(v.shape).astype(v.dtype)


def chunk_gdn(qk, v, g, beta, gate, weight, *, scale, rms_eps, l2_eps=1e-6):
    """A Gated DeltaNet mixer from its convolutions' outputs to its output
    projection's input, as ``chunk_kda`` is KDA's: the scalar-decay
    recurrence above, chunked, of ``l2norm(q, l2_eps) * scale`` and
    ``l2norm(k, l2_eps)``, then o's RMSNorm over a head's channels
    (``weight`` [dv]) times ``silu(gate)``. q and k come as their one
    convolution writes them for the kernels, heads first (a key head of 96
    lanes is no whole number of vregs, nor are two, so a block must be whole
    in its last extent): qk [B, 2, H, T, dk] raw float32, q then k
    (``conv_silu(..., heads=dk)``'s [B, 2 H, T, dk], its major extent split).
    Everything else comes and goes tokens first, as the convolution and the
    matmuls around the scan have it: v and the gate (before its SiLU) [B, T,
    H, dv]; g [B, T, H] float32 log-decay (<= 0), one a head and token; beta
    [B, T, H] in (0, 2). Returns [B, T, H, dv] in v's dtype. Where a grid
    step's value heads are whole vregs side by side
    (``_values_lie_tokens_first``) the kernels read v and the gate and write
    o as they lie; elsewhere they are transposed here. Differentiable in all
    six (qk's cotangent lies as qk does)."""
    batch, t, heads, dv = v.shape
    pad = -t % CHUNK
    if pad:
        # Padding tokens write nothing (beta 0) and decay nothing (g 0).
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        v, gate = (jnp.pad(x, widths) for x in (v, gate))
        g, beta = (jnp.pad(x, widths[:3]) for x in (g, beta))
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    scalar = lambda x: x.astype(F32).transpose(0, 2, 1)[..., None]  # noqa: E731
    closing = (weight.astype(F32)[None], (scale, l2_eps, rms_eps))
    kernels = _attention._on_tpu() or _attention._interpret()
    if kernels and _values_lie_tokens_first(heads, dv):
        flat = lambda x: x.reshape(batch, t + pad, heads * dv)  # noqa: E731
        o = _gdn_pallas(qk, flat(v), scalar(g), scalar(beta), flat(gate), *closing)
        return o.reshape(gate.shape)[:, :t]
    run = _gdn_pallas if kernels else _gdn_xla
    o = run(qk, heads_first(v), scalar(g), scalar(beta), heads_first(gate), *closing)
    return heads_first(o)[:, :t]


# ------------------------------------------- a fixed decay a head: Lightning
# Lightning Attention-2's recurrence, S_t = lambda_h S_{t-1} + k_t v_t^T, o_t
# = scale S_t^T q_t: the delta rule's chunk without its write strength, its
# learned decay and its triangular system. lambda_h = exp(-slope_h) is a
# constant of the head, so the decay between two rows of a chunk is a function
# of their distance: D[t, s] = exp(-slope (t - s)), made from the slope and two
# iotas where it is used, never an exponent of a running sum and never
# positive. With S the state at the chunk's start, t a row's index in it:
#
#     O  = scale ((Q K^T * D) V + (Q exp(-slope (t + 1))) S)
#     S' = exp(-slope C) S + (K exp(-slope (C - 1 - t)))^T V
#
# A chunk is 256 rows of one head: there is no inverse whose chain of small
# products wanted 64, a [256, 128] operand fills the MXU's rows twice over,
# and the states kept for the backward are a quarter of what 64 would write
# (on the chip, forward and backward of 32 heads of 128 at 16k tokens: 14.8 ms
# at 64, 11.0 at 128, 8.9 at 256: PERF.md §6, PR 54). q and k arrive normed and
# turned (the mixer's, under its scopes); o leaves through ``_rms_normed`` and
# the output gate's sigmoid while it is float32, as ``chunk_gdn``'s does. The
# grid is (batch, head, chunk), the state of the one head in VMEM over its
# chunks; the backward walks the chunks in reverse with the state's cotangent
# there and differentiates ``_lightning_chunk`` where it stands from the saved
# state, as ``_gdn_bwd_kernel`` does.
LIGHTNING_CHUNK = 256


def _lightning_chunk(St, q, k, v, gate, weight, slope, *, norm):
    """One head's chunk: St [dv, dk] float32, q, k [C, dk], v and the gate
    (before its sigmoid) [C, dv], the norm's weight [1, dv] and the head's
    slope [1, 1] float32; ``norm`` is (o's scale, the RMSNorm's epsilon). ->
    (the state at the chunk's end, the normed and gated o [C, dv] float32)."""
    scale, rms_eps = norm
    dt = v.dtype
    c = q.shape[0]
    row, col = _rows_cols(c)
    apart = jnp.maximum(row - col, 0).astype(F32)
    decay = jnp.where(row >= col, jnp.exp(-slope * apart), 0.0)
    at = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0).astype(F32)
    since_start = jnp.exp(-slope * (at + 1.0))
    to_end = jnp.exp(-slope * (c - 1.0 - at))
    sd = St.astype(dt)
    o = _nn((_nt(q, k) * decay).astype(dt), v) + _nt(
        (q.astype(F32) * since_start).astype(dt), sd)
    St = jnp.exp(-slope * c) * St + _tn(v, (k.astype(F32) * to_end).astype(dt))
    o = _rms_normed(o * scale, rms_eps) * weight * jax.nn.sigmoid(gate.astype(F32))
    return St, o


def _lightning_fwd_kernel(slope_ref, q_ref, k_ref, v_ref, gate_ref, w_ref,
                          o_ref, *rest, norm):
    # rest: (the states' output, the scratch) or the scratch alone.
    s_ref, st_scr = rest if len(rest) == 2 else (None, *rest)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_scr[...] = jnp.zeros(st_scr.shape, F32)

    St = st_scr[...]
    if s_ref is not None:
        s_ref[0, 0, 0] = St
    st_scr[...], o = _lightning_chunk(
        St, q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], gate_ref[0, 0], w_ref[...],
        slope_ref[0, :, :1], norm=norm)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def _lightning_bwd_kernel(slope_ref, q_ref, k_ref, v_ref, gate_ref, w_ref,
                          s_ref, do_ref, dq_ref, dk_ref, dv_ref, dgate_ref,
                          dw_ref, dst_scr, *, norm):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dst_scr[...] = jnp.zeros(dst_scr.shape, F32)
        # The weight's cotangent adds up over a head's chunks in its output
        # block, which stays in VMEM while the block's index stands.
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    slope = slope_ref[0, :, :1]
    _, vjp = jax.vjp(
        lambda *operands: _lightning_chunk(*operands, slope, norm=norm),
        s_ref[0, 0, 0], q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], gate_ref[0, 0],
        w_ref[...],
    )
    dst_scr[...], dq, dk_, dv_, dgate, dw = vjp(
        (dst_scr[...], do_ref[0, 0].astype(F32)))
    for ref, x in ((dq_ref, dq), (dk_ref, dk_), (dv_ref, dv_), (dgate_ref, dgate)):
        ref[0, 0] = x.astype(ref.dtype)
    dw_ref[0, 0] += dw


def _lightning_specs(dk, dv, chunk_of):
    """BlockSpecs over grid (batch, head, step) for operands that lie [B, H,
    T, d], ``chunk_of(step)`` the chunk a step works on. The states lie [B, H,
    N, dv, dk], the slopes [H, 1, _LANES] (a head's on every lane), the
    weight's cotangent [B, H, 1, dv]."""
    c = LIGHTNING_CHUNK

    def rows(d):
        return pl.BlockSpec((1, 1, c, d), lambda b, h, n: (b, h, chunk_of(n), 0))

    return {
        "k": rows(dk), "v": rows(dv),
        "slope": pl.BlockSpec((1, 1, _LANES), lambda b, h, n: (h, 0, 0)),
        "weight": pl.BlockSpec((1, dv), lambda b, h, n: (0, 0)),
        "dweight": pl.BlockSpec((1, 1, 1, dv), lambda b, h, n: (b, h, 0, 0)),
        "state": pl.BlockSpec((1, 1, 1, dv, dk),
                              lambda b, h, n: (b, h, chunk_of(n), 0, 0)),
        "scratch": pltpu.VMEM((dv, dk), F32),
        "params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    }


@_attention.kernel_entry("norm", "states")
def _lightning_forward_pallas(q, k, v, gate, weight, slopes, norm, states):
    batch, heads, t, dk = q.shape
    dv, n = v.shape[3], t // LIGHTNING_CHUNK
    s = _lightning_specs(dk, dv, lambda i: i)
    return pl.pallas_call(
        functools.partial(_lightning_fwd_kernel, norm=norm),
        grid=(batch, heads, n),
        in_specs=[s["slope"], s["k"], s["k"], s["v"], s["v"], s["weight"]],
        out_specs=[s["v"], s["state"]][:1 + states],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((batch, heads, n, dv, dk), F32),
        ][:1 + states],
        scratch_shapes=[s["scratch"]],
        compiler_params=s["params"],
        interpret=_attention._interpret(),
    )(slopes, q, k, v, gate, weight)


@_attention.kernel_entry("norm")
def _lightning_backward_pallas(q, k, v, gate, weight, slopes, states, do, norm):
    batch, heads, t, dk = q.shape
    dv, n = v.shape[3], t // LIGHTNING_CHUNK
    s = _lightning_specs(dk, dv, lambda i: n - 1 - i)
    return pl.pallas_call(
        functools.partial(_lightning_bwd_kernel, norm=norm),
        grid=(batch, heads, n),
        in_specs=[s["slope"], s["k"], s["k"], s["v"], s["v"], s["weight"],
                  s["state"], s["v"]],
        out_specs=[s["k"], s["k"], s["v"], s["v"], s["dweight"]],
        out_shape=[
            *(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, gate)),
            jax.ShapeDtypeStruct((batch, heads, 1, dv), F32),
        ],
        scratch_shapes=[s["scratch"]],
        compiler_params=s["params"],
        interpret=_attention._interpret(),
    )(slopes, q, k, v, gate, weight, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _lightning_pallas(q, k, v, gate, weight, slopes, norm):
    """The normed, gated o [B, H, T, dv] from q, k [B, H, T, dk], v and the
    gate [B, H, T, dv], the norm's weight [1, dv] and the slopes [H, 1,
    _LANES], T a whole number of chunks. As ``_gdn_pallas``: under a gradient
    the forward rule writes and names o and every chunk's first state
    (``lightning_o``, ``lightning_states``: models/llama.py REPLAY_KEEPS)."""
    return _lightning_forward_pallas(q, k, v, gate, weight, slopes, norm,
                                     states=False)[0]


def _lightning_pallas_fwd(q, k, v, gate, weight, slopes, norm):
    o, states = _lightning_forward_pallas(q, k, v, gate, weight, slopes, norm,
                                          states=True)
    o = checkpoint_name(o, "lightning_o")
    states = checkpoint_name(states, "lightning_states")
    return o, (q, k, v, gate, weight, slopes, states)


def _lightning_pallas_bwd(norm, residuals, do):
    *operands, slopes, states = residuals
    dq, dk, dv, dgate, dw = _lightning_backward_pallas(
        *operands, slopes, states, do.astype(operands[2].dtype), norm)
    # The slopes are the layer's constants: nothing learns them.
    return dq, dk, dv, dgate, dw.sum((0, 1)), jnp.zeros_like(slopes)


_lightning_pallas.defvjp(_lightning_pallas_fwd, _lightning_pallas_bwd)


def _lightning_xla(q, k, v, gate, weight, slopes, norm):
    """The same function of the same layouts under ``lax.scan``, one head a
    call, for JAX to differentiate: where there is no TPU."""
    batch, heads, t, dk = q.shape
    c = LIGHTNING_CHUNK

    def chunks(x):  # [B, H, T, d] -> [N, B, H, C, d]
        return jnp.moveaxis(x.reshape(batch, heads, t // c, c, -1), 2, 0)

    def one(St, q, k, v, gate, slope):
        return _lightning_chunk(St, q, k, v, gate, weight, slope, norm=norm)

    def step(St, chunk):
        heads_of = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0))
        return jax.vmap(heads_of, in_axes=(0, 0, 0, 0, 0, None))(
            St, *chunk, jax.lax.stop_gradient(slopes[:, :, :1]))

    _, o = jax.lax.scan(
        step, jnp.zeros((batch, heads, v.shape[3], dk), F32),
        tuple(chunks(x) for x in (q, k, v, gate)))
    return jnp.moveaxis(o, 0, 2).reshape(v.shape).astype(v.dtype)


def chunk_lightning(q, k, v, gate, weight, slopes, *, scale, rms_eps):
    """A Lightning (decay-only linear attention) mixer from its normed and
    turned q and k to its output projection's input, in ``chunk_gdn``'s
    layout: S_t = exp(-slopes_h) S_{t-1} + k_t v_t^T in float32, o_t = scale
    S_t^T q_t, then o's RMSNorm over a head's channels (``weight`` [dv]) times
    ``sigmoid(gate)``. q, k [B, T, H, dk]; v, gate [B, T, H, dv], the gate
    before its sigmoid; slopes [H] float32 >= 0, constants of the layer.
    Returns [B, T, H, dv] in v's dtype. Differentiable in q, k, v, the gate
    and the weight."""
    t = q.shape[1]
    pad = -t % LIGHTNING_CHUNK
    if pad:
        # Padding tokens follow the last real one and write zeros.
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v, gate = (jnp.pad(x, widths) for x in (q, k, v, gate))
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    run = (_lightning_pallas if _attention._on_tpu() or _attention._interpret()
           else _lightning_xla)
    lanes = jnp.broadcast_to(
        slopes.astype(F32)[:, None, None], (slopes.shape[0], 1, _LANES))
    o = run(heads_first(q), heads_first(k), heads_first(v), heads_first(gate),
            weight.astype(F32)[None], lanes, (scale, rms_eps))
    return heads_first(o)[:, :t]


# ------------------------------------- a step-scaled scalar decay: Mamba-2's SSD
# The state-space duality form of Mamba-2's mixer. A head has P value channels
# and a float32 state S in R^{P x N}; B and C, the "key" and the "query" of N
# channels, are one pair a token for every head (one group):
#
#     S_t = exp(dl_t A) S_{t-1} + dl_t u_t B_t^T,   y_t = S_t C_t + D u_t
#
# with dl_t > 0 the head's step (the mixer's softplus), A < 0 its rate and D
# its skip. No delta rule and no inverse: Lightning's chunk with a decay that
# is data. With L the running sum of dl A inside a chunk of 256 rows (float32,
# never positive, differences taken before the exponent as ``_gdn_head_chunk``
# takes them) and x = dl u:
#
#     Y  = ((C B^T) * exp(L_t - L_s)[s <= t]) X + exp(L_t) (C S^T) + D U
#     S' = exp(L_last) S + (X exp(L_last - L_t))^T B
#
# C B^T is the same [256, 256] matrix for every head: the kernels make it once
# a chunk (``g_scr``, at the chunk's first grid step) and a head multiplies it
# by its own decays. Layout: u and y lie [B, T, H * P] as the mixer's
# convolution leaves them and its norm takes them (nothing is transposed in
# HBM); a grid step is a chunk of ``_SSD_GROUP`` = 8 heads, their 512 lanes of u;
# two heads of 64 share a vreg's 128 lanes (a *tile*), and a tile's state lies
# transposed and side by side, [N, 2 P], so that C S^T and X^T B are one [.,
# 128] x [128, 128] product a tile and only the masked product is a head's
# own. The steps come as rows, [B, H / 8, 8, T] float32 (2 MiB at 8k tokens and
# 64 heads, moved once): the kernel turns the block to columns (one [128,
# 256] transposition), takes the running sums of all 8 heads at once with a
# head a lane (``_running_sums``: sublane rolls, exact float32, no matmul) and
# turns them back for the row form. The grid is (batch, chunk, group), the
# groups innermost, every head's state in VMEM over the sequence (2 MiB at 64
# heads), as ``_kda_fwd_kernel`` holds them. Under a gradient the forward
# writes the state at every chunk's start (64 MiB a layer at 8k tokens and 64
# heads); the backward kernel walks the chunks in reverse with the states'
# cotangents in VMEM and differentiates ``_ssd_chunk`` where it stands
# (``jax.vjp`` inside the kernel), the cotangent of C B^T added up over a
# chunk's groups and taken to B's and C's at its last. The skip and the
# step's product with u are inside (neither costs a pass over u in HBM); the
# gate and the norm over all heads' channels are the mixer's, outside.
SSD_CHUNK = 256
_SSD_GROUP = 8


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _lane(x, i):
    """x[:, i:i + 1] of x [r, _LANES]; its transpose is a select, not a pad."""
    return x[:, i:i + 1]


def _lane_fwd(x, i):
    return _lane(x, i), None


def _lane_bwd(i, _, g):
    at = jax.lax.broadcasted_iota(jnp.int32, (g.shape[0], _LANES), 1)
    return (jnp.where(at == i, g, 0.0),)


_lane.defvjp(_lane_fwd, _lane_bwd)


def _row(x, i):
    """x[i:i + 1] of a 2-D x; its transpose is a select, not a pad."""
    return _row_of(x, i, x.shape[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _row_of(x, i, rows):
    return x[i:i + 1]


def _row_fwd(x, i, rows):
    return _row_of(x, i, rows), None


def _row_bwd(i, rows, _, g):
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, g.shape[1]), 0)
    return (jnp.where(at == i, g, 0.0),)


_row_of.defvjp(_row_fwd, _row_bwd)


def _ssd_tile(p: int) -> int:
    """Heads whose P channels share a tile of lanes: two of 64; a power of
    two, so that a group is whole tiles."""
    return min(_SSD_GROUP, 1 << max(_LANES // p, 1).bit_length() - 1)


def _ssd_chunk(St, u, dt, a, D, Bm, Cm, G, roll=_xla_roll):
    """A chunk of one group of 8 heads, q of them a tile of q * P lanes. St
    [8 / q, N, q * P] float32, the tiles' states at its start, each head's
    transposed; u [C, 8 * P] in the matmuls' dtype; dt [8, C] float32, a
    head's steps a row; a [1, _LANES] float32, head j's rate A on lane j; D
    [8 / q, q * P] float32, a tile's skips a row, a head's on its lanes; Bm, Cm [C, N] float32; G
    [C, C] float32, C B^T. -> (the states at its end, y [C, 8 * P] float32)."""
    dtype = u.dtype
    c = u.shape[0]
    tiles, _, lanes = St.shape
    q = _SSD_GROUP // tiles
    p = lanes // q
    # Head j's steps on lane j, then the running sum of dl A down the rows.
    steps = jnp.concatenate([dt, jnp.zeros((_LANES - _SSD_GROUP, c), F32)]).T
    L, (last,) = _running_sums(steps * a, 1, roll, c)
    Lrow = L.T
    row, col = _rows_cols(c)
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // p

    def on_lanes(x, tile):
        """x [r, _LANES], head j on lane j -> [r, q * P]: the tile's heads,
        each on its P lanes."""
        out = jnp.broadcast_to(_lane(x, tile * q), (x.shape[0], lanes))
        for i in range(1, q):
            out = jnp.where(head_of == i, _lane(x, tile * q + i), out)
        return out

    Bd, Cd = Bm.astype(dtype), Cm.astype(dtype)
    states, ys = [], []
    for tile in range(tiles):
        uf = u[:, tile * lanes:(tile + 1) * lanes].astype(F32)
        Lt, end = on_lanes(L, tile), on_lanes(last, tile)
        x = uf * on_lanes(steps, tile)
        xd = x.astype(dtype)
        y = None
        for i in range(q):
            h = tile * q + i
            # exp(L_t - L_s) for s <= t: the difference is taken first and
            # is never positive where it stands.
            decay = jnp.where(row >= col, jnp.exp(jnp.minimum(
                _lane(L, h) - _row(Lrow, h), 0.0)), 0.0)
            mine = _nn((G * decay).astype(dtype), xd)
            y = mine if y is None else jnp.where(head_of == i, mine, y)
        y = y + jnp.exp(Lt) * _nn(Cd, St[tile].astype(dtype))
        ys.append(y + _row(D, tile) * uf)
        states.append(jnp.exp(end) * St[tile]
                      + _tn(Bd, (x * jnp.exp(end - Lt)).astype(dtype)))
    return jnp.stack(states), jnp.concatenate(ys, axis=1)


def _shared_scores(b_ref, c_ref):
    """C B^T of a chunk, float32: every head's, before its decays."""
    return _nt(c_ref[0], b_ref[0])


def _ssd_fwd_kernel(u_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, *rest):
    # rest: (the states' output, the two scratches) or the scratches alone.
    s_ref, g_scr, st_scr = rest if len(rest) == 3 else (None, *rest)
    step = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        st_scr[step] = jnp.zeros(st_scr.shape[1:], F32)

    @pl.when(step == 0)
    def _scores():
        g_scr[...] = _shared_scores(b_ref, c_ref)

    St = st_scr[step]
    if s_ref is not None:
        s_ref[0, 0, 0] = St
    st_scr[step], y = _ssd_chunk(
        St, u_ref[0], dt_ref[0, 0], a_ref[0], d_ref[0], b_ref[0].astype(F32),
        c_ref[0].astype(F32), g_scr[...], roll=_roll_here())
    y_ref[0] = y.astype(y_ref.dtype)


def _ssd_bwd_kernel(u_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, s_ref, dy_ref,
                    du_ref, ddt_ref, da_ref, dd_ref, db_ref, dc_ref, g_scr,
                    dg_scr, dst_scr):
    step = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dst_scr[step] = jnp.zeros(dst_scr.shape[1:], F32)

    # The rates' and the skips' cotangents add up over a batch row's steps in
    # their output blocks, which stay in VMEM while the row stands.
    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _init_heads():
        da_ref[...] = jnp.zeros(da_ref.shape, F32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, F32)

    # B's and C's add up over a chunk's groups, C B^T's in a scratch.
    @pl.when(step == 0)
    def _scores():
        g_scr[...] = _shared_scores(b_ref, c_ref)
        dg_scr[...] = jnp.zeros(dg_scr.shape, F32)
        db_ref[...] = jnp.zeros(db_ref.shape, F32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, F32)

    _, vjp = jax.vjp(
        functools.partial(_ssd_chunk, roll=_roll_here()),
        s_ref[0, 0, 0], u_ref[0], dt_ref[0, 0], a_ref[0], d_ref[0],
        b_ref[0].astype(F32), c_ref[0].astype(F32), g_scr[...])
    dst_scr[step], du, ddt, da, dd, dB, dC, dG = vjp(
        (dst_scr[step], dy_ref[0].astype(F32)))
    du_ref[0] = du.astype(du_ref.dtype)
    ddt_ref[0, 0] = ddt
    da_ref[0, step] += da
    dd_ref[0, step] += dd
    db_ref[0] += dB
    dc_ref[0] += dC
    dg_scr[...] += dG

    @pl.when(step == pl.num_programs(2) - 1)
    def _to_b_and_c():
        dG = dg_scr[...].astype(b_ref.dtype)
        dc_ref[0] += _nn(dG, b_ref[0])
        db_ref[0] += _tn(dG, c_ref[0])


def _ssd_specs(groups, p, n_state, chunk_of):
    """BlockSpecs over grid (batch, step, group), ``chunk_of(step)`` the chunk
    a step works on: u and y lie [B, T, H * P], a block a chunk's rows of a
    group's lanes; the steps [B, H / 8, 8, T]; the rates [H / 8, 1, _LANES]
    and the skips [H / 8, tiles, q * P]; B and C [B, T, N], a chunk's block the
    same for every group; the states [B, chunks, H / 8, tiles, N, q * P]."""
    c, q = SSD_CHUNK, _ssd_tile(p)
    tiles, lanes, width = _SSD_GROUP // q, q * p, _SSD_GROUP * p
    whole = lambda *block: pl.BlockSpec(  # noqa: E731
        (1, groups, *block), lambda b, n, g: (b, 0, 0, 0))
    return {
        "u": pl.BlockSpec((1, c, width), lambda b, n, g: (b, chunk_of(n), g)),
        "dt": pl.BlockSpec((1, 1, _SSD_GROUP, c),
                           lambda b, n, g: (b, g, 0, chunk_of(n))),
        "a": pl.BlockSpec((1, 1, _LANES), lambda b, n, g: (g, 0, 0)),
        "d": pl.BlockSpec((1, tiles, lanes), lambda b, n, g: (g, 0, 0)),
        "bc": pl.BlockSpec((1, c, n_state), lambda b, n, g: (b, chunk_of(n), 0)),
        "state": pl.BlockSpec((1, 1, 1, tiles, n_state, lanes),
                              lambda b, n, g: (b, chunk_of(n), g, 0, 0, 0)),
        "da": whole(1, _LANES), "dd": whole(tiles, lanes),
        "scores": pltpu.VMEM((c, c), F32),
        "states": pltpu.VMEM((groups, tiles, n_state, lanes), F32),
    }


@_attention.kernel_entry("states")
def _ssd_forward_pallas(u, dt, a, D, Bm, Cm, states):
    batch, t, _ = u.shape
    groups, p = dt.shape[1], D.shape[1] * D.shape[2] // _SSD_GROUP
    n, n_state = t // SSD_CHUNK, Bm.shape[2]
    s = _ssd_specs(groups, p, n_state, lambda i: i)
    tiles, lanes = s["states"].shape[1], s["states"].shape[3]
    return pl.pallas_call(
        _ssd_fwd_kernel, grid=(batch, n, groups),
        in_specs=[s["u"], s["dt"], s["a"], s["d"], s["bc"], s["bc"]],
        out_specs=[s["u"], s["state"]][:1 + states],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct((batch, n, groups, tiles, n_state, lanes), F32),
        ][:1 + states],
        scratch_shapes=[s["scores"], s["states"]],
        compiler_params=_params(),
        interpret=_attention._interpret(),
    )(u, dt, a, D, Bm, Cm)


@_attention.kernel_entry()
def _ssd_backward_pallas(u, dt, a, D, Bm, Cm, states, dy):
    batch, t, _ = u.shape
    groups, p = dt.shape[1], D.shape[1] * D.shape[2] // _SSD_GROUP
    n, n_state = t // SSD_CHUNK, Bm.shape[2]
    s = _ssd_specs(groups, p, n_state, lambda i: n - 1 - i)
    return pl.pallas_call(
        _ssd_bwd_kernel, grid=(batch, n, groups),
        in_specs=[s["u"], s["dt"], s["a"], s["d"], s["bc"], s["bc"], s["state"],
                  s["u"]],
        out_specs=[s["u"], s["dt"], s["da"], s["dd"], s["bc"], s["bc"]],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct(dt.shape, F32),
            jax.ShapeDtypeStruct((batch, *a.shape), F32),
            jax.ShapeDtypeStruct((batch, *D.shape), F32),
            jax.ShapeDtypeStruct(Bm.shape, F32),
            jax.ShapeDtypeStruct(Cm.shape, F32),
        ],
        scratch_shapes=[s["scores"], s["scores"], s["states"]],
        compiler_params=_params(),
        interpret=_attention._interpret(),
    )(u, dt, a, D, Bm, Cm, states, dy)


@jax.custom_vjp
def _ssd_pallas(u, dt, a, D, Bm, Cm):
    """y [B, T, H * P] from u in the same layout, the steps dt [B, H / 8, 8,
    T], the rates a [H / 8, 1, _LANES], the skips D [H / 8, tiles, q * P] and B,
    C [B, T, N], T a whole number of chunks and H of groups. As
    ``_lightning_pallas``: under a gradient the forward rule writes and names
    y and every chunk's first states (``ssd_y``, ``ssd_states``:
    models/llama.py REPLAY_KEEPS)."""
    return _ssd_forward_pallas(u, dt, a, D, Bm, Cm, states=False)[0]


def _ssd_pallas_fwd(u, dt, a, D, Bm, Cm):
    y, states = _ssd_forward_pallas(u, dt, a, D, Bm, Cm, states=True)
    y, states = checkpoint_name(y, "ssd_y"), checkpoint_name(states, "ssd_states")
    return y, (u, dt, a, D, Bm, Cm, states)


def _ssd_pallas_bwd(residuals, dy):
    u, Bm = residuals[0], residuals[4]
    du, ddt, da, dd, dB, dC = _ssd_backward_pallas(*residuals, dy.astype(u.dtype))
    return du, ddt, da.sum(0), dd.sum(0), dB.astype(Bm.dtype), dC.astype(Bm.dtype)


_ssd_pallas.defvjp(_ssd_pallas_fwd, _ssd_pallas_bwd)


def _ssd_xla(u, dt, a, D, Bm, Cm):
    """The same function of the same layouts under ``lax.scan``, a group a
    call, for JAX to differentiate: where there is no TPU."""
    batch, t, _ = u.shape
    groups, p = dt.shape[1], D.shape[1] * D.shape[2] // _SSD_GROUP
    c, q = SSD_CHUNK, _ssd_tile(p)
    n = t // c

    def chunks(x):  # [B, T, ...] -> [N, B, C, ...]
        return jnp.moveaxis(x.reshape(batch, n, c, *x.shape[2:]), 1, 0)

    def one(St, u, dt, Bm, Cm):  # a batch row's chunk: every group
        G = _nt(Cm.astype(u.dtype), Bm.astype(u.dtype))
        group = lambda St, u, dt, a, D: _ssd_chunk(  # noqa: E731
            St, u, dt, a, D, Bm.astype(F32), Cm.astype(F32), G)
        St, y = jax.vmap(group, in_axes=(0, 1, 0, 0, 0), out_axes=(0, 1))(
            St, u.reshape(c, groups, -1), dt, a, D)
        return St, y.reshape(c, -1)

    _, y = jax.lax.scan(
        lambda St, chunk: jax.vmap(one)(St, *chunk),
        jnp.zeros((batch, groups, _SSD_GROUP // q, Bm.shape[2], q * p), F32),
        (chunks(u), jnp.moveaxis(dt.reshape(batch, groups, _SSD_GROUP, n, c), 3, 0),
         chunks(Bm), chunks(Cm)))
    return jnp.moveaxis(y, 0, 1).reshape(u.shape).astype(u.dtype)


def ssd_road(p: int, n_state: int) -> str:
    """Which road ``chunk_ssd`` takes at P value channels a head and a state
    of N: "pallas" on a TPU where a group's lanes and the state fill whole
    vregs, and under the interpreter; else "xla"."""
    if _attention._interpret():
        return "pallas"
    tiles = not (_SSD_GROUP * p) % _LANES and not n_state % _LANES
    return "pallas" if _attention._on_tpu() and tiles else "xla"


def chunk_ssd(u, dt, a_log, Bm, Cm, D):
    """A Mamba-2 mixer's recurrence from its convolution's outputs to its
    gate's input, chunked: S_t = exp(dt_t A) S_{t-1} + dt_t u_t B_t^T in
    float32, A = -exp(a_log), y_t = S_t C_t + D u_t. u [B, T, H, P]; dt [B, T,
    H] float32, the steps after their softplus (> 0); a_log, D [H]; Bm, Cm
    [B, T, N], one pair a token for every head. Returns [B, T, H, P] in u's
    dtype. Differentiable in all six."""
    batch, t, heads, p = u.shape
    pad, more = -t % SSD_CHUNK, -heads % _SSD_GROUP
    # Padding tokens and heads step nowhere (dt 0) and write zeros.
    u = jnp.pad(u, ((0, 0), (0, pad), (0, more), (0, 0)))
    dt = jnp.pad(dt.astype(F32), ((0, 0), (0, pad), (0, more)))
    Bm, Cm = (jnp.pad(x.astype(u.dtype), ((0, 0), (0, pad), (0, 0))) for x in (Bm, Cm))
    groups = (heads + more) // _SSD_GROUP
    rates = jnp.pad(-jnp.exp(a_log.astype(F32)), (0, more)).reshape(groups, 1, -1)
    rates = jnp.pad(rates, ((0, 0), (0, 0), (0, _LANES - _SSD_GROUP)))
    skips = jnp.repeat(jnp.pad(D.astype(F32), (0, more)), p).reshape(
        groups, -1, _ssd_tile(p) * p)
    run = _ssd_pallas if ssd_road(p, Bm.shape[2]) == "pallas" else _ssd_xla
    y = run(u.reshape(batch, t + pad, -1),
            dt.transpose(0, 2, 1).reshape(batch, groups, _SSD_GROUP, t + pad),
            rates, skips, Bm, Cm)
    return y.reshape(batch, t + pad, heads + more, p)[:, :t, :heads]


# ----------------------------------- the gated convolution as one pass
# LFM2's mixer: p = x W_in is [B, T, 3 D], its thirds a gate B, a gate C and
# the convolved x~ (in that order); y = C * short_conv(B * x~, w), with no
# activation. The thirds are read where they lie, three BlockSpecs over the
# one array, in its own dtype (a bfloat16 sublane tile is 16 rows, so the
# halo's block is 16 and its last 8 are taken); the products and the taps are
# float32 in registers and y is rounded once. The pass back writes p's
# cotangent whole: its grid has one more axis, innermost, over the thirds,
# whose first step computes all three, writes B's block and holds C's and
# x~'s in VMEM for the two steps that hand them over (an output has one block
# a step; the inputs' blocks stand while that axis moves, so nothing is read
# twice): 4 arrays of [T, D] read and 3 written, no slice and no
# concatenation in XLA.


def _gated_blocks(p, w):
    """The gated kernels' blocks over p [B, T, 3 D] under a filter w [K, D],
    or None where ``_conv_blocks`` would say so of a third."""
    if p.shape[2] != 3 * w.shape[1]:
        raise ValueError(f"p {p.shape} is not three thirds of {w.shape[1]} channels")
    return _conv_blocks(jax.ShapeDtypeStruct((*p.shape[:2], w.shape[1]), p.dtype), w)


def _gated_specs(p, blocks):
    """Over p [B, T, 3 D] and the grid (a third's lanes, batch, a sequence's
    blocks, and backward the thirds): ``third(j)`` a block of third j,
    ``before(j)`` the sublane tile of p's dtype before it and ``after(j,
    dtype)`` the one after it in an array of ``dtype`` (clamped into the
    sequence at its ends; j None: an array of D channels, y's), ``filt``
    a filter's."""
    rows, lanes = blocks.rows, blocks.lanes
    steps = p.shape[2] // 3 // lanes
    grid = (steps, p.shape[0], p.shape[1] // rows)

    def spec(n, at, j):
        return pl.BlockSpec((1, n, lanes),
                            lambda l, b, t, *_: (b, at(t), l + (j or 0) * steps))

    def third(j):
        return spec(rows, lambda t: t, j)

    def before(j):
        n = _sublanes(p.dtype)
        return spec(n, lambda t: jnp.maximum(t * (rows // n) - 1, 0), j)

    def after(j, dtype):
        n = _sublanes(dtype)
        return spec(
            n, lambda t: jnp.minimum((t + 1) * (rows // n), p.shape[1] // n - 1), j)

    def filt(rows):
        return pl.BlockSpec((rows, lanes), lambda l, *_: (0, l))

    return grid, third, before, after, filt


def _gated_input(b_ref, x_ref, before, i, tile):
    """u = B * x~ on tile ``i`` of a block with the 8 rows before it, float32
    [8 + tile, lanes], then the tile's B and x~; ``before`` is u on the
    sublane tile before the block."""
    n = before.shape[0]
    at = pl.ds(pl.multiple_of(i * tile, tile), tile)
    lo = pl.ds(pl.multiple_of(jnp.maximum(i * tile - n, 0), n), n)
    own = _read(b_ref, lo) * _read(x_ref, lo)
    gate, x = _read(b_ref, at), _read(x_ref, at)
    return jnp.concatenate(
        [jax.lax.select(i == 0, before, own)[n - _CONV_HALO:], gate * x]), gate, x


def _gated_conv_fwd_kernel(b_ref, c_ref, x_ref, b_before_ref, x_before_ref, w_ref,
                           y_ref, *, tile, roll):
    w = w_ref[...].astype(F32)
    first = pl.program_id(2) == 0
    before = _edge(b_before_ref, first) * _edge(x_before_ref, first)

    def one(i, carry):
        at = pl.ds(pl.multiple_of(i * tile, tile), tile)
        ext, _, _ = _gated_input(b_ref, x_ref, before, i, tile)
        c = _conv_of(_taps(ext, w, roll), w)[_CONV_HALO:]
        _write(y_ref, at, _read(c_ref, at) * c)
        return carry

    jax.lax.fori_loop(0, y_ref.shape[1] // tile, one, None)


def _gated_conv_bwd_kernel(b_ref, c_ref, x_ref, b_before_ref, x_before_ref,
                           c_after_ref, dy_ref, dy_after_ref, w_ref, dp_ref, dw_ref,
                           held_ref, *, tile, roll):
    # held_ref [2, rows, lanes]: C's and x~'s cotangents until their steps.
    taps = w_ref.shape[0]
    third = pl.program_id(3)
    first, last = pl.program_id(2) == 0, pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when((pl.program_id(1) == 0) & first & (third == 0))
    def _init():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    @pl.when(third == 0)
    def _all_three():
        w = w_ref[...].astype(F32)
        before = _edge(b_before_ref, first) * _edge(x_before_ref, first)
        c_after, dy_after = _edge(c_after_ref, last), _edge(dy_after_ref, last)

        def one(i, carry):
            at = pl.ds(pl.multiple_of(i * tile, tile), tile)
            ext, gate, x = _gated_input(b_ref, x_ref, before, i, tile)
            shifted = [u[_CONV_HALO:] for u in _taps(ext, w, roll)]
            dy = _read(dy_ref, at)
            # The convolution's cotangent on the tile's rows and the 8 after.
            dc = jnp.concatenate([
                dy * _read(c_ref, at),
                _rows_after(dy_ref, dy_after[:_CONV_HALO], i, tile)
                * _rows_after(c_ref, c_after[:_CONV_HALO], i, tile)])
            # du_s = sum_i w[i] dc_{s + (K - 1) - i}: dc moved up, the rows
            # that wrap among the 8 after the tile.
            du = _add_up(
                (roll(dc, tap - (taps - 1)) if tap < taps - 1 else dc)[:tile] * w[tap:tap + 1]
                for tap in range(taps))
            _write(dp_ref, at, du * x)
            held_ref[0, at, :] = (dy * _conv_of(shifted, w)).astype(held_ref.dtype)
            held_ref[1, at, :] = (du * gate).astype(held_ref.dtype)
            for tap, u in enumerate(shifted):
                prod = dc[:tile] * u
                dw_ref[pl.ds(tap * _CONV_HALO, _CONV_HALO), :] += _add_up(
                    prod[r:r + _CONV_HALO] for r in range(0, tile, _CONV_HALO))
            return carry

        jax.lax.fori_loop(0, dy_ref.shape[1] // tile, one, None)

    @pl.when(third > 0)
    def _hand_over():
        dp_ref[0] = held_ref[third - 1]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _gated_forward(p, w, dtype, blocks):
    grid, third, before, _, filt = _gated_specs(p, blocks)
    return _conv_call(
        _gated_conv_fwd_kernel, blocks, grid=grid,
        in_specs=[third(0), third(1), third(2), before(0), before(2), filt(w.shape[0])],
        out_specs=third(None),
        out_shape=jax.ShapeDtypeStruct((*p.shape[:2], w.shape[1]), dtype),
    )(p, p, p, p, p, w)


@functools.partial(jax.jit, static_argnums=3)
def _gated_backward(p, w, dy, blocks):
    grid, third, before, after, filt = _gated_specs(p, blocks)
    taps, steps = w.shape[0], grid[0]
    dp, dw = _conv_call(
        _gated_conv_bwd_kernel, blocks, grid=(*grid, 3),
        semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
        in_specs=[third(0), third(1), third(2), before(0), before(2),
                  after(1, p.dtype), third(None), after(None, dy.dtype), filt(taps)],
        out_specs=[
            pl.BlockSpec((1, blocks.rows, blocks.lanes),
                         lambda l, b, t, j: (b, t, l + j * steps)),
            filt(taps * _CONV_HALO)],
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct((taps * _CONV_HALO, w.shape[1]), F32)],
        scratch_shapes=[pltpu.VMEM((2, blocks.rows, blocks.lanes), p.dtype)],
    )(p, p, p, p, p, p, dy, dy, w)
    return dp, dw.reshape(taps, _CONV_HALO, -1).sum(1).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gated_conv_pallas(p, w, dtype, blocks):
    return _gated_forward(p, w, dtype, blocks)


def _gated_conv_fwd(p, w, dtype, blocks):
    return _gated_forward(p, w, dtype, blocks), (p, w)


def _gated_conv_bwd(dtype, blocks, residuals, dy):
    return _gated_backward(*residuals, dy, blocks)


_gated_conv_pallas.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def gated_conv(p, w, dtype=None):
    """``C * short_conv(B * x~, w)`` rounded once to ``dtype`` (p's where None
    is given): p [B, T, 3 D], its thirds B, C and x~ in that order; w [K, D],
    tap K - 1 on the current token. No activation. On a TPU (or under the
    interpreter) where a third tiles it is one Pallas pass over p forward and
    one over p and the cotangent backward, which writes p's cotangent whole
    and adds the filter's up in float32; the residuals are p and w. Elsewhere
    it is what this line says, in float32, for XLA to differentiate."""
    dtype = jnp.dtype(dtype or p.dtype)
    blocks = _gated_blocks(p, w)
    if blocks is None:
        b, c, x = jnp.split(p.astype(F32), 3, axis=-1)
        return (c * short_conv(b * x, w)).astype(dtype)
    return _gated_conv_pallas(p, w, dtype, blocks)
