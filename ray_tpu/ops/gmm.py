"""Pallas grouped matmul (MoE expert dispatch) for TPU.

`gmm(lhs, rhs, tile_group)` computes, for every row-tile of `lhs`, a
matmul against the expert matrix `rhs[tile_group[tile]]` — the compute
core of sparse-MoE dispatch (reference integration point:
wallies/ray has no MoE kernels; this is net-new per SURVEY.md §2.3).

Design: the caller lays tokens out sorted by expert with every
expert's segment padded up to a `block_m` boundary ("tile-aligned
groups"), so each m-tile belongs to exactly ONE expert. That turns the
ragged problem into a dense batched matmul with a scalar-prefetched
expert index per tile — no masking, no ragged loops, full MXU tiles.
The padded layout is static: N + E*block_m rows whatever the routing
(73,728 for 65,536 pairs at 64 experts top-8, b2 x s4096: 12.5% more
tiles than pairs fill), and zero token drops; the capacity path needs
factor E/k to drop none (8.0 there: 193.7 against 68.0 ms a layer
forward and backward on a v5e, PERF.md §6, PR 26).

What crosses HBM: each operand block once per use the mathematics has
for it. The row tiles are every grid's inner dimension and an expert's
tiles are consecutive, so Pallas, which fetches a block only when its
index differs from the step before, reads an expert's weight block
once for all of the expert's tiles; the block is the expert's whole
[K, N] matrix wherever that fits VMEM twice over (`_gmm_block_n`: 4 MiB
at OLMoE's 2048 x 1024), so the rows are read once too. At the OLMoE
cell's shapes (73,728 rows, 64 experts) a call moves 0.72-0.88 GB and
takes 2.02-2.05 ms where the column blocks innermost fetched 2.4 GB of
weights and took 3.93-4.19 ms (PERF.md §6, PR 29: one call alone on a
v5e); what is left is the MXU's 1.57 ms on the padded rows, a fetch
that a 2.7 us step cannot hide at each change of expert, and 576 grid
steps.

A layout may be a bound that its rows do not fill: one expert-parallel
rank's share keeps room for every pair, N + (E_held + 1) * block_m rows,
and an expected E_held / E of them arrive. Such a caller passes
`tiles_used`, and the bound then costs memory and no time: a tile past
the used ones is not fetched, not multiplied and not written, forward or
backward, so those rows of every output are whatever the buffer held
(`unwritten` makes such a buffer for the caller's own passes), and
whoever reads the layout stops at `tiles_used` as the kernels do. A
skipped grid step costs about 0.2 us where writing its zeros cost 0.3
to 0.7: a call at Kimi-Linear's shapes, 1,056 tiles of which ~60 hold
rows, takes 0.22 ms at either width where it took 0.54 (1024 columns)
and 1.05 (2304) (PERF.md §6, PR 33).

Backward: dlhs is the same kernel contracting the weights' last
dimension as they are stored (no transposed copy of them; Mosaic
lowers it at the forward's speed: 2.02-2.05 ms); drhs is a
group-accumulating transposed gmm (`_tgmm`) that keeps the output block
and its float32 accumulator resident in VMEM across the consecutive
m-tiles of each expert and takes the whole [K, N] as that block where
it fits (`_tgmm_blocks`), so each input is read once: 2.11 ms a call
where (512, 512) blocks took 3.77 (the same runs).

Back to tokens from such a bounded layout: `pairs_summed`, a pass over
tokens whose cost follows the pairs that are here. The scalar core goes
through a tile's slots at 4.4 ns a pair, a pair that is here brings the
8-row tile around its row in by a DMA of its own (Mosaic slices a tiled
extent by whole tiles) and its row is added into its token's float32 sum
by single-sublane reads and writes: 61 ns a present row at 2,048 columns
(25 its read's set-up on the scalar core, 30 its sum, the bytes hidden
under the two) and 0.14 us at 5,120 (the bytes), where XLA's gather over
every pair took 42 to 96 ns a pair whether it was here or not (dots3's
65,536 pairs of which 2,052 are here: 0.63 ms against 6.30; Laguna's
131,072 and 16,390: 1.58 against 6.18; every pair here at OLMoE's shapes:
4.32 against 2.74, so the whole-layer road keeps XLA's gather; PERF.md
§6, PR 70).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret, kernel_entry


# What a call's blocks may take of VMEM, double buffers and the float32
# accumulator included, and the limit handed to Mosaic (a v5e core has
# 128 MiB; Mosaic's default lets a kernel use 16).
_BLOCK_BUDGET = 40 * 2**20
_VMEM_LIMIT = 64 * 2**20


def _blocks(dim: int) -> list:
    """The blocks a dimension can be cut into, largest first: the whole
    of it, then every divisor that is a multiple of the 128 lanes."""
    return [dim] + [
        dim // parts for parts in range(2, dim // 128 + 1)
        if dim % parts == 0 and (dim // parts) % 128 == 0
    ]


# Index maps, by grid position (j, i): block j of the output's columns,
# row tile i, the row tiles innermost. Module level so that
# tests/test_gmm_kernel.py walks the grid with what the kernel uses.
def _gmm_lhs_index(j, i, tg):
    return i, 0


def _gmm_rhs_index(j, i, tg):
    return tg[i], 0, j


def _gmm_rhs_t_index(j, i, tg):
    return tg[i], j, 0


def _gmm_out_index(j, i, tg):
    return i, j


def _gmm_kernel(tg_ref, *refs, transpose_rhs, bounded=False):
    # lhs @ rhs, or lhs @ rhs^T on the weights as stored.
    used_ref, lhs_ref, rhs_ref, out_ref = refs if bounded else (None, *refs)
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def compute():
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], contract,
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)

    if not bounded:
        compute()
        return
    # A tile past the used ones holds no row: nothing is fetched for it and
    # nothing written (`_used` keeps every block where the last used tile
    # left it).
    pl.when(pl.program_id(1) < used_ref[0])(compute)


def _used(index):
    """``index`` for a call that is told how many row tiles hold rows
    (``tiles_used``, the second prefetched scalar): a tile past them takes
    the block of the last used one, which the pipeline holds already: an
    input is not fetched again, and an output goes back once, as that
    tile's step left it, when the grid moves on or ends."""
    def bounded(*args):
        *grid, tile = args[:-2]
        tg, used = args[-2:]
        return index(*grid, jnp.minimum(tile, used[0] - 1), tg)

    return bounded


def _gmm_block_n(k: int, n: int, itemsize: int, block_m: int = 128) -> int:
    """Columns of the output a grid step computes: all of them where the
    expert's whole [k, n] matrix fits the budget twice over, else the
    largest block that does."""
    def fits(bn):
        blocks = (block_m * k + k * bn + block_m * bn) * itemsize
        return 2 * blocks + 4 * block_m * bn <= _BLOCK_BUDGET

    blocks = _blocks(n)
    return next((bn for bn in blocks if fits(bn)), blocks[-1])


def _gmm_grid(m, k, n, block_m, block_n, transpose_rhs=False, bounded=False):
    """(grid, in_specs, out_spec) of a `_gmm_kernel` call. The row tiles
    are the grid's inner dimension and the weight block's index follows
    the tile only through its expert, so over an expert's consecutive
    tiles the pipeline keeps the block it holds and fetches none.
    ``bounded``: the call takes ``tiles_used`` (`_used`), and the output
    blocks of the tiles past them are never written."""
    at = _used if bounded else (lambda index: index)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, block_n, k), at(_gmm_rhs_t_index))
    else:
        rhs_spec = pl.BlockSpec((1, k, block_n), at(_gmm_rhs_index))
    return (
        (n // block_n, m // block_m),
        [pl.BlockSpec((block_m, k), at(_gmm_lhs_index)), rhs_spec],
        pl.BlockSpec((block_m, block_n), at(_gmm_out_index)),
    )


# The three calls below stand behind ``kernel_entry``: a model's expert layers
# are one shape, and a layer calls the grouped matmul three times forward and
# six backward. A trace follows the budget its blocks are cut to.
def _budget():
    return (_BLOCK_BUDGET,)


@kernel_entry("block_m", "transpose_rhs", reads=_budget)
def _gmm_pallas(lhs, rhs, tile_group, block_m, transpose_rhs=False,
                tiles_used=None):
    """out[tile t] = lhs[tile t] @ rhs[tile_group[t]]; with
    `transpose_rhs`, @ rhs[tile_group[t]]^T, contracted on the weights'
    last dimension as they are stored. With ``tiles_used`` [1] int32, the
    rows of the tiles from that one on are not written: they hold whatever
    the buffer held."""
    m, k = lhs.shape
    n = rhs.shape[1 if transpose_rhs else 2]
    block_n = _gmm_block_n(k, n, lhs.dtype.itemsize, block_m)
    bounded = tiles_used is not None
    grid, in_specs, out_spec = _gmm_grid(
        m, k, n, block_m, block_n, transpose_rhs, bounded
    )
    scalars = (tile_group, tiles_used) if bounded else (tile_group,)
    kernel = functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs)
    if bounded:
        kernel = functools.partial(kernel, bounded=True)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_interpret(),
    )(*scalars, lhs, rhs)


# Grid position (i, j, t): block i of the weight's rows, block j of its
# columns, row tile t innermost.
def _tgmm_lhs_index(i, j, t, tg):
    return t, i


def _tgmm_dout_index(i, j, t, tg):
    return t, j


def _tgmm_out_index(i, j, t, tg):
    return tg[t], i, j


def _tgmm_kernel(tg_ref, *refs, bounded=False):
    used_ref, lhs_ref, dout_ref, drhs_ref, acc_scr = (
        refs if bounded else (None, *refs)
    )
    im = pl.program_id(2)
    nm = pl.num_programs(2)
    # Both sides of logical_or are evaluated: the neighbour index is
    # clamped so the first and last tile never read outside tg_ref.
    prev_im = jnp.maximum(im - 1, 0)
    next_im = jnp.minimum(im + 1, nm - 1)

    @pl.when(jnp.logical_or(im == 0, tg_ref[im] != tg_ref[prev_im]))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # `+=` on the scratch is what Mosaic folds into the matmul's own
    # accumulation; a product stored on an expert's first tile and added
    # on the others costs a third more (PERF.md §6, PR 29).
    def accumulate():
        acc_scr[...] += jax.lax.dot_general(
            lhs_ref[...],
            dout_ref[...],
            (((0,), (0,)), ((), ())),  # lhs^T @ dout
            preferred_element_type=jnp.float32,
        )

    if bounded:
        # Tiles past the used ones belong to the last group (their
        # tile_group says so), add nothing and fetch nothing (`_used`);
        # the group's block is written when the grid ends.
        pl.when(im < used_ref[0])(accumulate)
    else:
        accumulate()

    @pl.when(jnp.logical_or(im == nm - 1, tg_ref[next_im] != tg_ref[im]))
    def _flush():
        drhs_ref[0] = acc_scr[...].astype(drhs_ref.dtype)


def _tgmm_blocks(k: int, n: int, itemsize: int, block_m: int = 128) -> tuple:
    """(block_k, block_n) of the weight gradient's block a grid step
    accumulates: of those that fit the budget the pair that reads the
    fewest input bytes (lhs once per column block, dout once per row
    block); the whole [k, n] reads both once."""
    def fits(bk, bn):
        acc_and_out = (4 + 2 * itemsize) * bk * bn
        return acc_and_out + 2 * block_m * (bk + bn) * itemsize <= _BLOCK_BUDGET

    pairs = [(bk, bn) for bk in _blocks(k) for bn in _blocks(n)]
    return min(
        [p for p in pairs if fits(*p)] or pairs[-1:],
        key=lambda p: k * (n // p[1]) + n * (k // p[0]),
    )


def _tgmm_grid(m, k, n, block_m, block_k, block_n, bounded=False):
    """(grid, in_specs, out_spec) of a `_tgmm_kernel` call: the row
    tiles innermost, so all tiles of one expert meet the same output
    block consecutively and it is written once."""
    at = _used if bounded else (lambda index: index)
    out_index = _tgmm_out_index
    if bounded:
        out_index = lambda i, j, t, tg, used: _tgmm_out_index(i, j, t, tg)  # noqa: E731
    return (
        (k // block_k, n // block_n, m // block_m),
        [
            pl.BlockSpec((block_m, block_k), at(_tgmm_lhs_index)),
            pl.BlockSpec((block_m, block_n), at(_tgmm_dout_index)),
        ],
        pl.BlockSpec((1, block_k, block_n), out_index),
    )


@kernel_entry("num_groups", "block_m", reads=_budget)
def _tgmm_pallas(lhs, dout, tile_group, num_groups, block_m, tiles_used=None):
    """drhs[e] = sum over m-tiles t with tile_group[t]==e of
    lhs[t]^T @ dout[t], over the first ``tiles_used`` tiles where that is
    given."""
    m, k = lhs.shape
    _, n = dout.shape
    block_k, block_n = _tgmm_blocks(k, n, lhs.dtype.itemsize, block_m)
    bounded = tiles_used is not None
    grid, in_specs, out_spec = _tgmm_grid(
        m, k, n, block_m, block_k, block_n, bounded
    )
    scalars = (tile_group, tiles_used) if bounded else (tile_group,)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, bounded=True) if bounded else _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_interpret(),
    )(*scalars, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(lhs, rhs, tile_group, block_m: int = 128, tiles_used=None):
    """Grouped matmul: out[t*bm:(t+1)*bm] = lhs[t*bm:(t+1)*bm] @
    rhs[tile_group[t]].

    lhs [M, K] with M % block_m == 0, rows sorted so each block_m tile
    belongs to one group; rhs [E, K, N]; tile_group [M // block_m]
    int32. Differentiable in lhs and rhs.

    ``tiles_used`` [1] int32 (at least 1), where the layout is a static
    bound that the rows do not fill: tiles from that one on hold no row.
    Their inputs are not read, their matmuls not computed and their output
    rows not written, forward and backward: those rows of ``out`` and of
    ``lhs``'s gradient are uninitialised memory, and whoever reads the
    layout stops at ``tiles_used`` too. ``tile_group`` names the last
    group for them.
    """
    return _gmm_fwd(lhs, rhs, tile_group, block_m, tiles_used)[0]


def _gmm_fwd(lhs, rhs, tile_group, block_m, tiles_used=None):
    out = _gmm_pallas(lhs, rhs, tile_group, block_m, tiles_used=tiles_used)
    return out, (lhs, rhs, tile_group, tiles_used)


def _gmm_bwd(block_m, res, dout):
    lhs, rhs, tile_group, tiles_used = res
    # dlhs: the same kernel, contracting the weights' last dimension as
    # they are stored.
    dlhs = _gmm_pallas(
        dout, rhs, tile_group, block_m, transpose_rhs=True,
        tiles_used=tiles_used,
    ).astype(lhs.dtype)
    # drhs: group-accumulating transposed gmm.
    drhs = _tgmm_pallas(
        lhs, dout, tile_group, rhs.shape[0], block_m, tiles_used
    ).astype(rhs.dtype)
    no_grad = lambda x: None if x is None else jnp.zeros(x.shape, jax.dtypes.float0)  # noqa: E731
    return dlhs, drhs, no_grad(tile_group), no_grad(tiles_used)


gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _unwritten_kernel(after_ref, out_ref):
    pass


@kernel_entry("shape", "dtype")
def unwritten(shape, dtype, after):
    """An uninitialised buffer that exists once ``after`` does, for a loop
    that fills the part of a bounded layout that holds rows (a bounded
    ``gmm`` call leaves the rest of its output as this leaves all of it). A
    kernel that writes nothing, and not ``lax.empty`` or zeros: a loop
    carried from an allocation that has no operand sends XLA's TPU scheduler
    to program order, every optimizer update after the last backward op,
    and Kimi-Linear's step holds 1.4 GiB more (PERF.md §6, PR 33)."""
    return pl.pallas_call(
        _unwritten_kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=_interpret(),
    )(after)


# A row of a layout comes into VMEM with the tile it lies in: a [8, 128]
# tile of HBM is the least a DMA may slice along rows (Mosaic refuses a
# slice of a tiled extent that is not whole tiles), and a row's tiles lie
# side by side, so the 8 rows around it are one contiguous read.
_ROWS_A_READ = 8
# The rows one trip of a loop of ``_pairs_summed_kernel`` takes, written out
# in the trip's body (Mosaic unrolls a loop whole or not at all). At 2,048
# columns a present row costs 94 ns a row a trip, 71 at two, 62 at four and
# 57 at eight, and a call site's lowering 0.09, 0.09, 0.18 and 0.27 s (a
# step lowers three sites an expert layer, each anew, and eight a trip put
# dots3's set-up 12% over its parent's): four (PERF.md §6, PR 70).
_A_TRIP = 4


def _pairs_summed_blocks(k: int, d: int, itemsize: int) -> tuple:
    """(tokens a grid step, reads each half of its buffer holds) of a
    ``pairs_summed`` call: the step's float32 sums and its output block
    twice take at most a quarter of the budget, the two halves at most the
    rest, and together no more than every pair of the step's; whole trips
    of reads (``_A_TRIP``), and 64 at most: a grid step that has no more
    rows than a half holds (dots3's 64, 8 of 256 experts held) adds nothing
    while they fly, and past 64 a half's length buys nothing (timed at 16
    to 256 at the four cells' shapes: PERF.md §6, PR 70)."""
    tokens = next(
        (t for t in (256, 128, 64, 32, 16) if t * d * (4 + 2 * itemsize) * 4
         <= _BLOCK_BUDGET), 8
    )
    sums, out = (tokens + _ROWS_A_READ) * d * 4, 2 * tokens * d * itemsize
    left = _BLOCK_BUDGET - sums - out
    reads = left // (2 * _ROWS_A_READ * d * itemsize)
    reads = min(tokens * k // 2, 64, reads) // _A_TRIP * _A_TRIP
    return tokens, max(_A_TRIP, reads)


def _pairs_summed_kernel(slot_ref, *refs, k, m_pad, gated, trip):
    gate_ref, rows_ref, out_ref, read_scr, pair_scr, sum_scr, sems = (
        refs if gated else (None, *refs)
    )
    pairs, reads = slot_ref.shape[0], read_scr.shape[1]
    tokens = pairs // k
    # Two bfloat16 rows lie in one 32-bit sublane, the even one low: a row
    # is read as its sublane's words, its half moved to a float32's top.
    packed = read_scr.dtype.itemsize == 2
    words = read_scr.bitcast(jnp.uint32) if packed else read_scr

    # The step's pairs that are here, in the order they lie (a token's in
    # ascending k), by a loop that branches on nothing: every pair is noted
    # where the next one here belongs, and only one that is here moves on.
    def note(token, n):
        for pair in range(k):
            pair += token * k
            pair_scr[n] = pair
            n += (slot_ref[pair] < m_pad).astype(jnp.int32)
        return n

    n = jax.lax.fori_loop(0, tokens, note, jnp.int32(0))
    # The last trip over them is made whole by the last of them again: such
    # a one's read is started and waited for like any, and its row is added
    # to a row of the sums past the tokens', which nothing reads.
    last = pair_scr[jnp.maximum(n - 1, 0)]
    for i in range(trip - 1):
        pair_scr[n + i] = last

    def read(at, half, i):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(at, _ROWS_A_READ)], read_scr.at[half, i],
            sems.at[half],
        )

    # Of the c-th ``reads`` of the pairs that are here, which the buffer's
    # half c % 2 holds, the i-th: its read's start, its read's end, its sum.
    def start(c, i):
        slot = slot_ref[pair_scr[c * reads + i]]
        at = pl.multiple_of(slot // _ROWS_A_READ * _ROWS_A_READ, _ROWS_A_READ)
        read(at, c % 2, i).start()

    def wait(c, i):
        read(0, c % 2, 0).wait()

    def add(c, i):
        j = c * reads + i
        pair = pair_scr[j]
        row = slot_ref[pair] % _ROWS_A_READ
        if packed:
            word = words[c % 2, i, pl.ds(row // 2, 1), :]
            word = jnp.where(
                row % 2 == 1, word & jnp.uint32(0xFFFF0000), word << 16
            )
            value = pltpu.bitcast(word, jnp.float32)
        else:
            value = words[c % 2, i, pl.ds(row, 1), :].astype(jnp.float32)
        if gated:
            value = value * gate_ref[pair]
        token = jnp.where(j < n, pair // k, tokens)
        sum_scr[pl.ds(token, 1), :] += value

    def rows(c, each):
        # each(c, i) of every row i of the c-th chunk, ``trip`` of them a
        # trip; a chunk past the last has none.
        def some(t, carry):
            for i in range(trip):
                each(c, t * trip + i)
            return carry

        some_of = jnp.clip(n - c * reads, 0, reads)
        jax.lax.fori_loop(0, -(-some_of // trip), some, 0)

    # A chunk's reads are started together and waited for together, and the
    # next chunk's are in flight, in the buffer's other half, while this
    # one's rows are added.
    rows(0, start)
    sum_scr[...] = jnp.zeros_like(sum_scr)

    def turn(c, carry):
        rows(c + 1, start)
        rows(c, wait)
        rows(c, add)
        return carry

    jax.lax.fori_loop(0, -(-n // reads), turn, 0)
    out_ref[...] = sum_scr[pl.ds(0, tokens), :].astype(out_ref.dtype)


@kernel_entry(reads=lambda: (_BLOCK_BUDGET, _A_TRIP, _pairs_summed_blocks))
def pairs_summed(rows, slot_of_pair, gates=None):
    """out[t] = sum over k of gates[t, k] * rows[slot_of_pair[t, k]] over the
    pairs whose slot lies under ``rows``' [m_pad, D] extent, the products
    and the sum in float32, added in ascending k and rounded once to
    ``rows.dtype``; without ``gates`` [S, K] the rows alone. A token with no
    such pair reads zeros.

    A pass over tokens, a tile of them a grid step: ``slot_of_pair`` [S, K]
    int32 reaches the scalar core a tile at a time, which notes the pairs
    that are here (a pair that is not costs that compare and nothing else);
    the layout stays in HBM, and a pair that is here brings its row in by a
    DMA of its own (with the 8-row tile it lies in: ``_ROWS_A_READ``),
    started with the others of its chunk and waited for with them while the
    next chunk's are in flight, four rows a trip of every loop over them
    (``_A_TRIP``). What is read of the layout is the 8-row tiles that hold a
    named row, and of those only the named rows enter a sum: a layout filled
    to its used tiles (``unwritten``) may hold anything past them, and in a
    slot no pair names."""
    (m_pad, d), (s, k) = rows.shape, slot_of_pair.shape
    if rows.dtype.itemsize not in (2, 4) or m_pad % _ROWS_A_READ:
        raise ValueError(f"pairs_summed: rows {rows.dtype}{list(rows.shape)}")
    tokens, reads = _pairs_summed_blocks(k, d, rows.dtype.itemsize)
    tokens = min(tokens, -(-s // 8) * 8)
    trip = min(_A_TRIP, reads)
    if reads % trip:
        raise ValueError(f"pairs_summed: {reads} reads a half, {trip} a trip")
    tiles = -(-s // tokens)

    def scalars(of, fill):
        # Whole tiles of pairs, a pair past the tokens one that is not here.
        flat = of.reshape(s * k)
        return jnp.pad(flat, (0, tiles * tokens * k - s * k), constant_values=fill)

    pairs_spec = pl.BlockSpec(
        (tokens * k,), lambda i: (i,), memory_space=pltpu.SMEM
    )
    operands = [scalars(slot_of_pair, m_pad)]
    if gates is not None:
        operands.append(scalars(gates.astype(jnp.float32), 0))
    return pl.pallas_call(
        functools.partial(
            _pairs_summed_kernel, k=k, m_pad=m_pad, gated=gates is not None,
            trip=trip,
        ),
        grid=(tiles,),
        in_specs=[pairs_spec] * len(operands)
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tokens, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, d), rows.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, reads, _ROWS_A_READ, d), rows.dtype),
            pltpu.SMEM((tokens * k + trip,), jnp.int32),
            pltpu.VMEM((tokens + _ROWS_A_READ, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_interpret(),
    )(*operands, rows)


def aligned_group_layout(e_flat, num_groups: int, block_m: int = 128):
    """Tile-aligned destinations for group-sorted dispatch.

    e_flat [N] int32: group id of each row. Returns
    (order [N], dst [N], tile_group [Gm], m_pad) where dst is each
    sorted row's slot in the padded layout (expert segments start on
    block_m boundaries), tile_group maps every m-tile to its group, and
    m_pad is the static padded row count. Rows must be scattered in
    sorted order (argsort by e_flat) for dst to be contiguous per group.

    A group with no row keeps one tile, of padding: `_tgmm` writes a
    group's block of the weight gradient when it leaves the group's
    tiles, and a group it never visits would keep uninitialised memory
    (with 64 experts a router at initialisation leaves some empty).
    m_pad has room: a group pads by at most block_m either way.
    """
    n = e_flat.shape[0]
    m_pad = -(-(n + num_groups * block_m) // block_m) * block_m
    # Counts and per-row lookups of a [num_groups] table are compares
    # against the group ids, groups on the major axis, which XLA fuses
    # into one pass over the rows: an element-wise gather or scatter of n
    # scalars takes 0.6-1.1 ms at 131,072 rows on a v5e, the sort 0.1
    # (PERF.md §6, PR 33).
    ids = jnp.arange(num_groups, dtype=jnp.int32)[:, None]
    sizes = jnp.sum(ids == e_flat[None, :], axis=1, dtype=jnp.int32)  # [E]
    aligned = jnp.maximum(-(-sizes // block_m), 1) * block_m
    starts = jnp.cumsum(aligned) - aligned
    raw_starts = jnp.cumsum(sizes) - sizes
    e_sorted, order = jax.lax.sort(
        (e_flat.astype(jnp.int32), jnp.arange(n, dtype=jnp.int32)), num_keys=1
    )  # stable
    # A sorted row moves by its group's padding so far.
    shift = jnp.where(ids == e_sorted[None, :], (starts - raw_starts)[:, None], 0)
    dst = jnp.arange(n, dtype=jnp.int32) + shift.sum(0)
    tile_start = jnp.arange(m_pad // block_m, dtype=jnp.int32) * block_m
    tile_group = (
        jnp.searchsorted(starts, tile_start, side="right").astype(jnp.int32)
        - 1
    ).clip(0, num_groups - 1)
    return order, dst, tile_group, m_pad
