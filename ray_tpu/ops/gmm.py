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

Backward: dlhs reuses the same kernel with per-expert-transposed rhs;
drhs is a group-accumulating transposed gmm (`_tgmm`) that keeps the
output block resident in VMEM across the consecutive m-tiles of each
expert (tokens are group-sorted, so revisits are consecutive).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret


def _gmm_kernel(tg_ref, lhs_ref, rhs_ref, out_ref):
    out_ref[...] = jnp.dot(
        lhs_ref[...], rhs_ref[0], preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


def _gmm_pallas(lhs, rhs, tile_group, block_m, block_n):
    m, k = lhs.shape
    e, _, n = rhs.shape
    grid = (m // block_m, n // block_n)
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, k), lambda i, j, tg: (i, 0)),
                pl.BlockSpec((1, k, block_n), lambda i, j, tg: (tg[i], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (block_m, block_n), lambda i, j, tg: (i, j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        interpret=_interpret(),
    )(tile_group, lhs, rhs)


def _tgmm_kernel(tg_ref, lhs_ref, dout_ref, drhs_ref, acc_scr):
    im = pl.program_id(2)
    nm = pl.num_programs(2)
    # Both sides of logical_or are evaluated: the neighbour index is
    # clamped so the first and last tile never read outside tg_ref.
    prev_im = jnp.maximum(im - 1, 0)
    next_im = jnp.minimum(im + 1, nm - 1)

    @pl.when(jnp.logical_or(im == 0, tg_ref[im] != tg_ref[prev_im]))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        lhs_ref[...],
        dout_ref[...],
        (((0,), (0,)), ((), ())),  # lhs^T @ dout
        preferred_element_type=jnp.float32,
    )

    @pl.when(jnp.logical_or(im == nm - 1, tg_ref[next_im] != tg_ref[im]))
    def _flush():
        drhs_ref[0] = acc_scr[...].astype(drhs_ref.dtype)


def _tgmm_pallas(lhs, dout, tile_group, num_groups, block_k, block_n):
    """drhs[e] = sum over m-tiles t with tile_group[t]==e of
    lhs[t]^T @ dout[t].  Grid puts m innermost so all tiles of one
    expert hit the same output block consecutively."""
    m, k = lhs.shape
    _, n = dout.shape
    block_m = 128
    grid = (k // block_k, n // block_n, m // block_m)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda i, j, t, tg: (t, i)),
                pl.BlockSpec((block_m, block_n), lambda i, j, t, tg: (t, j)),
            ],
            out_specs=pl.BlockSpec(
                (1, block_k, block_n), lambda i, j, t, tg: (tg[t], i, j)
            ),
            scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), lhs.dtype),
        interpret=_interpret(),
    )(tile_group, lhs, dout)


def _pick_block(dim: int, preferred: int) -> int:
    b = min(preferred, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gmm(lhs, rhs, tile_group, block_m: int = 128, block_n: int = 512):
    """Grouped matmul: out[t*bm:(t+1)*bm] = lhs[t*bm:(t+1)*bm] @
    rhs[tile_group[t]].

    lhs [M, K] with M % block_m == 0, rows sorted so each block_m tile
    belongs to one group; rhs [E, K, N]; tile_group [M // block_m]
    int32. Differentiable in lhs and rhs.
    """
    return _gmm_fwd(lhs, rhs, tile_group, block_m, block_n)[0]


def _gmm_fwd(lhs, rhs, tile_group, block_m, block_n):
    bn = _pick_block(rhs.shape[2], block_n)
    out = _gmm_pallas(lhs, rhs, tile_group, block_m, bn)
    return out, (lhs, rhs, tile_group)


def _gmm_bwd(block_m, block_n, res, dout):
    lhs, rhs, tile_group = res
    e, k, n = rhs.shape
    # dlhs: same kernel, per-expert-transposed weights.
    bk = _pick_block(k, block_n)
    dlhs = _gmm_pallas(
        dout, rhs.transpose(0, 2, 1), tile_group, block_m, bk
    ).astype(lhs.dtype)
    # drhs: group-accumulating transposed gmm.
    drhs = _tgmm_pallas(
        lhs, dout, tile_group, e,
        _pick_block(k, 512), _pick_block(n, 512),
    ).astype(rhs.dtype)
    return dlhs, drhs, jnp.zeros(tile_group.shape, jax.dtypes.float0)


gmm.defvjp(_gmm_fwd, _gmm_bwd)


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
    sizes = jnp.bincount(e_flat, length=num_groups)  # [E]
    aligned = jnp.maximum(-(-sizes // block_m), 1) * block_m
    starts = jnp.concatenate(
        [jnp.zeros((1,), aligned.dtype), jnp.cumsum(aligned)[:-1]]
    )
    raw_starts = jnp.concatenate(
        [jnp.zeros((1,), sizes.dtype), jnp.cumsum(sizes)[:-1]]
    )
    order = jnp.argsort(e_flat)  # stable
    e_sorted = e_flat[order]
    rank = jnp.arange(n, dtype=jnp.int32) - raw_starts[e_sorted].astype(
        jnp.int32
    )
    dst = starts[e_sorted].astype(jnp.int32) + rank
    tile_start = jnp.arange(m_pad // block_m, dtype=jnp.int32) * block_m
    tile_group = (
        jnp.searchsorted(starts, tile_start, side="right").astype(jnp.int32)
        - 1
    ).clip(0, num_groups - 1)
    return order, dst, tile_group, m_pad
