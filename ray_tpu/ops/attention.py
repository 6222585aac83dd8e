"""Flash attention for TPU (pallas) with a portable reference path.

The reference framework has no attention kernels at all (it delegates
compute to torch); this is net-new capability required by the TPU
north-star (BASELINE.md long-context targets). Design follows the
standard blockwise-softmax scheme: iterate kv blocks innermost,
carrying a running (max, sum, acc) triple in VMEM so the full [Tq, Tk]
score matrix never materializes in HBM.

Forward and backward are pallas kernels on a TPU backend (MXU matmuls
in f32 accumulation; the backward recomputes probabilities from the saved
log-sum-exp; a grid step does what its tile's position needs,
``_tile_class``). On any other backend
`flash_attention` is `attention_reference`; which one ran is visible in the
lowered program (`tpu_custom_call`), and chip_smoke.py asserts it. Where the
ambient mesh splits the sequence it is the ring of `ring_attention.py` over
the same blocks (`_block_fwd`, `_block_bwd`). Under a sliding window the
kernels are three of their own (`_fwd_window_kernel` and its two backward
kernels), whose grids walk only the blocks the band crosses.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.mesh import ambient_axes, ambient_spec, logical_axis_shards

NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas interpreter mode: lets the TPU kernels (incl. the causal
    block-skip control flow) run bit-accurately on CPU for tests.
    Refused on a TPU backend, where it would quietly stand in for the
    compiled kernels."""
    on = os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"
    if on and _on_tpu():
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 on a TPU backend: interpret mode "
            "is for the CPU tests; unset it to run the compiled kernels"
        )
    return on


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Plain XLA attention; also the numerics oracle for kernel tests.

    Shapes: q [B, H, Tq, D]; k [B, Hkv, Tk, D]; v [B, Hkv, Tk, Dv] with
    H % Hkv == 0 (GQA). Dv may differ from D (latent attention: q/k heads
    of 192, v heads of 128); the default scale is D ** -0.5. ``window=w``
    keeps, of the keys a causal row i sees, the w nearest: 0 <= i - j < w.
    """
    b, h, tq, d = q.shape
    hkv = k.shape[1]
    if h != hkv:
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    scale = sm_scale if sm_scale is not None else 1.0 / d**0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        s = jnp.where(_visible(tq, k.shape[2], window), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _visible(tq: int, tk: int, window: Optional[int]) -> jax.Array:
    """[tq, tk] causal mask with the ends aligned (kv-cache semantics: query
    row i stands at key position i + tk - tq), cut to a band of ``window``
    keys where one is given."""
    qpos = jnp.arange(tq)[:, None] + (tk - tq)
    kpos = jnp.arange(tk)[None, :]
    seen = qpos >= kpos
    return seen if window is None else seen & (qpos - kpos < window)


# ----------------------------------------------------------- a tile's position
# What a grid step of the three causal kernels has to do follows from where
# its [block_q, block_k] tile lies, which the program ids and the static
# lengths say: an interior tile (every key visible to every row, none of them
# padding) needs no mask, an edge tile (crossed by the diagonal, or holding
# padded keys) the masked body, a dead tile (wholly above the diagonal)
# neither arithmetic nor a copy. A non-causal call is a causal one whose
# diagonal lies beyond the last key.


def _diagonal(causal: bool, seq_q: int, seq_k: int) -> int:
    """Row r sees key c where c <= r + this: the ends aligned."""
    return seq_k - seq_q if causal else seq_k


def _tile_class(i, j, *, causal: bool, block_q: int, block_k: int,
                seq_q: int, seq_k: int):
    """(live, interior) of the tile of row block ``i`` and key block ``j``.
    Numpy arrays for the counts, traced scalars inside the kernels."""
    off = _diagonal(causal, seq_q, seq_k)
    live = (i + 1) * block_q + off > j * block_k
    interior = ((j + 1) * block_k <= seq_k) & (
        i * block_q + off >= (j + 1) * block_k - 1
    )
    return live, interior


def causal_tiles(tq: int, tk: int, block_q: int, block_k: int,
                 causal: bool) -> tuple[int, int, int]:
    """(interior, edge, dead) tiles of one head's grid: how often each body
    of a kernel engages is a function of the shapes alone."""
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    live, interior = _tile_class(
        np.arange(-(-tq // block_q))[:, None], np.arange(-(-tk // block_k))[None, :],
        causal=causal, block_q=block_q, block_k=block_k, seq_q=tq, seq_k=tk,
    )
    return int(interior.sum()), int((live & ~interior).sum()), int((~live).sum())


def _last_live_key(i, *, causal: bool, block_q: int, block_k: int,
                   seq_q: int, seq_k: int):
    """The last key block that row block ``i`` sees. (lax's primitives, as
    in ``_band``.)"""
    at = jax.lax.mul(i + 1, jnp.int32(block_q)) + (
        _diagonal(causal, seq_q, seq_k) - 1
    )
    return jax.lax.min(
        jax.lax.div(at, jnp.int32(block_k)), jnp.int32(-(-seq_k // block_k) - 1)
    )


def _first_live_row(j, *, causal: bool, block_q: int, block_k: int,
                    seq_q: int, seq_k: int):
    """The first row block that sees key block ``j``."""
    at = jax.lax.mul(j, jnp.int32(block_k)) - _diagonal(causal, seq_q, seq_k)
    return jax.lax.div(jax.lax.max(at, jnp.int32(0)), jnp.int32(block_q))


def _key_walk(tile: dict):
    """The index map of K and V where the grid's last axis walks a row
    block's keys: a dead step is held at the last live block, which is
    resident, so the pipeline issues no copy."""
    def index(b, i, j):
        return b, jax.lax.min(j, _last_live_key(i, **tile)), 0

    return index


def _row_walk(tile: dict):
    """The same for q, do, lse and delta where the last axis walks a key
    block's rows: the dead steps come first and wait at the first live
    block."""
    def index(b, i, j):
        return b, jax.lax.max(j, _first_live_row(i, **tile)), 0

    return index


def _tile_mask(iq, ik, *, causal: bool, block_q: int, block_k: int,
               seq_q: int, seq_k: int):
    """[block_q, block_k]: the keys of block ``ik`` that are no padding and
    that the rows of block ``iq`` see."""
    shape = (block_q, block_k)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = kpos < seq_k  # padded keys
    if causal:
        # Ends aligned (kv-cache semantics, matching attention_reference):
        # query row i attends keys up to i + (seq_k - seq_q).
        qpos = iq * block_q + (seq_k - seq_q) + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0
        )
        mask = jnp.logical_and(mask, qpos >= kpos)
    return mask


def _by_position(body, iq, ik, tile: dict) -> None:
    """Run ``body(masked)`` as the tile's position asks: unmasked on an
    interior tile, masked on an edge tile, not at all on a dead one (a
    skipped tile is exactly a p = 0 update). A body no tile of the grid
    needs is not lowered."""
    n_interior, n_edge, _ = causal_tiles(
        tile["seq_q"], tile["seq_k"], tile["block_q"], tile["block_k"],
        tile["causal"],
    )
    live, interior = _tile_class(iq, ik, **tile)
    if n_interior:
        pl.when(interior)(functools.partial(body, False))
    if n_edge:
        pl.when(jnp.logical_and(live, jnp.logical_not(interior)))(
            functools.partial(body, True)
        )


def _causal_blocks(q, k, causal: bool, block_q: int, block_k: int):
    """What the three causal calls share: the padded lengths and the tile's
    statics (``_tile_class``'s keywords)."""
    tq, tk = q.shape[1], k.shape[1]
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    tq_p = (tq + block_q - 1) // block_q * block_q
    tk_p = (tk + block_k - 1) // block_k * block_k
    tile = dict(causal=causal, block_q=block_q, block_k=block_k,
                seq_q=tq, seq_k=tk)
    return tq_p, tk_p, tile


# ----------------------------------------------------------------- pallas fwd


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, **tile):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _tile(masked: bool):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * sm_scale
        if masked:
            s = jnp.where(_tile_mask(iq, ik, **tile), s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    _by_position(_tile, iq, ik, tile)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _flash_fwd_pallas(q, k, v, *, causal, sm_scale, block_q, block_k):
    bh, tq, d = q.shape
    tk, d_v = k.shape[1], v.shape[2]
    tq_p, tk_p, tile = _causal_blocks(q, k, causal, block_q, block_k)
    block_q, block_k = tile["block_q"], tile["block_k"]
    q, k, v = _pad_rows(q, tq_p), _pad_rows(k, tk_p), _pad_rows(v, tk_p)

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, **tile),
        grid=(bh, tq_p // block_q, tk_p // block_k),
        interpret=_interpret(),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), _key_walk(tile)),
            pl.BlockSpec((1, block_k, d_v), _key_walk(tile)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
            # lse kept 3-D (bh, tq, 1) so the trailing dims satisfy TPU
            # tiling (block_q % 8, last dim == full dim).
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq_p, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, tq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * bh * tq_p * tk_p * (d + d_v),
            bytes_accessed=(q.size + k.size + v.size + bh * tq_p * d_v) * 2,
            transcendentals=bh * tq_p * tk_p,
        ),
    )(q, k, v)
    return o[:, :tq], lse[:, :tq, 0]


# ----------------------------------------------------------------- pallas bwd
# FlashAttention-2 style backward: probabilities recomputed per block
# from the saved log-sum-exp, two kernels so each output accumulates in
# VMEM over its contraction dimension (dk/dv over q blocks, dq over kv
# blocks) and the [Tq, Tk] score matrix never hits HBM. p and ds are
# computed in float32. dK/dV, which transposes both, casts them to the
# inputs' dtype first, as the forward does its p: the MXU's one pass rounds
# a float32 operand the same, so the result is the same and 4% sooner. dQ
# transposes nothing and would only pay the cast (+2.6%), and an unmasked
# body won it nothing: it keeps one masked body and float32 operands
# (PERF.md §6, PR 46).


def _tile_p(q, k, lse, mask, sm_scale: float):
    """The tile's probabilities, float32 [block_q, block_k]."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    return jnp.exp(s - lse)


def _tile_ds(p, do, v, delta, sm_scale: float):
    """The scores' gradient, float32 [block_q, block_k]."""
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return p * (dp - delta) * sm_scale


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale: float, **tile):
    ik, jq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(jq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(masked: bool):
        q, do = q_ref[0], do_ref[0]  # [block_q, d], [block_q, d_v]
        mask = _tile_mask(jq, ik, **tile) if masked else None
        p = _tile_p(q, k_ref[0], lse_ref[0], mask, sm_scale)
        # dv before ds: p and ds are 4 MB each at 1,024 x 1,024
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = _tile_ds(p, do, v_ref[0], delta_ref[0], sm_scale)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _by_position(_tile, jq, ik, tile)

    @pl.when(jq == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, sm_scale: float, **tile):
    iq, jk = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _tile():
        k = k_ref[0]
        p = _tile_p(q_ref[0], k, lse_ref[0], _tile_mask(iq, jk, **tile), sm_scale)
        ds = _tile_ds(p, do_ref[0].astype(jnp.float32),
                      v_ref[0].astype(jnp.float32), delta_ref[0], sm_scale)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    pl.when(_tile_class(iq, jk, **tile)[0])(_tile)  # every live tile

    @pl.when(jk == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, *, causal, sm_scale,
                      block_q, block_k):
    bh, tq, d = q.shape
    tk, d_v = k.shape[1], v.shape[2]
    tq_p, tk_p, tile = _causal_blocks(q, k, causal, block_q, block_k)
    block_q, block_k = tile["block_q"], tile["block_k"]
    nq, nk = tq_p // block_q, tk_p // block_k
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [bh, tq, 1]
    q, do = _pad_rows(q, tq_p), _pad_rows(do, tq_p)
    lse3, delta3 = _pad_rows(lse[..., None], tq_p), _pad_rows(delta, tq_p)
    k, v = _pad_rows(k, tk_p), _pad_rows(v, tk_p)
    static = dict(sm_scale=sm_scale, **tile)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )
    cost = pl.CostEstimate(
        flops=5 * bh * tq_p * tk_p * (d + d_v) // 2,
        bytes_accessed=(q.size + k.size + v.size + do.size) * 2,
        transcendentals=bh * tq_p * tk_p,
    )
    # q and k (and their gradients) have d lanes; v, do and dv have d_v.
    rows = lambda width, index: pl.BlockSpec((1, block_q, width), index)  # noqa: E731
    keys = lambda width, index: pl.BlockSpec((1, block_k, width), index)  # noqa: E731
    here = lambda b, i, j: (b, i, 0)  # noqa: E731
    row_walk, key_walk = _row_walk(tile), _key_walk(tile)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        interpret=_interpret(),
        grid=(bh, nk, nq),
        in_specs=[rows(d, row_walk), keys(d, here), keys(d_v, here),
                  rows(d_v, row_walk), rows(1, row_walk), rows(1, row_walk)],
        out_specs=[keys(d, here), keys(d_v, here)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk_p, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk_p, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        compiler_params=params,
        cost_estimate=cost,
    )(q, k, v, do, lse3, delta3)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        interpret=_interpret(),
        grid=(bh, nq, nk),
        in_specs=[rows(d, here), keys(d, key_walk), keys(d_v, key_walk),
                  rows(d_v, here), rows(1, here), rows(1, here)],
        out_specs=rows(d, here),
        out_shape=jax.ShapeDtypeStruct((bh, tq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=params,
        cost_estimate=cost,
    )(q, k, v, do, lse3, delta3)
    return dq[:, :tq], dk[:, :tk], dv[:, :tk]


# ------------------------------------------------------------ pallas, a window
# The same three kernels over a band: row i sees keys 0 <= i - j < window, so
# a block of rows meets only the few blocks of keys its band crosses (and a
# block of keys the few blocks of rows). The grid's last axis walks those and
# no others; the index maps start each walk at the band's first block. Names
# of their own: a trace prices a call by its kernel's name, and a windowed
# call costs window / T of a causal one. K and V stay at their own heads (a
# q head's group is found by the index maps), dk and dv sum over the group
# inside the kernel, and the matmuls take their operands in the inputs' dtype.


def _band(i, rows: int, cols: int, n_cols: int, lo_shift: int, hi_shift: int):
    """(first, last) block of ``cols`` that the band of block ``i`` of
    ``rows`` crosses: positions i * rows + lo_shift .. i * rows + hi_shift,
    cut to [0, n_cols). Last < first: none. ``i`` is a numpy array for the
    grid's static extent and a traced scalar inside index maps and kernels,
    where the arithmetic is lax's primitives on numerators kept at or above
    zero: jax.numpy's ``//`` and ``%`` are jitted functions whose cached
    trace carries the first kernel's frames into the next kernel's Mosaic
    module, and a profile names a kernel by the first such frame."""
    if isinstance(i, np.ndarray):
        lo = np.maximum(i * rows + lo_shift, 0) // cols
        return lo, np.minimum((i * rows + hi_shift) // cols, n_cols - 1)
    at = jax.lax.mul(i, jnp.int32(rows))
    lo = jax.lax.div(jax.lax.max(at + lo_shift, jnp.int32(0)), jnp.int32(cols))
    # floor((at + hi_shift) / cols) where that is -1 or more, else -1
    hi = jax.lax.div(
        jax.lax.max(at + (hi_shift + cols), jnp.int32(0)), jnp.int32(cols)
    ) - 1
    return lo, jax.lax.min(hi, jnp.int32(n_cols - 1))


def _band_steps(n_rows: int, *band) -> int:
    """The longest walk any block makes: the grid's last extent."""
    lo, hi = _band(np.arange(n_rows), *band)
    return max(int((hi - lo).max()) + 1, 1)


def _band_mask(row_block, key_block, *, block_q: int, block_k: int,
               seq_k: int, seq_q: int, window: int):
    """[block_q, block_k]: which keys of block ``key_block`` the rows of
    block ``row_block`` see, the ends aligned as in the causal kernels."""
    shape = (block_q, block_k)
    kpos = key_block * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    qpos = row_block * block_q + (seq_k - seq_q) + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0
    )
    return (kpos < seq_k) & (qpos >= kpos) & (qpos - kpos < window)


def _key_block_map(keys, group: int):
    """The index map of K and V where the grid's last axis walks a row
    block's band: q head ``b``'s K/V head, the walk's block held at the
    band's last once past it (no new copy, and the kernel skips it)."""
    def index(b, i, j):
        lo, hi = _band(i, *keys)
        return jax.lax.div(b, jnp.int32(group)), jax.lax.min(lo + j, hi), 0

    return index


def _fwd_window_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                       acc_scr, *, sm_scale: float, band, **tile):
    # ``tile``: _band_mask's block sizes, lengths and window.
    iq, j = pl.program_id(1), pl.program_id(2)
    lo, hi = _band(iq, *band)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(lo + j <= hi)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        s = jnp.where(_band_mask(iq, lo + j, **tile), s, NEG_INF)
        # A row that sees nothing of this block keeps m at NEG_INF and adds
        # p = 1 a key: the first block it does see multiplies that by
        # exp(NEG_INF - m) = 0, and every row sees its own position.
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _window_ds(q, k, v, do, lse, delta, mask, sm_scale):
    """(p, ds) of one [block_q, block_k] tile, both in the inputs' dtype."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta) * sm_scale
    return p.astype(do.dtype), ds.astype(q.dtype)


def _bwd_dkv_window_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_scr, dv_scr,
                           *, sm_scale: float, band, steps: int, **tile):
    # The last axis walks the band's query blocks once for each q head of
    # this K/V head's group.
    ik, j = pl.program_id(1), pl.program_id(2)
    lo, hi = _band(ik, *band)
    jq = lo + jax.lax.rem(j, jnp.int32(steps))

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(jq <= hi)
    def _compute():
        q, do = q_ref[0], do_ref[0]
        p, ds = _window_ds(
            q, k_ref[0], v_ref[0], do, lse_ref[0], delta_ref[0],
            _band_mask(jq, ik, **tile), sm_scale,
        )
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_window_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_scr, *, sm_scale: float, band, **tile):
    iq, j = pl.program_id(1), pl.program_id(2)
    lo, hi = _band(iq, *band)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(lo + j <= hi)
    def _compute():
        k = k_ref[0]
        _, ds = _window_ds(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
            _band_mask(iq, lo + j, **tile), sm_scale,
        )
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _window_blocks(q, k, window: int, block_q: int, block_k: int):
    """What the three windowed calls share: the blocks, the padded lengths
    and the two bands, of keys a block of rows crosses and of rows a block
    of keys is seen by."""
    tq, tk = q.shape[1], k.shape[1]
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    tq_p = (tq + block_q - 1) // block_q * block_q
    tk_p = (tk + block_k - 1) // block_k * block_k
    nq, nk, off = tq_p // block_q, tk_p // block_k, tk - tq
    keys = (block_q, block_k, nk, off - window + 1, off + block_q - 1)
    rows = (block_k, block_q, nq, -off, block_k + window - 2 - off)
    return block_q, block_k, tq_p, tk_p, nq, nk, keys, rows


def _pad_rows(x, t_p: int):
    pad = t_p - x.shape[1]
    return x if not pad else jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def _flash_fwd_window_pallas(q, k, v, *, window, sm_scale, block_q, block_k):
    """q [b * h, tq, d]; k, v [b * hkv, tk, .]: q head n reads K/V head
    n // (h // hkv)."""
    bh, tq, d = q.shape
    tk, group, d_v = k.shape[1], bh // k.shape[0], v.shape[2]
    block_q, block_k, tq_p, tk_p, nq, nk, keys, _ = _window_blocks(
        q, k, window, block_q, block_k
    )
    steps = _band_steps(nq, *keys)
    q, k, v = _pad_rows(q, tq_p), _pad_rows(k, tk_p), _pad_rows(v, tk_p)

    key_block = _key_block_map(keys, group)
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_window_kernel, sm_scale=sm_scale, window=window,
            block_q=block_q, block_k=block_k, seq_k=tk, seq_q=tq, band=keys,
        ),
        grid=(bh, nq, steps),
        interpret=_interpret(),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), key_block),
            pl.BlockSpec((1, block_k, d_v), key_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq_p, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, tq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * bh * tq_p * steps * block_k * (d + d_v),
            bytes_accessed=(q.size + k.size + v.size + bh * tq_p * d_v) * 2,
            transcendentals=bh * tq_p * steps * block_k,
        ),
    )(q, k, v)
    return o[:, :tq], lse[:, :tq, 0]


def _flash_bwd_window_pallas(q, k, v, o, lse, do, *, window, sm_scale,
                             block_q, block_k):
    bh, tq, d = q.shape
    bkv, tk, d_v = k.shape[0], k.shape[1], v.shape[2]
    group = bh // bkv
    block_q, block_k, tq_p, tk_p, nq, nk, keys, rows = _window_blocks(
        q, k, window, block_q, block_k
    )
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [bh, tq, 1]
    q, do = _pad_rows(q, tq_p), _pad_rows(do, tq_p)
    lse3, delta3 = _pad_rows(lse[..., None], tq_p), _pad_rows(delta, tq_p)
    k, v = _pad_rows(k, tk_p), _pad_rows(v, tk_p)
    static = dict(sm_scale=sm_scale, window=window, block_q=block_q,
                  block_k=block_k, seq_k=tk, seq_q=tq)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )

    steps = _band_steps(nk, *rows)

    def row_block(b, i, j):
        lo, hi = _band(i, *rows)
        walk = jax.lax.rem(j, jnp.int32(steps))
        at = jax.lax.min(lo + walk, jax.lax.max(hi, jnp.int32(0)))
        return b * group + jax.lax.div(j, jnp.int32(steps)), at, 0

    tile = lambda width, index: pl.BlockSpec((1, block_q, width), index)  # noqa: E731
    k_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    v_spec = pl.BlockSpec((1, block_k, d_v), lambda b, i, j: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_window_kernel, band=rows, steps=steps, **static
        ),
        interpret=_interpret(),
        grid=(bkv, nk, group * steps),
        in_specs=[tile(d, row_block), k_spec, v_spec, tile(d_v, row_block),
                  tile(1, row_block), tile(1, row_block)],
        out_specs=[k_spec, v_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, tk_p, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, tk_p, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        compiler_params=params,
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * tk_p * steps * block_q * (d + d_v),
            bytes_accessed=(q.size + k.size + v.size + do.size) * 2,
            transcendentals=bh * tk_p * steps * block_q,
        ),
    )(q, k, v, do, lse3, delta3)

    steps = _band_steps(nq, *keys)

    key_block = _key_block_map(keys, group)
    here = lambda b, i, j: (b, i, 0)  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_window_kernel, band=keys, **static),
        interpret=_interpret(),
        grid=(bh, nq, steps),
        in_specs=[
            tile(d, here), pl.BlockSpec((1, block_k, d), key_block),
            pl.BlockSpec((1, block_k, d_v), key_block), tile(d_v, here),
            tile(1, here), tile(1, here),
        ],
        out_specs=tile(d, here),
        out_shape=jax.ShapeDtypeStruct((bh, tq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=params,
        cost_estimate=pl.CostEstimate(
            flops=2 * bh * tq_p * steps * block_k * (2 * d + d_v),
            bytes_accessed=(q.size + k.size + v.size + do.size) * 2,
            transcendentals=bh * tq_p * steps * block_k,
        ),
    )(q, k, v, do, lse3, delta3)
    return dq[:, :tq], dk[:, :tk], dv[:, :tk]


# ------------------------------------------------- pallas, a list of blocks a row
# Block-sparse top-k attention (InfLLM-V2): row i of K/V group g sees, of the
# keys up to its own, those in the blocks of ``block_size`` keys that
# ``select_blocks`` chose for (i, g), a set that differs from row to row and
# from group to group. The three kernels are the causal ones' tiles with a
# mask made from a bitmap and never from a [T, T] array: the chosen blocks of
# a key tile are the bits of one int32 a row (``_pack``: [B * G, T, key tiles]
# words, 16 MiB at 16k tokens and two groups), which a grid step takes from its
# row tile's words by its key tile's lane and shifts by each column's block. A
# tile above the diagonal, or one in which no row chose a block, runs nothing.
# What a tile costs is a dense tile's matmuls: the rows of a tile choose
# differently, and together they choose nearly every block below them (a
# kernel that gathered each row's 64 blocks would move 2 MiB of K and V a row
# and group, 64 GiB a forward at 16k, and put 16 rows on the MXU: PERF.md §6,
# PR 54). K and V stay at their own heads, as in the windowed kernels; dk and
# dv sum over a group's heads inside the kernel.
# 1,024 x 1,024, the causal kernels' tile: PERF.md §6, PR 54, has the sweep on
# the chip (forward and backward 86 ms at 16k tokens where 512 x 512 took 116).
SPARSE_BLOCK_Q = 1024
SPARSE_BLOCK_K = 1024


def select_blocks(q, k, *, block_size: int, topk: int, window: int,
                  init_blocks: int, kernel_size: int, kernel_stride: int,
                  sm_scale: Optional[float] = None, rows: int = 512):
    """The blocks of keys each row and K/V group attends: [B, G, T, blocks]
    bool. q [B, H, T, D]; k [B, G, T, D]. Compressed keys are means of
    ``kernel_size`` keys every ``kernel_stride``; a head's row scores those
    that end at or before it, soft-maxed in float32; a group's heads' scores
    are summed; a block's score is the largest among the compressed keys
    that overlap it. Forced: the first ``init_blocks`` blocks and the
    ``window // block_size`` that end with the row's own. Chosen: the forced
    and the highest scores among the other visible blocks, ``topk`` in all
    (ties to the lower index), every visible block where there are fewer.
    An integer set: nothing here is differentiated."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    b, h, t, d = q.shape
    g = k.shape[1]
    f32 = jnp.float32
    scale = sm_scale if sm_scale is not None else 1.0 / d**0.5
    n_blocks = -(-t // block_size)
    m = (t - kernel_size) // kernel_stride + 1
    starts = np.arange(m) * kernel_stride
    kc = k.astype(f32)[:, :, starts[:, None] + np.arange(kernel_size)].mean(3)
    # The compressed keys that overlap block j are lo[j] .. lo[j] + width - 1
    # where they exist.
    blocks = np.arange(n_blocks)
    lo = np.maximum(-(-(blocks * block_size - kernel_size + 1) // kernel_stride), 0)
    hi = np.minimum(((blocks + 1) * block_size - 1) // kernel_stride, m - 1)
    width = max(int((hi - lo).max()) + 1, 1)
    over = lo[:, None] + np.arange(width)  # [blocks, width]
    real = over <= hi[:, None]
    over = np.minimum(over, m - 1)
    rows = min(rows, t)
    t_p = -(-t // rows) * rows
    qg = _pad_rows(q.reshape(b * h, t, d), t_p).reshape(b, g, h // g, t_p, d)

    def some_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, rows, axis=3).astype(f32)
        s = jnp.einsum("bgjrd,bgmd->bgjrm", qb, kc,
                       precision=jax.lax.Precision.HIGHEST) * scale
        at = start + jnp.arange(rows)
        seen = (starts + kernel_size)[None, :] <= at[:, None] + 1  # [rows, m]
        s = jnp.where(seen, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
        total = jnp.sum(e, axis=-1, keepdims=True)
        p = (e / jnp.where(total == 0.0, 1.0, total)).sum(2)  # [b, g, rows, m]
        pooled = jnp.where(real, p[..., over], 0.0).max(-1)  # [b, g, rows, blocks]
        own = (at // block_size)[:, None]
        visible = blocks[None, :] <= own
        forced = visible & ((blocks[None, :] < init_blocks)
                            | (blocks[None, :] > own - window // block_size))
        score = jnp.where(visible, jnp.where(forced, jnp.inf, pooled), -jnp.inf)
        # A block's rank among its row's: how many lie ahead of it, by a
        # higher score or, of equal scores, a lower index. (One compare and
        # one sum a pair of blocks, fused; ``lax.top_k`` sorts, 50 ms a step
        # at 16k where this is 5: PERF.md §6, PR 54.)
        ahead = (score[..., None, :] > score[..., :, None]) | (
            (score[..., None, :] == score[..., :, None])
            & (blocks[None, :] < blocks[:, None]))
        return (ahead.sum(-1) < topk) & visible

    chosen = jax.lax.map(some_rows, jnp.arange(t_p // rows) * rows)
    # [chunks, b, g, rows, blocks] -> [b, g, t, blocks]
    return jnp.moveaxis(chosen, 0, 2).reshape(b, g, t_p, n_blocks)[:, :, :t]


def _sparse_blocks(t: int, block_size: int):
    """(block_q, block_k, the padded length): a key tile is a whole number
    of ``block_size`` blocks, at most 16 of them (a word's low bits)."""
    block_k = min(SPARSE_BLOCK_K, 16 * block_size)
    block_k = max(block_k // block_size, 1) * block_size
    block_q = min(SPARSE_BLOCK_Q, -(-t // 8) * 8)
    step = block_q * block_k // np.gcd(block_q, block_k)
    return block_q, block_k, -(-t // step) * step


def _pack(blocks, block_size: int, block_k: int, t_p: int):
    """[B, G, T, blocks] bool -> [B * G, t_p, lanes] int32: lane j of a row
    holds, bit u, whether the row chose block u of key tile j."""
    b, g, t, n = blocks.shape
    bits, tiles = block_k // block_size, t_p // block_k
    x = jnp.pad(blocks, ((0, 0), (0, 0), (0, t_p - t), (0, tiles * bits - n)))
    words = (x.reshape(b * g, t_p, tiles, bits).astype(jnp.int32)
             << np.arange(bits, dtype=np.int32)).sum(-1)
    return jnp.pad(words, ((0, 0), (0, 0), (0, -tiles % 128)))


def _tile_words(words, ik):
    """[block_q, 1]: each row's word of key tile ``ik``, from the rows' words
    [block_q, lanes]. Zero throughout: no row of the tile chose a block of
    that key tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
    return jnp.sum(jnp.where(lane == ik, words, 0), axis=1, keepdims=True)


def _sparse_mask(mine, iq, ik, *, block_q: int, block_k: int, block_size: int):
    """[block_q, block_k]: which keys of tile ``ik`` the rows of tile ``iq``
    see, from the rows' words of that tile. (lax's primitives, as in
    ``_band``.)"""
    shape = (block_q, block_k)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    bit = jax.lax.shift_right_logical(
        jnp.broadcast_to(mine, shape), jax.lax.div(col, jnp.int32(block_size)))
    chosen = jax.lax.bitwise_and(bit, jnp.int32(1)) == 1
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.logical_and(chosen, qpos >= ik * block_k + col)


def _sparse_live(iq, ik, block_q: int, block_k: int):
    """The tile holds a key at or before one of its rows."""
    return ik * block_k < (iq + 1) * block_q


def _sparse_fwd_kernel(q_ref, k_ref, v_ref, w_ref, o_ref, lse_ref, m_scr, l_scr,
                       acc_scr, *, sm_scale: float, **tile):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_sparse_live(iq, ik, tile["block_q"], tile["block_k"]))
    def _compute():
        mine = _tile_words(w_ref[0], ik)

        @pl.when(jnp.max(mine) != 0)
        def _chosen():
            mask = _sparse_mask(mine, iq, ik, **tile)
            s = jax.lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            s = jnp.where(mask, s, NEG_INF)
            # As in the windowed kernel: a row that sees nothing of this tile
            # adds p = 1 a key at m = NEG_INF, which the first tile it does see
            # multiplies by 0; every row sees its own position.
            m_prev = m_scr[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_scr[:] = l_scr[:] * correction + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[:] = m_new

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _bwd_dkv_sparse_kernel(q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref,
                           delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                           *, sm_scale: float, steps: int, **tile):
    # The last axis walks the row tiles once for each q head of this K/V
    # head's group.
    ik, j = pl.program_id(1), pl.program_id(2)
    jq = jax.lax.rem(j, jnp.int32(steps))

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_sparse_live(jq, ik, tile["block_q"], tile["block_k"]))
    def _compute():
        mine = _tile_words(w_ref[0], ik)

        @pl.when(jnp.max(mine) != 0)
        def _chosen():
            q, do = q_ref[0], do_ref[0]
            p, ds = _window_ds(q, k_ref[0], v_ref[0], do, lse_ref[0], delta_ref[0],
                               _sparse_mask(mine, jq, ik, **tile), sm_scale)
            dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_sparse_kernel(q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref,
                          delta_ref, dq_ref, dq_scr, *, sm_scale: float, **tile):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_sparse_live(iq, ik, tile["block_q"], tile["block_k"]))
    def _compute():
        mine = _tile_words(w_ref[0], ik)

        @pl.when(jnp.max(mine) != 0)
        def _chosen():
            k = k_ref[0]
            _, ds = _window_ds(q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
                               _sparse_mask(mine, iq, ik, **tile), sm_scale)
            dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _sparse_specs(q, k, v, words, block_size: int):
    """What the three sparse calls share: the tile's statics, the grid's
    extents and the BlockSpecs of a walk over a row tile's keys."""
    bh, t_p, d = q.shape
    group, d_v = bh // k.shape[0], v.shape[2]
    block_q, block_k, _ = _sparse_blocks(t_p, block_size)
    nq, nk = t_p // block_q, t_p // block_k
    tile = dict(block_q=block_q, block_k=block_k, block_size=block_size)

    def last_key(i):  # the last key tile a row tile sees
        return jax.lax.div((i + 1) * block_q - 1, jnp.int32(block_k))

    def key_walk(b, i, j):
        return jax.lax.div(b, jnp.int32(group)), jax.lax.min(j, last_key(i)), 0

    here = lambda b, i, j: (b, i, 0)  # noqa: E731
    rows = lambda width, index: pl.BlockSpec((1, block_q, width), index)  # noqa: E731
    keys = lambda width, index: pl.BlockSpec((1, block_k, width), index)  # noqa: E731
    lanes = words.shape[2]
    return dict(
        tile=tile, group=group, nq=nq, nk=nk, rows=rows, keys=keys, here=here,
        key_walk=key_walk, lanes=lanes, d=d, d_v=d_v,
        words_here=pl.BlockSpec(
            (1, block_q, lanes),
            lambda b, i, j: (jax.lax.div(b, jnp.int32(group)), i, 0)),
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2**20),
    )


def _sparse_fwd_pallas(q, k, v, words, *, sm_scale, block_size):
    """q [b * h, t, d]; k, v [b * g, t, .]; words [b * g, t, lanes]; t
    padded to the tiles. -> (o, lse [bh, t])."""
    bh, t_p, d = q.shape
    s = _sparse_specs(q, k, v, words, block_size)
    d_v, tile = s["d_v"], s["tile"]
    o, lse = pl.pallas_call(
        functools.partial(_sparse_fwd_kernel, sm_scale=sm_scale, **tile),
        grid=(bh, s["nq"], s["nk"]),
        interpret=_interpret(),
        in_specs=[s["rows"](d, s["here"]), s["keys"](d, s["key_walk"]),
                  s["keys"](d_v, s["key_walk"]), s["words_here"]],
        out_specs=[s["rows"](d_v, s["here"]), s["rows"](1, s["here"])],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_p, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, t_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile["block_q"], 1), jnp.float32),
            pltpu.VMEM((tile["block_q"], 1), jnp.float32),
            pltpu.VMEM((tile["block_q"], d_v), jnp.float32),
        ],
        compiler_params=s["params"],
    )(q, k, v, words)
    return o, lse[..., 0]


def _sparse_bwd_pallas(q, k, v, words, o, lse, do, *, sm_scale, block_size):
    bh, t_p, d = q.shape
    s = _sparse_specs(q, k, v, words, block_size)
    d_v, tile, group, nq = s["d_v"], s["tile"], s["group"], s["nq"]
    block_q, block_k = tile["block_q"], tile["block_k"]
    rows, keys, here = s["rows"], s["keys"], s["here"]
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [bh, t, 1]
    lse3 = lse[..., None]
    static = dict(sm_scale=sm_scale, **tile)

    def row_tile(i, j):  # a dead step waits at the first live row tile
        first = jax.lax.div(i * block_k, jnp.int32(block_q))
        return jax.lax.max(jax.lax.rem(j, jnp.int32(nq)), first)

    def row_walk(b, i, j):
        return b * group + jax.lax.div(j, jnp.int32(nq)), row_tile(i, j), 0

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_sparse_kernel, steps=nq, **static),
        interpret=_interpret(),
        grid=(k.shape[0], s["nk"], group * nq),
        in_specs=[rows(d, row_walk), keys(d, here), keys(d_v, here),
                  pl.BlockSpec((1, block_q, s["lanes"]),
                               lambda b, i, j: (b, row_tile(i, j), 0)),
                  rows(d_v, row_walk), rows(1, row_walk), rows(1, row_walk)],
        out_specs=[keys(d, here), keys(d_v, here)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        compiler_params=s["params"],
    )(q, k, v, words, do, lse3, delta)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_sparse_kernel, **static),
        interpret=_interpret(),
        grid=(bh, nq, s["nk"]),
        in_specs=[rows(d, here), keys(d, s["key_walk"]),
                  keys(d_v, s["key_walk"]), s["words_here"], rows(d_v, here),
                  rows(1, here), rows(1, here)],
        out_specs=rows(d, here),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=s["params"],
    )(q, k, v, words, do, lse3, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sparse_flash(q, k, v, words, sm_scale, block_size):
    return _sparse_fwd_pallas(q, k, v, words, sm_scale=sm_scale,
                              block_size=block_size)[0]


def _sparse_flash_fwd(q, k, v, words, sm_scale, block_size):
    o, lse = _sparse_fwd_pallas(q, k, v, words, sm_scale=sm_scale,
                                block_size=block_size)
    # Named for the remat policy, as ``_flash``'s are.
    o, lse = checkpoint_name(o, "sparse_o"), checkpoint_name(lse, "sparse_lse")
    return o, (q, k, v, words, o, lse)


def _sparse_flash_bwd(sm_scale, block_size, res, do):
    dq, dk, dv = _sparse_bwd_pallas(*res, do, sm_scale=sm_scale,
                                    block_size=block_size)
    return dq, dk, dv, np.zeros(res[3].shape, jax.dtypes.float0)


_sparse_flash.defvjp(_sparse_flash_fwd, _sparse_flash_bwd)


def _sparse_xla(q, k, v, blocks, block_size: int, scale: float):
    """The same attention by a [T, T] mask: the CPU's road, at test sizes."""
    b, h, t, d = q.shape
    g = k.shape[1]
    seen = jnp.repeat(blocks, block_size, axis=-1)[..., :t] & _visible(t, t, None)
    s = jnp.einsum("bgjtd,bgsd->bgjts", q.reshape(b, g, h // g, t, d), k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(seen[:, :, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("bgjts,bgsd->bgjtd", p.astype(v.dtype), v)
    return o.reshape(b, h, t, v.shape[-1])


def _sparse_attention(q, k, v, blocks, block_size: int, scale: float):
    """``flash_attention``'s road under ``blocks``."""
    b, h, t, d = q.shape
    g, d_v = k.shape[1], v.shape[-1]
    if not _kernels_fit(t, t, d, d_v):
        return _sparse_xla(q, k, v, blocks, block_size, scale)
    _, block_k, t_p = _sparse_blocks(t, block_size)
    o = _sparse_flash(
        _pad_rows(q.reshape(b * h, t, d), t_p),
        _pad_rows(k.reshape(b * g, t, d), t_p),
        _pad_rows(v.reshape(b * g, t, d_v), t_p),
        _pack(blocks, block_size, block_k, t_p), scale, block_size,
    )
    return o[:, :t].reshape(b, h, t, d_v)


# ------------------------------------------------- one block, kernels or XLA


def _kernels_fit(tq: int, tk: int, d: int, d_v: int) -> bool:
    """Where the Pallas kernels run: on the TPU, or under the interpreter,
    at shapes their tiling takes (>= 8 x 128 blocks). Below them (unit
    tests, short prompts, a short shard of a ring) the XLA mathematics."""
    return (
        (_on_tpu() or _interpret())
        and tq >= 128 and tk >= 128 and d % 8 == 0 and d_v % 8 == 0
    )


def _scores(q, k, causal: bool, scale: float, window=None):
    """Scaled scores [bh, tq, tk] in float32, the causal mask with the
    ends aligned like the kernels' and attention_reference's (the plain
    lower triangle for a ring's square block), and its band under a window."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        s = jnp.where(_visible(*s.shape[-2:], window), s, NEG_INF)
    return s


def _block_fwd(q, k, v, causal, scale, block_q, block_k, window=None):
    """One block of attention on [bh, t, d] operands -> (o, lse [bh, tq]).
    Under a ``window`` the kernels are the windowed ones, which alone take
    K and V at fewer heads than q's."""
    if _kernels_fit(q.shape[1], k.shape[1], q.shape[2], v.shape[2]):
        if window is not None:
            return _flash_fwd_window_pallas(
                q, k, v, window=window, sm_scale=scale,
                block_q=block_q, block_k=block_k,
            )
        return _flash_fwd_pallas(
            q, k, v, causal=causal, sm_scale=scale,
            block_q=block_q, block_k=block_k,
        )
    s = _scores(q, k, causal, scale, window)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jax.lax.dot_general(
        (p / l_safe), v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return o, (m + jnp.log(l_safe))[..., 0]


def _block_bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k,
               window=None):
    """(dq, dk, dv) of one block given the (o, lse) of the whole row, which
    for a ring is the merged one: probabilities are recomputed from it,
    p = exp(s - lse). In XLA the memory high-water is the [tq, tk] block
    per batch*head slice."""
    if _kernels_fit(q.shape[1], k.shape[1], q.shape[2], v.shape[2]):
        if window is not None:
            return _flash_bwd_window_pallas(
                q, k, v, o, lse, do, window=window, sm_scale=scale,
                block_q=block_q, block_k=block_k,
            )
        return _flash_bwd_pallas(
            q, k, v, o, lse, do, causal=causal, sm_scale=scale,
            block_q=block_q, block_k=block_k,
        )
    p = jnp.exp(_scores(q, k, causal, scale, window) - lse[..., :, None])
    do_f = do.astype(jnp.float32)
    dv = jax.lax.dot_general(
        p, do_f, (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(do_f * o.astype(jnp.float32), axis=-1, keepdims=True)
    dp = jax.lax.dot_general(
        do_f, v.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta) * scale
    dq = jax.lax.dot_general(
        ds, k.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    dk = jax.lax.dot_general(
        ds, q.astype(jnp.float32), (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ------------------------------------------------------------------ custom vjp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, window):
    return _block_fwd(q, k, v, causal, sm_scale, block_q, block_k, window)[0]


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, window):
    o, lse = _block_fwd(q, k, v, causal, sm_scale, block_q, block_k, window)
    # Named so that a remat policy can keep them (models/llama.py
    # REPLAY_KEEPS): with both kept a layer's replay holds no forward
    # kernel, only q, k and v for the backward ones. lse as [bh, tq], not the
    # kernel's [bh, tq, 1] column, which HBM would pad to 128 lanes.
    o, lse = checkpoint_name(o, "flash_o"), checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, window, res, do):
    return _block_bwd(*res, do, causal, sm_scale, block_q, block_k, window)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# The windowed kernels' blocks: a band of 512 fills a quarter of the two
# 1,024-key blocks a 1,024-row block would need. (PERF.md §6, PR 45, has the
# sweep on the chip.)
WINDOW_BLOCK_Q = 512
WINDOW_BLOCK_K = 512


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    # 1,024 x 1,024: PERF.md §6, PR 46, has the sweep on the chip.
    block_q: int = 1024,
    block_k: int = 1024,
    blocks: Optional[jax.Array] = None,
    block_size: Optional[int] = None,
) -> jax.Array:
    """Blockwise (flash) attention, the one entry point of the mixers.

    q [B, H, Tq, D]; k [B, Hkv, Tk, D]; v [B, Hkv, Tk, Dv], GQA via
    H % Hkv == 0. Dv may differ from D; ``sm_scale`` is the caller's,
    D ** -0.5 where none is given. Which road it takes follows from what
    it can observe: a ring over the ambient mesh (``jax.set_mesh``) where
    that splits the sequence, else the Pallas kernels where they fit
    (``_kernels_fit``), else the XLA reference. ``window=w`` (causal only)
    keeps keys 0 <= i - j < w of row i: the kernels' road takes it through
    the windowed kernels, whose grids walk the band alone, at blocks of
    their own, and the reference masks it; the ring refuses it.
    ``blocks`` [B, Hkv, T, ceil(T / block_size)] bool (causal self-attention
    only; ``select_blocks`` makes it) keeps, of the keys row i of a K/V group
    sees, those in the blocks of ``block_size`` keys it marks: the sparse
    kernels where they fit, a masked soft-max elsewhere; the ring refuses it.
    """
    b, h, tq, d = q.shape
    hkv, tk, d_v = k.shape[1], k.shape[2], v.shape[-1]
    if causal and tq > tk:
        # End-aligned (kv-cache) causal semantics put the first
        # tq - tk query rows before every key; their softmax is over an
        # empty set. A kv cache always satisfies tk >= tq.
        raise ValueError(
            f"causal attention requires Tq <= Tk (got Tq={tq}, Tk={tk}): "
            "query rows are aligned to the END of the key sequence"
        )
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} needs causal=True and at least the row itself"
        )
    scale = sm_scale if sm_scale is not None else 1.0 / d**0.5
    if blocks is not None:
        if not causal or window is not None or tq != tk or not block_size:
            raise ValueError(
                "blocks= is causal self-attention's, with its block_size and "
                f"no window (causal={causal}, window={window}, Tq={tq}, "
                f"Tk={tk}, block_size={block_size})"
            )
        if logical_axis_shards("seq") > 1:
            raise ValueError(
                f"blocks= under mesh axis {ambient_axes('seq')}, which splits "
                "the sequence: the ring has no selection"
            )
        return _sparse_attention(q, k, v, blocks, block_size, scale)
    if logical_axis_shards("seq") > 1:
        from .ring_attention import ring_attention  # it imports this module

        axes = ambient_axes("seq")
        if window is not None:
            raise ValueError(
                f"window={window} under mesh axis {axes}, which splits the "
                "sequence: the ring has no band"
            )
        if d_v != d:
            raise ValueError(
                f"the ring's blocks are [.., {d}] throughout: v {v.shape} "
                f"has another head dim than q {q.shape}, and mesh axis "
                f"{axes} splits the sequence"
            )
        if h != hkv:
            k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
        spec = ambient_spec(("batch", "heads", "seq", None))
        # Blocks of 512 where one device's kernels run 1,024: ROADMAP A13.
        return jax.shard_map(
            lambda *qkv: ring_attention(*qkv, axes, causal, scale, 512),
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
        )(q, k, v)
    if not _kernels_fit(tq, tk, d, d_v):
        return attention_reference(
            q, k, v, causal=causal, window=window, sm_scale=scale
        )
    if window is not None:
        # K and V stay at their own heads: the windowed kernels' index maps
        # find a q head's.
        block_q, block_k = WINDOW_BLOCK_Q, WINDOW_BLOCK_K
    elif h != hkv:
        k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    o = _flash(
        q.reshape(b * h, tq, d), k.reshape(-1, tk, d), v.reshape(-1, tk, d_v),
        causal, scale, block_q, block_k, window,
    )
    return o.reshape(b, h, tq, d_v)
