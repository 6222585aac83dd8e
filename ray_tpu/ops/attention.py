"""Flash attention for TPU (pallas) with a portable reference path.

The reference framework has no attention kernels at all (it delegates
compute to torch); this is net-new capability required by the TPU
north-star (BASELINE.md long-context targets). Design follows the
standard blockwise-softmax scheme: iterate kv blocks innermost,
carrying a running (max, sum, acc) triple in VMEM so the full [Tq, Tk]
score matrix never materializes in HBM.

Forward and backward are pallas kernels on a TPU backend (MXU matmuls
in f32 accumulation; the backward recomputes probabilities from the saved
log-sum-exp). Which keys a row sees is a mask (``_Mask``: causal, a band, a
bitmap of chosen blocks, a bit a chosen key), and a mask is data: each
pass's tile update is written once, a grid step runs it as its tile's
position under the mask asks (``_by_position``), and one forward and one
backward call take any mask. On any other backend
`flash_attention` is `attention_reference`; which one ran is visible in the
lowered program (`tpu_custom_call`), and chip_smoke.py asserts it. Where the
ambient mesh splits the sequence it is the ring of `ring_attention.py` over
the same blocks (`_block_fwd`, `_block_bwd`).
"""
from __future__ import annotations

import functools
import inspect
import os
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.mesh import ambient_axes, ambient_spec, logical_axis_shards
from ..util import tracing

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


def kernel_entry(*static: str, reads: Optional[Callable[[], tuple]] = None):
    """A kernel's call site behind one jitted entry that JAX inlines as it
    traces: ``@kernel_entry("heads", "norm")`` over a function whose body ends
    in ``pl.pallas_call(...)(...)``. JAX keeps one jaxpr a (function, shapes
    and dtypes of its arrays, values of its ``static`` arguments), and at an
    inlined call writes that jaxpr's equations again under the caller's name
    stack: a model's second layer of a shape runs no Python of the wrapper or
    of the kernel's body (nine Mamba layers traced ``_ssd_bwd_kernel``, whose
    body differentiates a chunk, nine times: 24 of the Granite cell's 29 s of
    tracing, PERF.md §6, PR 68), and the step's text holds every call where it
    was, under its layer's scopes: the equations are the caller's, no ``jit``
    is left in the jaxpr.

    Arrays (and None) are arguments; everything else a trace follows is named
    in ``static`` and hashes by value, so it is a number, a string, a dtype or
    a tuple of them, never a closure made at the call (a ``_Mask``): a wrapper
    that builds ``functools.partial(kernel, norm=norm)`` does so inside the
    entry, from the static ``norm``. What a trace depends on beside its
    arguments is part of the key too, here and nowhere else: the
    interpreter's switch, the backend's probe (benchmarks/rehearse.py and
    tests replace ``_on_tpu``), and ``reads()``, the module constants and
    functions the body reads that a test patches. Under a key that differs
    the body is traced anew, never taken for another's.

    The entry counts its calls and its traces (``util/tracing.py``
    ``count_entry``): "52 calls, 6 traces" is what the mechanism saved."""
    def wrap(fn):
        name = f"{fn.__module__.rpartition('.')[2]}.{fn.__name__}"
        parameters = inspect.signature(fn).parameters
        names = tuple(parameters)
        defaults = {k: p.default for k, p in parameters.items()
                    if p.default is not p.empty}

        def body(*, traced_under, **operands):
            tracing.count_entry(name, True)
            return fn(**operands)

        # What JAX reports a trace under (``ray_tpu.compile.trace``).
        body.__name__ = body.__qualname__ = fn.__name__
        entry = jax.jit(body, static_argnames=("traced_under", *static),
                        inline=True)

        @functools.wraps(fn)
        def call(*args, **kwargs):
            tracing.count_entry(name, False)
            under = (_interpret(), _on_tpu(), *(reads() if reads else ()))
            return entry(traced_under=under,
                         **{**defaults, **dict(zip(names, args)), **kwargs})

        return call

    return wrap


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


# ---------------------------------------------------------------------- a mask
# Which keys a row sees. The kernels below know nothing else of it than what
# a ``_Mask`` says, at trace time (nothing of it reaches the device but the
# arithmetic its functions trace):
#
# - the two walks a grid's last axis makes: over a row block's keys (the
#   forward and dQ) and over a key block's rows (dK/dV). For each, the grid's
#   last extent (``key_steps``; ``row_steps``, once for each of the ``group``
#   q heads that a K/V head serves, 1 where K and V come repeated), the
#   block the walked operands' index maps fetch at step j (``key_index``,
#   ``row_index``: a step that is off the walk is held at a block that is
#   resident, so the pipeline issues no copy), and the tile step j stands on
#   with its class (``keys``, ``rows``: the block, ``live``, and ``interior``
#   or None): an interior tile (every key visible to every row, none of them
#   padding) needs no mask, an edge tile the masked body, a dead tile neither
#   arithmetic nor a copy;
# - ``sees``: an edge tile's [block_q, block_k] boolean. A mask may read an
#   operand of its own for it (the bitmap's words, [K/V heads, rows, lanes]:
#   the calls hand a step its row block's);
# - its three kernels' names, what XLA is told its calls cost (``flops``: how
#   many tiles a walk visits is the mask's) and the VMEM they may take.
#
# Causal (``_causal_mask``): the tile's position against the diagonal, which
# the program ids and the static lengths say. Both walks cover the whole
# grid, dead steps waiting at the first or last live block, and it alone
# declares interior tiles (PERF.md §6, PR 46). A non-causal call is a causal
# one whose diagonal lies beyond the last key. K and V come repeated to q's
# heads (ROADMAP A5b).
#
# A band (``_window_mask``): row i sees keys 0 <= i - j < window, so a block
# of rows meets only the few blocks of keys its band crosses (and a block of
# keys the few blocks of rows). The walks visit those and no others, from the
# band's first block. Names of their own: a trace prices a call by its
# kernel's name, and a windowed call costs window / T of a causal one. K and
# V stay at their own heads (a q head's group is found by the index maps), and
# dk and dv sum over the group inside the kernel.
#
# A bitmap (``_bitmap_mask``), block-sparse top-k attention (InfLLM-V2): row
# i of K/V group g sees, of the keys up to its own, those in the blocks of
# ``block_size`` keys that ``select_blocks`` chose for (i, g), a set that
# differs from row to row and from group to group. The mask is made from a
# bitmap and never from a [T, T] array: the chosen blocks of a key tile are
# the bits of one int32 a row (``_pack``: [B * G, T, key tiles] words, 16 MiB
# at 16k tokens and two groups), which a grid step takes from its row tile's
# words by its key tile's lane and shifts by each column's block. A tile above
# the diagonal, or one in which no row chose a block, runs nothing. What a
# tile costs is a dense tile's matmuls: the rows of a tile choose differently,
# and together they choose nearly every block below them (a kernel that
# gathered each row's 64 blocks would move 2 MiB of K and V a row and group,
# 64 GiB a forward at 16k, and put 16 rows on the MXU: PERF.md §6, PR 54). K
# and V stay at their own heads, as under a band.


class _Mask(NamedTuple):
    block_q: int
    block_k: int
    tq_p: int  # the lengths padded to whole blocks
    tk_p: int
    group: int
    key_steps: int
    row_steps: int
    keys: Callable  # (iq, j) -> (ik, live, interior)
    rows: Callable  # (ik, j) -> (jq, live, interior)
    key_index: Callable  # (i, j) -> key block
    row_index: Callable  # (i, j) -> row block
    sees: Callable  # (iq, ik[, the rows' words of tile ik]) -> bool [block_q, block_k]
    kernels: tuple  # forward, dK/dV, dQ
    flops: Optional[tuple] = None  # of the three calls; None: no cost is stated
    bodies: tuple = (False, True)  # some tile of the grid is (interior, edge)
    vmem_limit_bytes: Optional[int] = None
    # A mask whose words are not one lane a key tile (``_select_mask``): the
    # lanes a step's block of them has, (walked) -> its index map, and
    # (the block [block_q, lanes], ik) -> the tile's words. None: the bitmap's.
    words_lanes: Optional[int] = None
    words_index: Optional[Callable] = None
    tile_words: Optional[Callable] = None


def _blocks(q, k, block_q: int, block_k: int):
    """The blocks cut to the lengths, and the lengths padded to them."""
    tq, tk = q.shape[1], k.shape[1]
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    tq_p = (tq + block_q - 1) // block_q * block_q
    tk_p = (tk + block_k - 1) // block_k * block_k
    return block_q, block_k, tq_p, tk_p


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


def _causal_mask(q, k, v, causal: bool, block_q: int, block_k: int) -> _Mask:
    """q [bh, tq, d]; k [bh, tk, d]; v [bh, tk, d_v]."""
    bh, tq, d = q.shape
    tk, d_v = k.shape[1], v.shape[2]
    block_q, block_k, tq_p, tk_p = _blocks(q, k, block_q, block_k)
    tile = dict(causal=causal, block_q=block_q, block_k=block_k,
                seq_q=tq, seq_k=tk)
    n_interior, n_edge, _ = causal_tiles(tq, tk, block_q, block_k, causal)
    pairs = bh * tq_p * tk_p

    def sees(iq, ik):
        """The keys of block ``ik`` that are no padding and that the rows of
        block ``iq`` see."""
        shape = (block_q, block_k)
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = kpos < tk  # padded keys
        if causal:
            # Ends aligned (kv-cache semantics, matching attention_reference):
            # query row i attends keys up to i + (tk - tq).
            qpos = iq * block_q + (tk - tq) + jax.lax.broadcasted_iota(
                jnp.int32, shape, 0
            )
            mask = jnp.logical_and(mask, qpos >= kpos)
        return mask

    return _Mask(
        block_q, block_k, tq_p, tk_p, group=1,
        key_steps=tk_p // block_k, row_steps=tq_p // block_q,
        keys=lambda iq, j: (j, *_tile_class(iq, j, **tile)),
        rows=lambda ik, j: (j, *_tile_class(j, ik, **tile)),
        # A dead step of a row block's walk is held at its last live key
        # block; those of a key block's walk come first and wait at its first
        # live row block.
        key_index=lambda i, j: jax.lax.min(j, _last_live_key(i, **tile)),
        row_index=lambda i, j: jax.lax.max(j, _first_live_row(i, **tile)),
        sees=sees,
        kernels=(_fwd_kernel, _bwd_dkv_kernel, _bwd_dq_kernel),
        flops=(2 * pairs * (d + d_v), 5 * pairs * (d + d_v) // 2,
               5 * pairs * (d + d_v) // 2),
        bodies=(n_interior > 0, n_edge > 0),
    )


# The windowed kernels' blocks: a band of 512 fills a quarter of the two
# 1,024-key blocks a 1,024-row block would need. (PERF.md §6, PR 45, has the
# sweep on the chip.)
WINDOW_BLOCK_Q = 512
WINDOW_BLOCK_K = 512


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


def _window_mask(q, k, v, window: int, block_q: int, block_k: int) -> _Mask:
    """q [b * h, tq, d]; k, v [b * hkv, tk, .]: q head n reads K/V head
    n // (h // hkv)."""
    bh, tq, d = q.shape
    tk, d_v = k.shape[1], v.shape[2]
    block_q, block_k, tq_p, tk_p = _blocks(q, k, block_q, block_k)
    nq, nk, off = tq_p // block_q, tk_p // block_k, tk - tq
    # The two bands (``_band``'s arguments): of keys a block of rows crosses,
    # and of rows a block of keys is seen by.
    keys = (block_q, block_k, nk, off - window + 1, off + block_q - 1)
    rows = (block_k, block_q, nq, -off, block_k + window - 2 - off)
    key_steps, row_steps = _band_steps(nq, *keys), _band_steps(nk, *rows)
    key_pairs = bh * tq_p * key_steps * block_k
    row_pairs = bh * tk_p * row_steps * block_q

    def walk_keys(iq, j):
        lo, hi = _band(iq, *keys)
        ik = lo + j
        return ik, ik <= hi, None

    def walk_rows(ik, j):
        lo, hi = _band(ik, *rows)
        jq = lo + jax.lax.rem(j, jnp.int32(row_steps))
        return jq, jq <= hi, None

    def key_index(i, j):  # held at the band's last block once past it
        lo, hi = _band(i, *keys)
        return jax.lax.min(lo + j, hi)

    def row_index(i, j):
        lo, hi = _band(i, *rows)
        walk = jax.lax.rem(j, jnp.int32(row_steps))
        return jax.lax.min(lo + walk, jax.lax.max(hi, jnp.int32(0)))

    def sees(iq, ik):
        """Which keys of block ``ik`` the rows of block ``iq`` see, the ends
        aligned as under the causal mask."""
        shape = (block_q, block_k)
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        qpos = iq * block_q + (tk - tq) + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0
        )
        return (kpos < tk) & (qpos >= kpos) & (qpos - kpos < window)

    return _Mask(
        block_q, block_k, tq_p, tk_p, group=bh // k.shape[0],
        key_steps=key_steps, row_steps=row_steps,
        keys=walk_keys, rows=walk_rows, key_index=key_index,
        row_index=row_index, sees=sees,
        kernels=(_fwd_window_kernel, _bwd_dkv_window_kernel,
                 _bwd_dq_window_kernel),
        flops=(2 * key_pairs * (d + d_v), 4 * row_pairs * (d + d_v),
               2 * key_pairs * (2 * d + d_v)),
    )


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


def _bitmap_mask(q, k, block_size: int) -> _Mask:
    """q [b * h, t, d]; k [b * g, t, d]; t padded to the tiles
    (``_sparse_blocks``). The rows' words, [b * g, t, lanes] (``_pack``), are
    the calls' operand."""
    t_p = q.shape[1]
    block_q, block_k, _ = _sparse_blocks(t_p, block_size)
    nq = t_p // block_q

    def live(iq, ik):
        """The tile holds a key at or before one of its rows."""
        return ik * block_k < (iq + 1) * block_q

    def walk_rows(ik, j):
        jq = jax.lax.rem(j, jnp.int32(nq))
        return jq, live(jq, ik), None

    def key_index(i, j):  # held at the last key tile the row tile sees
        return jax.lax.min(
            j, jax.lax.div((i + 1) * block_q - 1, jnp.int32(block_k)))

    def row_index(i, j):  # a dead step waits at the first live row tile
        return jax.lax.max(jax.lax.rem(j, jnp.int32(nq)),
                           jax.lax.div(i * block_k, jnp.int32(block_q)))

    def sees(iq, ik, mine):
        """Which keys of tile ``ik`` the rows of tile ``iq`` see, from the
        rows' words of that tile. (lax's primitives, as in ``_band``.)"""
        shape = (block_q, block_k)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        bit = jax.lax.shift_right_logical(
            jnp.broadcast_to(mine, shape), jax.lax.div(col, jnp.int32(block_size)))
        chosen = jax.lax.bitwise_and(bit, jnp.int32(1)) == 1
        qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        return jnp.logical_and(chosen, qpos >= ik * block_k + col)

    return _Mask(
        block_q, block_k, t_p, t_p, group=q.shape[0] // k.shape[0],
        key_steps=t_p // block_k, row_steps=nq,
        keys=lambda iq, j: (j, live(iq, j), None), rows=walk_rows,
        key_index=key_index, row_index=row_index, sees=sees,
        kernels=(_sparse_fwd_kernel, _bwd_dkv_sparse_kernel,
                 _bwd_dq_sparse_kernel),
        # As PR 54 set it. (They compile for v5e without it too.)
        vmem_limit_bytes=64 * 2**20,
    )


# A bit a (row, key) (``_select_mask``), token-level top-k attention
# (DeepSeek-V3.2's sparse attention): row i sees the keys ``index_keys`` chose
# for it, one set for all heads, which differs from row to row and is made
# from the rows' own scores. The words [B, rows, lanes] int32 hold key s of a
# row at lane group s // 4096, lane s % 128, bit (s % 4096) // 128 (8 MiB at
# 8k tokens): a key tile of ``block_k`` keys is ``block_k // 128`` adjacent
# bits of one group's 128 lanes, which a step's block of words brings (the
# group is the index map's, so a tile's words arrive as its K and V do), and
# column c of the tile reads bit c // 128 of lane c % 128: shifts and no
# movement across lanes. The causal cut is in the bits (a row chooses among
# the keys up to its own). What a tile costs is a dense tile's matmuls, as
# under the bitmap: a tile's rows choose differently and together nearly
# every key below them. K and V come at q's heads.
SELECT_BLOCK = 1024
_GROUP_KEYS = 32 * 128  # the keys a group of 128 lanes holds a row


def _select_tiles(t: int):
    """(the tile's side, the padded length): tiles are square, a power of two
    of 128 keys so that whole tiles fill a lane group."""
    block = max(b for b in (128, 256, 512, SELECT_BLOCK) if b <= max(t, 128))
    return block, -(-t // block) * block


def _pack_keys(seen, t_p: int):
    """[B, T, T] bool -> the words [B, t_p, lanes] int32."""
    b, t, _ = seen.shape
    groups = -(-t_p // _GROUP_KEYS)
    x = jnp.pad(seen, ((0, 0), (0, t_p - t), (0, groups * _GROUP_KEYS - t)))
    x = x.reshape(b, t_p, groups, 32, 128).astype(jnp.uint32)
    words = (x << np.arange(32, dtype=np.uint32)[:, None]).sum(3, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(b, t_p, -1)


def _unpack_keys(words, t: int):
    """The words -> [B, T, T] bool."""
    b, t_p, lanes = words.shape
    x = jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(b, t_p, -1, 1, 128)
    bits = (x >> np.arange(32, dtype=np.uint32)[:, None]) & 1
    return bits.reshape(b, t_p, -1)[:, :t, :t] == 1


def _select_mask(q, heads: int) -> _Mask:
    """q [b * heads, t, d], t padded to the tiles (``_select_tiles``); the
    words [b, t, lanes] are the calls' operand."""
    t_p = q.shape[1]
    block = _select_tiles(t_p)[0]
    n = t_p // block
    bits, tiles = block // 128, _GROUP_KEYS // block  # a tile's, a group's
    field = -1 if bits == 32 else (1 << bits) - 1

    def live(iq, ik):  # the tile holds a key at or before one of its rows
        return ik <= iq

    def walk_rows(ik, j):
        return j, live(j, ik), None

    def words_index(walked: bool):
        def index(b, i, j):
            batch = jax.lax.div(b, jnp.int32(heads))
            if walked:  # key tile i's rows
                return batch, jax.lax.max(j, i), jax.lax.div(i, jnp.int32(tiles))
            return batch, i, jax.lax.div(jax.lax.min(j, i), jnp.int32(tiles))

        return index

    def tile_words(words, ik):
        """[block, 128]: the tile's bits of each row's lanes, from bit 0."""
        shift = jax.lax.rem(ik, jnp.int32(tiles)) * bits
        return jax.lax.bitwise_and(
            jax.lax.shift_right_logical(words, jnp.broadcast_to(shift, words.shape)),
            jnp.int32(field))

    def sees(iq, ik, mine):
        shape = (block, block)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        bit = jax.lax.shift_right_logical(
            jnp.concatenate([mine] * bits, axis=1) if bits > 1 else mine,
            jax.lax.div(col, jnp.int32(128)))
        return jax.lax.bitwise_and(bit, jnp.int32(1)) == 1

    return _Mask(
        block, block, t_p, t_p, group=1, key_steps=n, row_steps=n,
        keys=lambda iq, j: (j, live(iq, j), None), rows=walk_rows,
        key_index=lambda i, j: jax.lax.min(j, i),
        row_index=lambda i, j: jax.lax.max(j, i), sees=sees,
        kernels=(_fwd_select_kernel, _bwd_dkv_select_kernel,
                 _bwd_dq_select_kernel),
        vmem_limit_bytes=64 * 2**20,
        words_lanes=128, words_index=words_index, tile_words=tile_words,
    )


# ------------------------------------------------------- one tile update a pass
# Each pass (the forward, dK/dV, dQ) writes its tile update, its start and
# its finish once; a kernel is a walk that runs them as the mask says.


def _by_position(mask: _Mask, body, iq, ik, live, interior, words_ref) -> None:
    """Run ``body(sees)`` as the tile's position asks: with ``sees`` None
    (unmasked) on an interior tile, with the function that makes the tile's
    boolean on an edge tile, not at all on a dead one (a skipped tile is
    exactly a p = 0 update). ``interior`` None: every live tile is an edge
    one. A body no tile of the grid needs is not lowered."""
    edge = live
    if interior is not None:
        some_interior, some_edge = mask.bodies
        if some_interior:
            pl.when(interior)(functools.partial(body, None))
        if not some_edge:
            return
        edge = jnp.logical_and(live, jnp.logical_not(interior))

    @pl.when(edge)
    def _edge():
        if words_ref is None:
            return body(lambda: mask.sees(iq, ik))
        # A mask with words of its own: a tile in which no row chose a block
        # runs nothing either.
        mine = (mask.tile_words or _tile_words)(words_ref[0], ik)
        pl.when(jnp.max(mine) != 0)(
            lambda: body(lambda: mask.sees(iq, ik, mine)))


def _softmax_start(first, m_scr, l_scr, acc_scr) -> None:
    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)


def _softmax_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, sm_scale, sees):
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = s * sm_scale
    if sees is not None:
        s = jnp.where(sees(), s, NEG_INF)
    # A row that sees nothing of this tile keeps m at NEG_INF and adds p = 1
    # a key: the first tile it does see multiplies that by
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


def _softmax_finish(last, o_ref, lse_ref, m_scr, l_scr, acc_scr) -> None:
    @pl.when(last)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


# FlashAttention-2 style backward: probabilities recomputed per block
# from the saved log-sum-exp, two kernels so each output accumulates in
# VMEM over its contraction dimension (dk/dv over q blocks, dq over kv
# blocks) and the [Tq, Tk] score matrix never hits HBM. p and ds are
# computed in float32.


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


def _zero(first, *scratch) -> None:
    """A backward pass's start."""
    @pl.when(first)
    def _init():
        for scr in scratch:
            scr[:] = jnp.zeros_like(scr)


def _write(last, *outs) -> None:
    """A backward pass's finish: each (ref, its float32 sum)."""
    @pl.when(last)
    def _finish():
        for ref, scr in outs:
            ref[0] = scr[:].astype(ref.dtype)


def _dkv_update(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_scr, dv_scr,
                sm_scale, sees):
    """dK/dV transposes p and ds, and casts them to the inputs' dtype first,
    as the forward does its p: the MXU's one pass rounds a float32 operand
    the same, so the result is the same and 4% sooner (PERF.md §6, PR 46)."""
    q, do = q_ref[0], do_ref[0]  # [block_q, d], [block_q, d_v]
    mask = None if sees is None else sees()
    p = _tile_p(q, k_ref[0], lse_ref[0], mask, sm_scale)

    def add_dv():
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # dv before ds where p and ds are 4 MB each (1,024 x 1,024: PR 46). In a
    # smaller tile both fit beside each other, and dv's matmul between them
    # cost a band's 512 x 512 dK/dV 12.7% (PERF.md §6, PR 56).
    dv_first = p.size >= 1024 * 1024
    if dv_first:
        add_dv()
    ds = _tile_ds(p, do, v_ref[0], delta_ref[0], sm_scale)
    if not dv_first:
        add_dv()
    dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dq_update(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_scr,
               sm_scale, operands, sees):
    """``operands``: the dtype ds, do, v and k reach the MXU in, the inputs'
    where None. Under the causal mask it is float32, and the difference is
    meant: dQ transposes nothing and would only pay the cast there (+2.6%),
    and an unmasked body won it nothing, so every dQ runs the one masked body
    on every live tile (PERF.md §6, PR 46). Under a band and a bitmap the
    operands are the inputs' dtype, as PR 45 and PR 54 measured them."""
    k = k_ref[0]
    to = operands or k.dtype
    p = _tile_p(q_ref[0], k, lse_ref[0], sees(), sm_scale)
    ds = _tile_ds(p, do_ref[0].astype(to), v_ref[0].astype(to), delta_ref[0],
                  sm_scale)
    dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
        ds.astype(to), k.astype(to), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _forward(mask: _Mask, sm_scale, q_ref, k_ref, v_ref, words_ref, o_ref,
             lse_ref, *scratch) -> None:
    """The forward's walk over a row block's keys. ``scratch``: m, l, acc."""
    iq, j = pl.program_id(1), pl.program_id(2)
    _softmax_start(j == 0, *scratch)
    update = functools.partial(
        _softmax_update, q_ref, k_ref, v_ref, *scratch, sm_scale)
    _by_position(mask, update, iq, *mask.keys(iq, j), words_ref)
    _softmax_finish(j == pl.num_programs(2) - 1, o_ref, lse_ref, *scratch)


def _dkv(mask: _Mask, sm_scale, q_ref, k_ref, v_ref, words_ref, do_ref, lse_ref,
         delta_ref, dk_ref, dv_ref, dk_scr, dv_scr) -> None:
    """dK/dV's walk over a key block's rows, once for each q head of the K/V
    head's group."""
    ik, j = pl.program_id(1), pl.program_id(2)
    _zero(j == 0, dk_scr, dv_scr)
    update = functools.partial(
        _dkv_update, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_scr,
        dv_scr, sm_scale)
    jq, live, interior = mask.rows(ik, j)
    _by_position(mask, update, jq, ik, live, interior, words_ref)
    _write(j == pl.num_programs(2) - 1, (dk_ref, dk_scr), (dv_ref, dv_scr))


def _dq(mask: _Mask, sm_scale, q_ref, k_ref, v_ref, words_ref, do_ref, lse_ref,
        delta_ref, dq_ref, dq_scr, operands=None) -> None:
    """dQ's walk over a row block's keys."""
    iq, j = pl.program_id(1), pl.program_id(2)
    _zero(j == 0, dq_scr)
    update = functools.partial(
        _dq_update, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_scr,
        sm_scale, operands)
    ik, live, _ = mask.keys(iq, j)
    _by_position(mask, update, iq, ik, live, None, words_ref)
    _write(j == pl.num_programs(2) - 1, (dq_ref, dq_scr))


# The twelve functions handed to ``pallas_call``, a name a mask and pass: a
# trace names a call by the first ``*_kernel`` identifier in its Mosaic module
# (benchmarks/lib/trace.py) and the benchmark's FLOP tables price it by that
# name (benchmarks/lib/flops*.py), so no other function here ends in
# ``_kernel`` (but ``_index_kernel``, which makes the fourth mask's words).
# Only the bitmap's and the selection's take the rows' words, after v.


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, mask, sm_scale):
    _forward(mask, sm_scale, q_ref, k_ref, v_ref, None, *refs)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, *refs, mask, sm_scale):
    _dkv(mask, sm_scale, q_ref, k_ref, v_ref, None, *refs)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, *refs, mask, sm_scale):
    _dq(mask, sm_scale, q_ref, k_ref, v_ref, None, *refs, operands=jnp.float32)


def _fwd_window_kernel(q_ref, k_ref, v_ref, *refs, mask, sm_scale):
    _forward(mask, sm_scale, q_ref, k_ref, v_ref, None, *refs)


def _bwd_dkv_window_kernel(q_ref, k_ref, v_ref, *refs, mask, sm_scale):
    _dkv(mask, sm_scale, q_ref, k_ref, v_ref, None, *refs)


def _bwd_dq_window_kernel(q_ref, k_ref, v_ref, *refs, mask, sm_scale):
    _dq(mask, sm_scale, q_ref, k_ref, v_ref, None, *refs)


def _sparse_fwd_kernel(*refs, mask, sm_scale):
    _forward(mask, sm_scale, *refs)


def _bwd_dkv_sparse_kernel(*refs, mask, sm_scale):
    _dkv(mask, sm_scale, *refs)


def _bwd_dq_sparse_kernel(*refs, mask, sm_scale):
    _dq(mask, sm_scale, *refs)


def _fwd_select_kernel(*refs, mask, sm_scale):
    _forward(mask, sm_scale, *refs)


def _bwd_dkv_select_kernel(*refs, mask, sm_scale):
    _dkv(mask, sm_scale, *refs)


def _bwd_dq_select_kernel(*refs, mask, sm_scale):
    _dq(mask, sm_scale, *refs)


# ------------------------------------------------------ the two calls, any mask
# Grids are (head, the block the outputs stay on, the walk). q and k (and
# their gradients) have d lanes; v, o, do and dv have d_v; lse and delta one,
# kept 3-D ([bh, tq, 1]) so the trailing dims satisfy TPU tiling
# (block_q % 8, last dim == full dim).


def _pad_rows(x, t_p: int):
    pad = t_p - x.shape[1]
    return x if not pad else jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def _tiles(mask: _Mask):
    """(rows, keys): the BlockSpec of a block of rows, and of keys, ``width``
    lanes wide under an index map."""
    return (lambda width, index: pl.BlockSpec((1, mask.block_q, width), index),
            lambda width, index: pl.BlockSpec((1, mask.block_k, width), index))


def _here(b, i, j):
    return b, i, 0


def _key_walk(mask: _Mask):
    """The index map of K and V where the last axis walks a row block's keys
    for q head ``b``: at K/V head b // group (no arithmetic where K and V
    come repeated)."""
    def index(b, i, j):
        head = b if mask.group == 1 else jax.lax.div(b, jnp.int32(mask.group))
        return head, mask.key_index(i, j), 0

    return index


def _row_walk(mask: _Mask):
    """The same for q, do, lse and delta where the last axis walks a key
    block's rows for K/V head ``b``: once for each q head of its group."""
    def index(b, i, j):
        head = b if mask.group == 1 else (
            b * mask.group + jax.lax.div(j, jnp.int32(mask.row_steps)))
        return head, mask.row_index(i, j), 0

    return index


def _words_spec(mask: _Mask, words, walked: bool) -> list:
    """The spec of a mask's words [K/V heads, rows, lanes], or none: the rows
    of the step's own tile at q head b's K/V head, or, where K/V head b's
    rows are ``walked``, the walk's."""
    if words is None:
        return []
    if mask.words_index is not None:
        return [pl.BlockSpec((1, mask.block_q, mask.words_lanes),
                             mask.words_index(walked))]

    def index(b, i, j):
        if walked:
            return b, mask.row_index(i, j), 0
        return jax.lax.div(b, jnp.int32(mask.group)), i, 0

    return [pl.BlockSpec((1, mask.block_q, words.shape[2]), index)]


def _call(mask: _Mask, which: int, sm_scale: float, q, k, v, **call):
    """``pallas_call`` of the mask's kernel ``which`` (0 the forward, 1
    dK/dV, 2 dQ) with what the three share. q, k, v: padded."""
    bh, tq_p, d_v = q.shape[0], q.shape[1], v.shape[2]
    walked = (k.shape[1] * mask.row_steps * mask.block_q if which == 1
              else tq_p * mask.key_steps * mask.block_k)
    return pl.pallas_call(
        functools.partial(mask.kernels[which], mask=mask, sm_scale=sm_scale),
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=mask.vmem_limit_bytes,
        ),
        # XLA schedules around a call by what it is told here.
        cost_estimate=mask.flops and pl.CostEstimate(
            flops=mask.flops[which],
            bytes_accessed=(q.size + k.size + v.size + bh * tq_p * d_v) * 2,
            transcendentals=bh * walked,
        ),
        **call,
    )


def _forward_call(mask: _Mask, q, k, v, sm_scale: float, words=None):
    """q [bh, tq, d]; k, v [bh // mask.group, tk, .]; ``words`` where the
    mask has them. -> (o, lse [bh, tq])."""
    bh, tq, d = q.shape
    d_v, block_q = v.shape[2], mask.block_q
    q = _pad_rows(q, mask.tq_p)
    k, v = _pad_rows(k, mask.tk_p), _pad_rows(v, mask.tk_p)
    rows, keys = _tiles(mask)
    key_walk = _key_walk(mask)
    o, lse = _call(
        mask, 0, sm_scale, q, k, v,
        grid=(bh, mask.tq_p // block_q, mask.key_steps),
        in_specs=[rows(d, _here), keys(d, key_walk), keys(d_v, key_walk),
                  *_words_spec(mask, words, False)],
        out_specs=[rows(d_v, _here), rows(1, _here)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, mask.tq_p, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, mask.tq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
    )(q, k, v, *(() if words is None else (words,)))
    return o[:, :tq], lse[:, :tq, 0]


def _backward_call(mask: _Mask, q, k, v, o, lse, do, sm_scale: float,
                   words=None):
    """(dq, dk, dv) of ``_forward_call``'s operands, given its (o, lse) or a
    ring's merged ones; dk and dv at K and V's own heads."""
    bh, tq, d = q.shape
    tk, d_v = k.shape[1], v.shape[2]
    block_q, block_k = mask.block_q, mask.block_k
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [bh, tq, 1]
    q, do = _pad_rows(q, mask.tq_p), _pad_rows(do, mask.tq_p)
    lse3, delta3 = _pad_rows(lse[..., None], mask.tq_p), _pad_rows(delta, mask.tq_p)
    k, v = _pad_rows(k, mask.tk_p), _pad_rows(v, mask.tk_p)
    operands = (q, k, v, *(() if words is None else (words,)), do, lse3, delta3)
    rows, keys = _tiles(mask)
    row_walk, key_walk = _row_walk(mask), _key_walk(mask)
    dk, dv = _call(
        mask, 1, sm_scale, q, k, v,
        grid=(k.shape[0], mask.tk_p // block_k, mask.group * mask.row_steps),
        in_specs=[rows(d, row_walk), keys(d, _here), keys(d_v, _here),
                  *_words_spec(mask, words, True), rows(d_v, row_walk),
                  rows(1, row_walk), rows(1, row_walk)],
        out_specs=[keys(d, _here), keys(d_v, _here)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
    )(*operands)
    dq = _call(
        mask, 2, sm_scale, q, k, v,
        grid=(bh, mask.tq_p // block_q, mask.key_steps),
        in_specs=[rows(d, _here), keys(d, key_walk), keys(d_v, key_walk),
                  *_words_spec(mask, words, False), rows(d_v, _here),
                  rows(1, _here), rows(1, _here)],
        out_specs=rows(d, _here),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(*operands)
    return dq[:, :tq], dk[:, :tk], dv[:, :tk]


# The entries (``kernel_entry``) stand above the mask, here for the bitmap and
# at ``_block_fwd`` and ``_block_bwd`` for the other two: a ``_Mask`` is a
# tuple of closures made afresh at every call, which as a static argument
# would hash by what it is and not by what it says, and no second layer would
# find the first's trace. What makes the mask is plain values.


@kernel_entry("sm_scale", "block_size")
def _sparse_fwd(q, k, v, words, sm_scale, block_size):
    return _forward_call(_bitmap_mask(q, k, block_size), q, k, v, sm_scale, words)


@kernel_entry("sm_scale", "block_size")
def _sparse_bwd(q, k, v, words, o, lse, do, sm_scale, block_size):
    return _backward_call(
        _bitmap_mask(q, k, block_size), q, k, v, o, lse, do, sm_scale, words)


# The bitmap's road has a ``custom_vjp`` of its own: its residuals carry other
# names (a policy keeps a sparse layer's apart from a full one's), and the
# words are an operand with no gradient.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sparse_flash(q, k, v, words, sm_scale, block_size):
    """q [b * h, t, d]; k, v [b * g, t, .]; words [b * g, t, lanes]; t
    padded to the tiles."""
    return _sparse_fwd(q, k, v, words, sm_scale, block_size)[0]


def _sparse_flash_fwd(q, k, v, words, sm_scale, block_size):
    o, lse = _sparse_fwd(q, k, v, words, sm_scale, block_size)
    # Named for the remat policy, as ``_flash``'s are.
    o, lse = checkpoint_name(o, "sparse_o"), checkpoint_name(lse, "sparse_lse")
    return o, (q, k, v, words, o, lse)


def _sparse_flash_bwd(sm_scale, block_size, res, do):
    q, k, v, words, o, lse = res
    dq, dk, dv = _sparse_bwd(q, k, v, words, o, lse, do, sm_scale, block_size)
    return dq, dk, dv, np.zeros(words.shape, jax.dtypes.float0)


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


# ------------------------------------------- the chosen keys, and attention under them


@kernel_entry("sm_scale", "heads")
def _select_fwd(q, k, v, words, sm_scale, heads):
    return _forward_call(_select_mask(q, heads), q, k, v, sm_scale, words)


@kernel_entry("sm_scale", "heads")
def _select_bwd(q, k, v, words, o, lse, do, sm_scale, heads):
    return _backward_call(
        _select_mask(q, heads), q, k, v, o, lse, do, sm_scale, words)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _select_flash(q, k, v, words, sm_scale, heads):
    """q, k [b * heads, t, d]; v [b * heads, t, d_v]; words [b, t, lanes]; t
    padded to the tiles."""
    return _select_fwd(q, k, v, words, sm_scale, heads)[0]


def _select_flash_fwd(q, k, v, words, sm_scale, heads):
    o, lse = _select_fwd(q, k, v, words, sm_scale, heads)
    # Named for the remat policy, as ``_flash``'s are.
    o, lse = checkpoint_name(o, "select_o"), checkpoint_name(lse, "select_lse")
    return o, (q, k, v, words, o, lse)


def _select_flash_bwd(sm_scale, heads, res, do):
    q, k, v, words, o, lse = res
    dq, dk, dv = _select_bwd(q, k, v, words, o, lse, do, sm_scale, heads)
    return dq, dk, dv, np.zeros(words.shape, jax.dtypes.float0)


_select_flash.defvjp(_select_flash_fwd, _select_flash_bwd)


def _select_attention(q, k, v, words, scale: float):
    """``flash_attention``'s road under ``keys``: the kernels where they fit,
    else a masked soft-max from the unpacked words (the CPU's road, at test
    sizes)."""
    b, h, t, d = q.shape
    d_v = v.shape[-1]
    if not _kernels_fit(t, t, d, d_v):
        s = jnp.einsum("bhtd,bhsd->bhts", q, k,
                       preferred_element_type=jnp.float32) * scale
        seen = _unpack_keys(words, t)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
        return jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), v)
    t_p = _select_tiles(t)[1]
    o = _select_flash(
        _pad_rows(q.reshape(b * h, t, d), t_p),
        _pad_rows(k.reshape(b * h, t, d), t_p),
        _pad_rows(v.reshape(b * h, t, d_v), t_p), words, scale, h,
    )
    return o[:, :t].reshape(b, h, t, d_v)


_INT_MIN = -(2**31)
# The indexer kernel's blocks: rows a grid step, keys a trip of its loop.
INDEX_BLOCK_Q = 256
INDEX_BLOCK_K = 512


def _ordered(x):
    """float32 -> int32 whose signed order is the floats' (-0.0 as 0.0)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    return jnp.where(bits < 0, jax.lax.bitwise_xor(bits, jnp.int32(2**31 - 1)), bits)


def _index_kernel(q_ref, k_ref, w_ref, o_ref, keys_scr, *, topk, block_k):
    """One block of rows against every key up to their own: the scores, each
    row's ``topk``-th largest, the words. q_ref [1, heads, rows, d]; k_ref
    [1, t, d]; w_ref [1, rows, heads] float32; o_ref [1, rows, lanes];
    keys_scr [rows, t] int32, the scores in ``_ordered``'s form, _INT_MIN
    where the row does not see the key."""
    iq = pl.program_id(1)
    heads, rows = q_ref.shape[1], q_ref.shape[2]
    t_p = k_ref.shape[1]
    live = jax.lax.div((iq + 1) * rows + (block_k - 1), jnp.int32(block_k))
    keys_scr[...] = jnp.full(keys_scr.shape, _INT_MIN, jnp.int32)
    w = w_ref[0]

    def score_tile(j, carry):
        at = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(at, block_k), :]
        acc = jnp.zeros((rows, block_k), jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + w[:, h:h + 1] * jnp.maximum(s, 0.0)
        shape = (rows, block_k)
        row = iq * rows + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        col = at + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        keys_scr[:, pl.ds(at, block_k)] = jnp.where(
            col <= row, _ordered(acc), _INT_MIN)
        return carry

    jax.lax.fori_loop(0, live, score_tile, 0)

    def count_at_least(trial):
        """[rows, 1]: how many of a row's keys stand at or above ``trial``."""
        def some(j, lanes):
            at = pl.multiple_of(j * block_k, block_k)
            hit = (keys_scr[:, pl.ds(at, block_k)] >= trial).astype(jnp.int32)
            for c in range(block_k // 128):
                lanes = lanes + hit[:, c * 128:(c + 1) * 128]
            return lanes

        lanes = jax.lax.fori_loop(
            0, live, some, jnp.zeros((rows, 128), jnp.int32))
        return jnp.sum(lanes, axis=1, keepdims=True)

    def bisect(i, found):
        # The threshold's bits from the top, in the order's unsigned form:
        # the largest value that ``topk`` keys or more stand at or above.
        trial = jax.lax.bitwise_or(
            found, jax.lax.shift_left(jnp.int32(1), 31 - i))
        enough = count_at_least(jax.lax.bitwise_xor(trial, jnp.int32(_INT_MIN))) >= topk
        return jnp.where(enough, trial, found)

    found = jax.lax.fori_loop(0, 32, bisect, jnp.zeros((rows, 1), jnp.int32))
    # A row with fewer keys than ``topk`` finds 0, below every score: it keeps
    # what it sees, which _INT_MIN is not.
    least = jnp.maximum(
        jax.lax.bitwise_xor(found, jnp.int32(_INT_MIN)), jnp.int32(_INT_MIN + 1))
    for group in range(o_ref.shape[2] // 128):
        word = jnp.zeros((rows, 128), jnp.int32)
        for bit in range(32):
            at = group * _GROUP_KEYS + bit * 128
            if at >= t_p:
                break
            chosen = (keys_scr[:, at:at + 128] >= least).astype(jnp.int32)
            word = jax.lax.bitwise_or(word, jax.lax.shift_left(chosen, jnp.int32(bit)))
        o_ref[0, :, group * 128:(group + 1) * 128] = word


def _index_blocks():
    """What an indexer's trace reads beside its arguments."""
    return (INDEX_BLOCK_Q, INDEX_BLOCK_K, SELECT_BLOCK)


@kernel_entry("topk", reads=_index_blocks)
def _index_call(q, k, w, topk):
    """q [b, heads, t, d]; k [b, t, d]; w [b, t, heads] float32; t padded to
    the selection's tiles -> the words [b, t, lanes]."""
    b, heads, t_p, d = q.shape
    rows, block_k = min(INDEX_BLOCK_Q, t_p), min(INDEX_BLOCK_K, t_p)
    lanes = -(-t_p // _GROUP_KEYS) * 128
    return pl.pallas_call(
        functools.partial(_index_kernel, topk=topk, block_k=block_k),
        grid=(b, t_p // rows),
        in_specs=[
            pl.BlockSpec((1, heads, rows, d), lambda n, i: (n, 0, i, 0)),
            pl.BlockSpec((1, t_p, d), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((1, rows, heads), lambda n, i: (n, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, lanes), lambda n, i: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t_p, lanes), jnp.int32),
        scratch_shapes=[pltpu.VMEM((rows, t_p), jnp.int32)],
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=96 * 2**20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=b * heads * t_p * t_p * d, transcendentals=0,
            bytes_accessed=(q.size + k.size) * 2 + w.size * 4 + b * t_p * lanes * 4,
        ),
    )(q, k, w)


def index_scores(q, k, w):
    """[B, T, T] float32: I[t, s] = sum_h w[t, h] ReLU(q[t, h] . k[s]), the
    products in float32 from the operands as they come. The XLA road, whole:
    for test sizes."""
    s = jnp.einsum("bhtd,bsd->bhts", q, k, preferred_element_type=jnp.float32)
    return jnp.einsum("bhts,bth->bts", jnp.maximum(s, 0.0), w.astype(jnp.float32))


def index_keys(q, k, w, *, topk: int):
    """The keys each row attends, one set for all heads, as the words
    ``flash_attention(keys=)`` takes: row t keeps, of the keys s <= t, those
    whose score I[t, s] (``index_scores``) is at or above the ``topk``-th
    largest of them (every key while t < topk; ties kept, so that no order
    among equals is asked). q [B, Hi, T, D] and k [B, T, D] the index
    queries and the one index key a token, w [B, T, Hi] the heads' weights.
    One Pallas kernel (``_index_kernel``) where the kernels fit, XLA's lines
    elsewhere. An integer set: nothing here is differentiated."""
    q, k, w = (jax.lax.stop_gradient(x) for x in (q, k, w))
    b, heads, t, d = q.shape
    t_p = _select_tiles(t)[1]
    w = w.astype(jnp.float32)
    if _kernels_fit(t, t, d, d):
        words = _index_call(
            jnp.pad(q, ((0, 0), (0, 0), (0, t_p - t), (0, 0))),
            _pad_rows(k, t_p), _pad_rows(w, t_p), topk)
    else:
        seen = _visible(t, t, None)
        scores = jnp.where(seen, index_scores(q, k, w), -jnp.inf)
        # -inf while a row sees fewer than topk: it keeps what it sees
        least = jax.lax.top_k(scores, min(topk, t))[0][..., -1:]
        words = _pack_keys(seen & (scores >= least), t_p)
    # Named for the remat policy (models/llama.py REPLAY_KEEPS): a layer's
    # replay is handed the set and runs no indexer.
    return checkpoint_name(words, "select_words")


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


def _mask_of(q, k, v, causal, block_q, block_k, window):
    """The mask of one block of the kernels' road: a band under a ``window``
    (which alone takes K and V at fewer heads than q's), else the causal
    one."""
    if window is not None:
        return _window_mask(q, k, v, window, block_q, block_k)
    return _causal_mask(q, k, v, causal, block_q, block_k)


def _tiling():
    """What a causal or banded trace reads beside its arguments."""
    return (_tile_class,)


_BLOCK_STATICS = ("causal", "scale", "block_q", "block_k", "window")


@kernel_entry(*_BLOCK_STATICS, reads=_tiling)
def _block_fwd(q, k, v, causal, scale, block_q, block_k, window=None):
    """One block of attention on [bh, t, d] operands -> (o, lse [bh, tq])."""
    if _kernels_fit(q.shape[1], k.shape[1], q.shape[2], v.shape[2]):
        mask = _mask_of(q, k, v, causal, block_q, block_k, window)
        return _forward_call(mask, q, k, v, scale)
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


@kernel_entry(*_BLOCK_STATICS, reads=_tiling)
def _block_bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k,
               window=None):
    """(dq, dk, dv) of one block given the (o, lse) of the whole row, which
    for a ring is the merged one: probabilities are recomputed from it,
    p = exp(s - lse). In XLA the memory high-water is the [tq, tk] block
    per batch*head slice."""
    if _kernels_fit(q.shape[1], k.shape[1], q.shape[2], v.shape[2]):
        mask = _mask_of(q, k, v, causal, block_q, block_k, window)
        return _backward_call(mask, q, k, v, o, lse, do, scale)
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
    keys: Optional[jax.Array] = None,
) -> jax.Array:
    """Blockwise (flash) attention, the one entry point of the mixers.

    q [B, H, Tq, D]; k [B, Hkv, Tk, D]; v [B, Hkv, Tk, Dv], GQA via
    H % Hkv == 0. Dv may differ from D; ``sm_scale`` is the caller's,
    D ** -0.5 where none is given. Which road it takes follows from what
    it can observe: a ring over the ambient mesh (``jax.set_mesh``) where
    that splits the sequence, else the Pallas kernels where they fit
    (``_kernels_fit``), else the XLA reference. The kernels' road takes
    one of four masks (``_Mask``). ``window=w`` (causal only) keeps keys
    0 <= i - j < w of row i: a band, at blocks of its own; the reference
    masks it; the ring refuses it. ``blocks`` [B, Hkv, T, ceil(T /
    block_size)] bool (causal self-attention only; ``select_blocks`` makes
    it) keeps, of the keys row i of a K/V group sees, those in the blocks of
    ``block_size`` keys it marks: a bitmap where the kernels fit, a masked
    soft-max elsewhere; the ring refuses it. ``keys`` (causal
    self-attention at K and V of q's heads only; ``index_keys`` makes it), the
    words [B, T padded, lanes] int32 of a bit a (row, key), keeps of the keys
    row i sees those it marks, one set for all heads: the selection's kernels
    where they fit, a masked soft-max elsewhere; the ring refuses it. Else
    the causal mask.
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
    if keys is not None:
        if not causal or window is not None or tq != tk or h != hkv:
            raise ValueError(
                "keys= is causal self-attention's, with no window and K and V "
                f"at q's heads (causal={causal}, window={window}, Tq={tq}, "
                f"Tk={tk}, heads={h}, K/V heads={hkv})"
            )
        if logical_axis_shards("seq") > 1:
            raise ValueError(
                f"keys= under mesh axis {ambient_axes('seq')}, which splits "
                "the sequence: the ring has no selection"
            )
        return _select_attention(q, k, v, keys, scale)
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
        # K and V stay at their own heads: the band's index maps find a q
        # head's.
        block_q, block_k = WINDOW_BLOCK_Q, WINDOW_BLOCK_K
    elif h != hkv:
        k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    o = _flash(
        q.reshape(b * h, tq, d), k.reshape(-1, tk, d), v.reshape(-1, tk, d_v),
        causal, scale, block_q, block_k, window,
    )
    return o.reshape(b, h, tq, d_v)
