"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): a residual
path of ``mult`` streams a token, mixed around every sublayer by three maps
computed from the streams themselves.

A token's streams X [n, C]; for a sublayer F (its norm and its mixer or FFN):

    x~ = vec(X) / rms(vec(X))              over all n C channels, no weight
    [h_pre | h_post | h_res] = x~ Phi      Phi [n C, n + n + n n]
    H_pre  = sigmoid(a_pre h_pre + b_pre)                      [n]
    H_post = 2 sigmoid(a_post h_post + b_post)                 [n]
    H_res  = SK(clip(a_res mat(h_res) + b_res, lo, hi))        [n, n]
    u = sum_i H_pre[i] X[i];  y = F(u);  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

SK is Sinkhorn-Knopp: M = exp(.), then ``sinkhorn_iters`` times each row over
(its sum + eps) and each column over (its sum + eps), which brings M to the
doubly stochastic matrices: a stream-to-stream map that neither grows nor
shrinks what it carries.

The streams are laid [n, B, T, C], a stream a whole [B, T, C] slab (a size-4
axis beside the channels would be padded to a tile's rows on the TPU), and
every map is an array [.., B, T] with the tokens along the lanes. The maps
are float32 throughout, and the small half of a hyper-connection (the maps
from x~ Phi on: sigmoids, Sinkhorn's whole loop) is XLA's: its sums over the
streams are written out, n being 4, so that it stays elementwise for XLA to
fuse. Sinkhorn's backward is its own for the same reason: autodiff would
transpose each broadcast into a reduction, forty small kernels a call,
where the written-out sums stay in one.

The big-array half, every pass over the [n, B, T, C] streams, is Pallas
kernels under ``jax.custom_vjp`` wherever the shape tiles (``_by_kernels``:
C a multiple of 128 and the tokens of ``_TILE``, on the TPU or under the
interpreter), each behind one jitted entry:

    read      ``_hc_pre_fwd_kernel``   X once: r, x~ Phi, H_pre, u        5 C
    write     ``_hc_post_fwd_kernel``  X and y once: X'                   9 C
    write's   ``_hc_post_bwd_kernel``  dX', X, y once: H_res^T dX', dy,
    backward                           the 20 sums d_post, d_res         14 C
    read's    ``_hc_pre_sums_kernel``  du, X: d_pre's 4 sums              5 C
    backward  ``_hc_pre_bwd_kernel``   the cotangent from the write, X,
                                       du: the whole dX, over the first  13 C

(two-byte channels moved a token; XLA's autodiff of the written-out sums made
the backward from several passes, 6.0 ms a connection at Xing4's 4,096 x
3,584 where these read 1.5: PERF.md section 6, PR 41). d_pre has to pass the
maps' small backward before dh is whole, so the read's backward is two
kernels with that between them; Phi's gradient is one einsum beside them.
Elsewhere the written-out sums run (``_read``, ``_write``) under autodiff.
Sums and products are float32 on both paths, each output rounded once.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import attention as _attention
from ..util import tracing


@dataclass(frozen=True)
class HyperConnections:
    """The source's ``hc_*`` keys, and the initial values it does not give
    (the benchmark's configuration file lists them under ``assumed``)."""
    mult: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)
    # The three gating factors' initial value, and the diagonal of b_res's
    # (b_pre, b_post and the rest of b_res start at zero).
    alpha_init: float = 1.0
    res_diagonal_init: float = 2.0


def _sum_over(m: jax.Array, axis: int) -> jax.Array:
    """The sum over a short leading axis as written-out adds of its slices,
    the axis kept: elementwise, where ``sum`` is a reduction."""
    parts = [jax.lax.index_in_dim(m, i, axis) for i in range(m.shape[axis])]
    return functools.reduce(jnp.add, parts)


def _sinkhorn_steps(logits, iters, eps, clamp):
    """The projection, and what its backward reads: exp's output and, for
    each normalisation, its result and its divisor."""
    m = jnp.exp(jnp.clip(logits, *clamp))
    start, steps = m, []
    for _ in range(iters):
        for axis in (1, 0):  # a row's entries lie along axis 1
            s = _sum_over(m, axis) + eps
            m = m / s
            steps.append((m, s, axis))
    return m, start, steps


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def sinkhorn(logits: jax.Array, iters: int, eps: float,
             clamp: Tuple[float, float]) -> jax.Array:
    """``logits`` [n, n, ...] float32 (row, column, then the tokens) to the
    doubly stochastic matrix Sinkhorn-Knopp reaches from exp(clip(logits))
    in ``iters`` iterations of rows, then columns."""
    return _sinkhorn_steps(logits, iters, eps, clamp)[0]


def _sinkhorn_fwd(logits, iters, eps, clamp):
    m, start, steps = _sinkhorn_steps(logits, iters, eps, clamp)
    return m, (logits, start, steps)


def _sinkhorn_bwd(iters, eps, clamp, residuals, g):
    logits, start, steps = residuals
    for m, s, axis in reversed(steps):
        # m = m_before / s, s = sum(m_before) + eps: each entry's cotangent
        # less its line's, weighted by the line's results.
        g = (g - _sum_over(g * m, axis)) / s
    inside = (logits >= clamp[0]) & (logits <= clamp[1])
    return (jnp.where(inside, g * start, 0.0),)


sinkhorn.defvjp(_sinkhorn_fwd, _sinkhorn_bwd)


def expand_streams(x: jax.Array, mult: int) -> jax.Array:
    """[B, T, C] to the ``mult`` streams [n, B, T, C], each a copy."""
    return jnp.broadcast_to(x[None], (mult, *x.shape))


def collapse_streams(x: jax.Array) -> jax.Array:
    """The streams' sum, added up in float32 and rounded once."""
    return _sum_over(x.astype(jnp.float32), 0)[0].astype(x.dtype)


# ------------------------------------------------------- the written-out sums


def _write(x, y, post, res):
    xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
    rows = [
        functools.reduce(jnp.add, [
            res[i, j][..., None] * xf[j] for j in range(x.shape[0])
        ]) + post[i][..., None] * yf
        for i in range(x.shape[0])
    ]
    return jnp.stack(rows).astype(x.dtype)


def _read(x, phi, alpha_pre, b_pre, rms_eps):
    """(u, x~ Phi, x): the read u [B, T, C] = sum_i H_pre[i] X[i] rounded
    once, x~ Phi [2 n + n n, B, T] float32, and the streams handed through,
    so that what ``write_streams`` sends back to them arrives at the read's
    backward. x [n, B, T, C], ``phi`` [n C, 2 n + n n], ``alpha_pre`` a
    float32 scalar and ``b_pre`` [n] float32."""
    n, c = x.shape[0], x.shape[-1]
    xf = x.astype(jnp.float32)
    # x~ Phi = (vec(X) Phi) / rms: the norm is one number a token.
    h = jnp.einsum(
        "nbtc,nck->kbt", x, phi.reshape(n, c, -1).astype(x.dtype),
        preferred_element_type=jnp.float32,
    ) * jax.lax.rsqrt(jnp.mean(xf * xf, axis=(0, -1)) + rms_eps)
    pre = _h_pre(h, alpha_pre, b_pre)
    u = functools.reduce(jnp.add, [
        pre[i][..., None] * xf[i] for i in range(n)
    ]).astype(x.dtype)
    return u, h, x


def _h_pre(h, alpha_pre, b_pre):
    n = b_pre.shape[0]
    return jax.nn.sigmoid(alpha_pre * h[:n] + b_pre[..., None, None])


# ------------------------------------------------------------------ the kernels
#
# A grid step holds ``_TILE`` tokens' whole channels, all streams. A sum over
# the channels of a product of two [tile, C] arrays is the diagonal of their
# [tile, tile] product on the MXU, which takes the streams as they are stored
# (a bfloat16 product is exact in its float32 sum) and leaves the sums with
# the tokens along the lanes. What is elementwise goes through the VPU a slab
# of channels at a time (``_slabs``), in float32, the maps as columns beside
# the tokens' rows (XLA turns the [k, tokens] maps over: two megabytes).

_TILE = 128
_VMEM_LIMIT = 64 * 2**20  # as ops/gmm.py: Mosaic's default lets a kernel use 16


def _by_kernels(x: jax.Array) -> bool:
    """Whether the kernels run for the streams x [n, B, T, C]: where their
    shape tiles, on the TPU or under the interpreter."""
    return (x.shape[-1] % 128 == 0 and math.prod(x.shape[1:-1]) % _TILE == 0
            and (_attention._on_tpu() or _attention._interpret()))


def _slabs(channels: int) -> list:
    """The index [:, at:at + width] of each slab of a [tile, C] block."""
    width = next(w for w in (512, 256, 128) if channels % w == 0)
    return [(slice(None), pl.ds(at, width)) for at in range(0, channels, width)]


def _exact(dtype):
    """The MXU's float32 sum of bfloat16 products is exact; float32 operands
    take its six passes."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _token_sums(stacked, against, sums_ref, row):
    """sums_ref[row(i), t] = sum_c stacked[i tile + t, c] against[t, c], for
    ``stacked`` [n tile, C] and ``against`` [tile, C]."""
    tile = against.shape[0]
    products = jax.lax.dot_general(
        stacked, against, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_exact(stacked.dtype))
    diagonal = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
    for i in range(stacked.shape[0] // tile):
        block = products[i * tile:(i + 1) * tile]
        sums_ref[pl.ds(row(i), 1), :] = jnp.sum(
            jnp.where(diagonal, block, 0.0), axis=0, keepdims=True)


def _hc_pre_fwd_kernel(x_ref, phi_ref, affine_ref, u_ref, h_ref, *, rms_eps, maps):
    """One tile of the read: x [n, tile, C] is fetched once for the streams'
    rsqrt r, x~ Phi = r (vec(X) Phi), H_pre and u = sum_i H_pre[i] x[i].
    ``phi_ref`` [n, C, 128] holds Phi's ``maps`` columns and zeros;
    ``affine_ref`` [2, 128] alpha_pre, then b_pre, over H_pre's n columns.
    ``h_ref`` [tile, 128]: x~ Phi, and r in column ``maps``."""
    n, tile, c = x_ref.shape
    projected = functools.reduce(jnp.add, [
        jnp.dot(x_ref[j], phi_ref[j], preferred_element_type=jnp.float32,
                precision=_exact(x_ref.dtype))
        for j in range(n)
    ])
    squares = jnp.zeros((tile, 128), jnp.float32)
    for slab in _slabs(c):
        for j in range(n):
            xf = x_ref[(j, *slab)].astype(jnp.float32)
            xf = xf * xf
            squares += functools.reduce(jnp.add, [
                xf[:, at:at + 128] for at in range(0, xf.shape[1], 128)
            ])
    r = jax.lax.rsqrt(jnp.sum(squares, axis=1, keepdims=True) / (n * c) + rms_eps)
    h = projected * r
    pre = jax.nn.sigmoid(h * affine_ref[0:1, :] + affine_ref[1:2, :])
    column = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
    h_ref[...] = jnp.where(column == maps, r, h)
    for slab in _slabs(c):
        u_ref[slab] = functools.reduce(jnp.add, [
            pre[:, j:j + 1] * x_ref[(j, *slab)].astype(jnp.float32) for j in range(n)
        ]).astype(u_ref.dtype)


def _hc_post_fwd_kernel(x_ref, y_ref, maps_ref, out_ref):
    """One tile of ``write_streams``: out[i] = sum_j H_res[i, j] x[j] +
    H_post[i] y, with ``maps_ref`` [tile, n + n n]: H_post, then H_res by
    rows."""
    n, _, c = x_ref.shape
    maps = maps_ref[...]
    for slab in _slabs(c):
        x = [x_ref[(j, *slab)].astype(jnp.float32) for j in range(n)]
        y = y_ref[slab].astype(jnp.float32)
        for i in range(n):
            out_ref[(i, *slab)] = (functools.reduce(jnp.add, [
                maps[:, n + i * n + j:n + i * n + j + 1] * x[j] for j in range(n)
            ]) + maps[:, i:i + 1] * y).astype(out_ref.dtype)


def _hc_post_bwd_kernel(g_ref, x_ref, y_ref, maps_ref, dx_ref, dy_ref, sums_ref):
    """One tile of ``write_streams``' backward. g = dX' and x [n, tile, C],
    y [tile, C], ``maps_ref`` [tile, n + n n]: H_post, then H_res by rows.
    dx[j] = sum_i H_res[i, j] g[i], dy = sum_i H_post[i] g[i], and
    ``sums_ref`` [n + n n, tile]: d_post[i] = sum_c g[i] y, then
    d_res[i, j] = sum_c g[i] x[j] by rows."""
    n, tile, c = g_ref.shape
    stacked = g_ref[...].reshape(n * tile, c)
    _token_sums(stacked, y_ref[...], sums_ref, lambda i: i)
    for j in range(n):
        _token_sums(stacked, x_ref[j], sums_ref, lambda i: n + i * n + j)  # noqa: B023
    maps = maps_ref[...]
    for slab in _slabs(c):
        g = [g_ref[(i, *slab)].astype(jnp.float32) for i in range(n)]
        dy_ref[slab] = functools.reduce(jnp.add, [
            maps[:, i:i + 1] * g[i] for i in range(n)
        ]).astype(dy_ref.dtype)
        for j in range(n):
            dx_ref[(j, *slab)] = functools.reduce(jnp.add, [
                maps[:, n + i * n + j:n + i * n + j + 1] * g[i] for i in range(n)
            ]).astype(dx_ref.dtype)


def _hc_pre_sums_kernel(du_ref, x_ref, sums_ref):
    """One tile of d_pre[i] = sum_c du x[i]: ``sums_ref`` [n, tile]."""
    n, tile, c = x_ref.shape
    _token_sums(x_ref[...].reshape(n * tile, c), du_ref[...], sums_ref, lambda i: i)


def _hc_pre_bwd_kernel(dx_ref, x_ref, du_ref, maps_ref, dh_ref, phi_ref, out_ref):
    """One tile of the read's backward, onto the cotangent ``dx_ref`` that
    came down from ``write_streams``: out[j] = dx[j] + H_pre[j] du + a x[j] +
    (r dh) Phi[j]^T. ``maps_ref`` [tile, n + 1] holds H_pre, then a;
    ``dh_ref`` [tile, K] and ``phi_ref`` [n, K, C] are ``_pieces``'."""
    n, _, c = x_ref.shape
    maps, dh = maps_ref[...], dh_ref[...]
    for slab in _slabs(c):
        du = du_ref[slab].astype(jnp.float32)
        for j in range(n):
            through_phi = jnp.dot(
                dh, phi_ref[(j, *slab)], preferred_element_type=jnp.float32,
                precision=_exact(dh.dtype))
            out_ref[(j, *slab)] = (
                dx_ref[(j, *slab)].astype(jnp.float32) + maps[:, j:j + 1] * du
                + maps[:, n:n + 1] * x_ref[(j, *slab)].astype(jnp.float32)
                + through_phi
            ).astype(out_ref.dtype)


def _flat(a: jax.Array) -> jax.Array:
    """[.., B, T, C] to [.., tokens, C]."""
    return a.reshape(*a.shape[:-3], -1, a.shape[-1])


def _columns(*maps) -> jax.Array:
    """Maps [.., B, T] float32, side by side as columns [tokens, k]."""
    tokens = math.prod(maps[0].shape[-2:])
    return jnp.concatenate([m.reshape(-1, tokens) for m in maps]).T


def _tiles(kernel, x, in_specs, out_specs, out_shape, **kwargs):
    """``kernel`` over the token tiles of the flat streams x [n, tokens, C]."""
    return pl.pallas_call(
        kernel, grid=(x.shape[1] // _TILE,), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_attention._interpret(), **kwargs)


def _streams_spec(x):
    return pl.BlockSpec((x.shape[0], _TILE, x.shape[-1]), lambda t: (0, t, 0))


def _rows_spec(width):
    """A tile's rows of a [tokens, width] array."""
    return pl.BlockSpec((_TILE, width), lambda t: (t, 0))


def _lanes_spec(rows):
    """A tile's lanes of a [rows, tokens] array."""
    return pl.BlockSpec((rows, _TILE), lambda t: (0, t))


# One jitted entry a kernel, so that the step's text holds a body once
# whatever the number of hyper-connections that call it (a forward kernel's
# twice under remat, whose partial evaluation copies the entry's jaxpr: for
# the replays where the policy keeps nothing, and for a layer's second
# connection, whose streams are a kept value, where it keeps what the kernels
# wrote), and not a Mosaic lowering a call site.


@functools.partial(jax.jit, static_argnums=4)
def _pre_fwd(x, phi, alpha_pre, b_pre, rms_eps):
    """The read u and, one array so that no caller's unused part makes a
    second body of this, x~ Phi [2 n + n n, B, T] with r [B, T] after it,
    from one pass over the streams; ``phi`` [n C, 2 n + n n]."""
    n, c, maps = x.shape[0], x.shape[-1], phi.shape[-1]
    flat = _flat(x)
    weights = jnp.pad(phi.reshape(n, c, maps).astype(x.dtype),
                      ((0, 0), (0, 0), (0, 128 - maps)))
    affine = jnp.zeros((2, 128), jnp.float32)
    affine = affine.at[0, :n].set(alpha_pre).at[1, :n].set(b_pre)
    u, h = _tiles(
        functools.partial(_hc_pre_fwd_kernel, rms_eps=rms_eps, maps=maps), flat,
        [_streams_spec(flat), pl.BlockSpec(weights.shape, lambda t: (0, 0, 0)),
         pl.BlockSpec(affine.shape, lambda t: (0, 0))],
        [_rows_spec(c), _rows_spec(128)],
        [jax.ShapeDtypeStruct(flat.shape[1:], x.dtype),
         jax.ShapeDtypeStruct((flat.shape[1], 128), jnp.float32)],
    )(flat, weights, affine)
    return u.reshape(x.shape[1:]), h.T[:maps + 1].reshape(maps + 1, *x.shape[1:-1])


@jax.jit
def _post_fwd(x, y, post, res):
    """``write_streams`` from one pass over the streams and y."""
    n, c = x.shape[0], x.shape[-1]
    flat = _flat(x)
    return _tiles(
        _hc_post_fwd_kernel, flat,
        [_streams_spec(flat), _rows_spec(c), _rows_spec(n + n * n)],
        _streams_spec(flat), jax.ShapeDtypeStruct(flat.shape, x.dtype),
    )(flat, _flat(y), _columns(post, res)).reshape(x.shape)


@jax.jit
def _post_bwd(g, x, y, post, res):
    """``write_streams``' cotangents (dx, dy, d_post, d_res) from dX' = g:
    dX', X and y are read once."""
    n, c = x.shape[0], x.shape[-1]
    flat = _flat(x)
    dx, dy, sums = _tiles(
        _hc_post_bwd_kernel, flat,
        [_streams_spec(flat), _streams_spec(flat), _rows_spec(c), _rows_spec(n + n * n)],
        [_streams_spec(flat), _rows_spec(c), _lanes_spec(n + n * n)],
        [jax.ShapeDtypeStruct(flat.shape, x.dtype),
         jax.ShapeDtypeStruct(flat.shape[1:], y.dtype),
         jax.ShapeDtypeStruct((n + n * n, flat.shape[1]), jnp.float32)],
    )(_flat(g), flat, _flat(y), _columns(post, res))
    return (dx.reshape(x.shape), dy.reshape(y.shape),
            sums[:n].reshape(post.shape), sums[n:].reshape(res.shape))


@jax.jit
def _pre_sums(du, x):
    """d_pre [n, B, T] float32: the cotangent of H_pre."""
    n, c = x.shape[0], x.shape[-1]
    flat = _flat(x)
    return _tiles(
        _hc_pre_sums_kernel, flat, [_rows_spec(c), _streams_spec(flat)],
        _lanes_spec(n), jax.ShapeDtypeStruct((n, flat.shape[1]), jnp.float32),
    )(_flat(du), flat).reshape(x.shape[:-1])


def _pieces(v: jax.Array, dtype) -> jax.Array:
    """float32 v [k, ..] as rows of ``dtype`` whose sum is v: itself, or for
    bfloat16 its leading 8 bits and the next 8 beneath them, [2 k, ..], so
    that a bfloat16 matmul of them carries 16 bits of v into its float32
    sum (the streams' gradient is rounded to 8)."""
    if dtype == jnp.float32:
        return v
    high = v.astype(dtype)
    return jnp.concatenate([high, (v - high.astype(jnp.float32)).astype(dtype)])


@jax.jit
def _pre_bwd(dx, x, du, pre, a, dh, phi):
    """The streams' whole cotangent, written over ``dx`` [n, B, T, C] (what
    came down from ``write_streams``); ``dh`` is ``_pieces`` of r dh and
    ``phi`` [n, C, 2 n + n n] in the streams' dtype."""
    n, c = x.shape[0], x.shape[-1]
    flat = _flat(x)
    k = dh.shape[0]
    padded = -k % 128  # the MXU's contraction, whole
    turned = jnp.tile(jnp.swapaxes(phi, 1, 2), (1, k // phi.shape[-1], 1))
    return _tiles(
        _hc_pre_bwd_kernel, flat,
        [_streams_spec(flat), _streams_spec(flat), _rows_spec(c), _rows_spec(n + 1),
         _rows_spec(k + padded), pl.BlockSpec((n, k + padded, c), lambda t: (0, 0, 0))],
        _streams_spec(flat), jax.ShapeDtypeStruct(flat.shape, x.dtype),
        input_output_aliases={0: 0},
    )(_flat(dx), flat, _flat(du), _columns(pre, a[None]),
      jnp.pad(_columns(dh), ((0, 0), (0, padded))),
      jnp.pad(turned, ((0, 0), (0, padded), (0, 0)))).reshape(x.shape)


_write_by_kernels = jax.custom_vjp(_post_fwd)


def _write_fwd(x, y, post, res):
    # Named for the remat policy, like the read's outputs below (models/llama.py
    # REPLAY_KEEPS): a layer's first write is its second connection's
    # streams, and a replay that holds it runs no write.
    out = checkpoint_name(_post_fwd(x, y, post, res), "hc_write")
    return out, (x, y, post, res)


def _write_bwd(residuals, g):
    return _post_bwd(g, *residuals)


_write_by_kernels.defvjp(_write_fwd, _write_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _read_by_kernels(x, phi, alpha_pre, b_pre, rms_eps):
    return _read_fwd(x, phi, alpha_pre, b_pre, rms_eps)[0]


def _read_fwd(x, phi, alpha_pre, b_pre, rms_eps):
    u, h = _pre_fwd(x, phi, alpha_pre, b_pre, rms_eps)
    u, h = checkpoint_name(u, "hc_read"), checkpoint_name(h, "hc_maps")
    h, r = h[:-1], h[-1]
    return (u, h, x), (x, phi, alpha_pre, h, _h_pre(h, alpha_pre, b_pre), r)


def _read_bwd(rms_eps, residuals, cotangents):
    """With z = alpha_pre h[:n] + b_pre, r the streams' rsqrt and N = n C:
    dh[:n] += alpha_pre dz, and through h = r (vec(X) Phi) the streams get
    (r dh) Phi^T + a X with a = -(r^2 / N) sum_k dh[k] h[k], the norm's part."""
    x, phi, alpha_pre, h, pre, r = residuals
    du, dh, dx = cotangents
    n, c = x.shape[0], x.shape[-1]
    dz = _pre_sums(du, x) * pre * (1.0 - pre)
    dh = jnp.concatenate([dh[:n] + alpha_pre * dz, dh[n:]])
    a = -(r * r / (n * c)) * _sum_over(dh * h, 0)[0]
    through = _pieces(dh * r, x.dtype)
    dx = _pre_bwd(dx, x, du, pre, a, through, phi.reshape(n, c, -1).astype(x.dtype))
    # Phi's gradient with the channels along the lanes, then turned over.
    dphi = jnp.einsum(
        "nbtc,kbt->nkc", x, through, preferred_element_type=jnp.float32,
        precision=_exact(x.dtype))
    dphi = _sum_over(dphi.reshape(n, -1, h.shape[0], c), 1)[:, 0]
    dphi = jnp.swapaxes(dphi, 1, 2).reshape(phi.shape).astype(phi.dtype)
    return dx, dphi, jnp.sum(dz * h[:n]), jnp.sum(dz, axis=(1, 2))


_read_by_kernels.defvjp(_read_fwd, _read_bwd)


def write_streams(x, y, post, res):
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y: x [n, B, T, C], the
    sublayer's y [B, T, C], ``post`` [n, B, T] and ``res`` [n, n, B, T]."""
    with tracing.scope(tracing.HC), tracing.scope(tracing.HC_POST):
        return (_write_by_kernels if _by_kernels(x) else _write)(x, y, post, res)


class HyperConnection(nn.Module):
    """One sublayer's three maps, and the read through the first of them:
    ``u, (post, res) = self(x)``; the caller runs its sublayer on ``u`` and
    hands the result to ``write_streams`` with the other two. With
    ``streams``, ``u, x, (post, res)``: the streams as the read hands them
    on, for ``write_streams``, so that their two cotangents meet inside the
    read's backward and not in an ``add`` of XLA's."""
    hc: HyperConnections
    rms_eps: float
    phi_init: Callable
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, streams: bool = False):
        hc, n, C = self.hc, x.shape[0], x.shape[-1]
        assert n == hc.mult, (n, hc.mult)
        phi = self.param("phi", self.phi_init, (n * C, 2 * n + n * n), self.param_dtype)
        alpha = self.param(
            "alpha", nn.initializers.constant(hc.alpha_init), (3,), self.param_dtype)
        b_pre = self.param("b_pre", nn.initializers.zeros, (n,), self.param_dtype)
        b_post = self.param("b_post", nn.initializers.zeros, (n,), self.param_dtype)
        b_res = self.param(
            "b_res", lambda key, shape, dtype: hc.res_diagonal_init * jnp.eye(n, dtype=dtype),
            (n, n), self.param_dtype)
        f32 = jnp.float32
        with tracing.scope(tracing.HC):
            with tracing.scope(tracing.HC_PRE):
                alpha = alpha.astype(f32)
                read = _read_by_kernels if _by_kernels(x) else _read
                u, h, x = read(x, phi, alpha[0], b_pre.astype(f32), self.rms_eps)
                lane = lambda b: b.astype(f32)[..., None, None]  # noqa: E731
                post = 2.0 * jax.nn.sigmoid(alpha[1] * h[n:2 * n] + lane(b_post))
                logits = alpha[2] * h[2 * n:].reshape(n, n, *h.shape[1:]) + lane(b_res)
            with tracing.scope(tracing.HC_SINKHORN):
                res = sinkhorn(logits, hc.sinkhorn_iters, hc.eps, hc.clamp)
        return (u, x, (post, res)) if streams else (u, (post, res))
