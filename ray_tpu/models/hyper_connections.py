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
are float32 throughout; the sums over the streams are written out, n being
4, so that each of ``read``, ``write`` and Sinkhorn's whole loop is
elementwise for XLA to fuse. Sinkhorn's backward is its own for the same
reason: autodiff would transpose each broadcast into a reduction, forty
small kernels a call, where the written-out sums stay in one.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

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


def write_streams(x, y, post, res):
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y: x [n, B, T, C], the
    sublayer's y [B, T, C], ``post`` [n, B, T] and ``res`` [n, n, B, T]."""
    with tracing.scope(tracing.HC), tracing.scope(tracing.HC_POST):
        xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
        rows = [
            functools.reduce(jnp.add, [
                res[i, j][..., None] * xf[j] for j in range(x.shape[0])
            ]) + post[i][..., None] * yf
            for i in range(x.shape[0])
        ]
        return jnp.stack(rows).astype(x.dtype)


class HyperConnection(nn.Module):
    """One sublayer's three maps, and the read through the first of them:
    ``u, (post, res) = self(x)``; the caller runs its sublayer on ``u`` and
    hands the result to ``write_streams`` with the other two."""
    hc: HyperConnections
    rms_eps: float
    phi_init: Callable
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
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
                xf = x.astype(f32)
                # x~ Phi = (vec(X) Phi) / rms: the norm is one number a token.
                h = jnp.einsum(
                    "nbtc,nck->kbt", x, phi.reshape(n, C, -1).astype(x.dtype),
                    preferred_element_type=f32,
                ) * jax.lax.rsqrt(jnp.mean(xf * xf, axis=(0, -1)) + self.rms_eps)
                alpha = alpha.astype(f32)
                lane = lambda b: b.astype(f32)[..., None, None]  # noqa: E731
                pre = jax.nn.sigmoid(alpha[0] * h[:n] + lane(b_pre))
                post = 2.0 * jax.nn.sigmoid(alpha[1] * h[n:2 * n] + lane(b_post))
                logits = alpha[2] * h[2 * n:].reshape(n, n, *h.shape[1:]) + lane(b_res)
                u = functools.reduce(jnp.add, [
                    pre[i][..., None] * xf[i] for i in range(n)
                ]).astype(x.dtype)
            with tracing.scope(tracing.HC_SINKHORN):
                res = sinkhorn(logits, hc.sinkhorn_iters, hc.eps, hc.clamp)
        return u, (post, res)
