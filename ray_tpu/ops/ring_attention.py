"""Ring attention: exact attention over a sequence split across a mesh axis.

What ``flash_attention`` maps over the ambient mesh (``jax.shard_map``) where
that splits the sequence; the blocks are ``ops/attention.py``'s.

Each device holds a [B, H, T/n, D] shard of q/k/v. K/V shards rotate around
the mesh axis with `lax.ppermute` (ICI neighbor exchange) while each
device computes one block of attention per step and folds it into a
running (o, lse) pair — the flash-attention merge — so the full
sequence is never gathered and per-step memory is one block.

The whole ring carries a custom VJP: the backward pass is a second ring
pass in which dk/dv accumulators rotate WITH their k/v shards and arrive
home after a full cycle — communication stays one neighbor hop per step
in both directions, riding ICI.

Causality uses the global block index: the diagonal block applies the
in-block causal mask; blocks from higher indices are dropped via an
-inf lse (forward) and zeroed gradients (backward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from .attention import NEG_INF, _block_bwd, _block_fwd


def _merge(o_a, lse_a, o_b, lse_b):
    """Fold two normalized partial results: weights exp(lse_i - lse).
    The running accumulator stays f32 across the whole ring (one final
    downcast) — per-step rounding would cost ~n quantization steps."""
    m = jnp.maximum(lse_a, lse_b)
    lse = m + jnp.log(jnp.exp(lse_a - m) + jnp.exp(lse_b - m))
    w_a = jnp.exp(lse_a - lse)[..., None]
    w_b = jnp.exp(lse_b - lse)[..., None]
    return o_a.astype(jnp.float32) * w_a + o_b.astype(jnp.float32) * w_b, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_attention(q, k, v, axis_name, causal, scale, block):
    """One device's part, inside a shard_map that splits the sequence of
    q/k/v [B, H, T_local, D] (kv heads already H) along ``axis_name``;
    ``block`` is the kernels' block_q and block_k."""
    return _ring_fwd(q, k, v, axis_name, causal, scale, block)[0]


def _ring_fwd(q, k, v, axis_name, causal, scale, block):
    b, h, t, d = q.shape
    bh = b * h
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qf = q.reshape(bh, t, d)

    # Diagonal block first (the only one with an in-block causal mask).
    o, lse = _block_fwd(
        qf, k.reshape(bh, t, d), v.reshape(bh, t, d), causal, scale,
        block, block,
    )
    o = o.astype(jnp.float32)  # f32 accumulator across the ring

    def step(carry, s):
        k_c, v_c, o_acc, lse_acc = carry
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        kv_idx = (my - s) % n
        o_j, lse_j = _block_fwd(
            qf, k_c.reshape(bh, t, d), v_c.reshape(bh, t, d), False, scale,
            block, block,
        )
        if causal:
            # Future blocks contribute nothing.
            lse_j = jnp.where(kv_idx > my, NEG_INF, lse_j)
        o_acc, lse_acc = _merge(o_acc, lse_acc, o_j, lse_j)
        return (k_c, v_c, o_acc, lse_acc), None

    if n > 1:
        (_, _, o, lse), _ = jax.lax.scan(
            step, (k, v, o, lse), jnp.arange(1, n)
        )
    o = o.astype(q.dtype).reshape(b, h, t, d)
    return o, (q, k, v, o, lse)


def _ring_bwd(axis_name, causal, scale, block, res, do):
    q, k, v, o, lse = res
    b, h, t, d = q.shape
    bh = b * h
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qf = q.reshape(bh, t, d)
    of = o.reshape(bh, t, d)
    dof = do.reshape(bh, t, d)

    dq, dk_diag, dv_diag = _block_bwd(
        qf, k.reshape(bh, t, d), v.reshape(bh, t, d), of, lse, dof,
        causal, scale, block, block,
    )

    def step(carry, s):
        k_c, v_c, dk_c, dv_c, dq_acc = carry
        # dk/dv accumulators rotate WITH their shards: after the full
        # cycle each arrives back at its owner.
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        dk_c = jax.lax.ppermute(dk_c, axis_name, perm)
        dv_c = jax.lax.ppermute(dv_c, axis_name, perm)
        kv_idx = (my - s) % n
        dq_j, dk_j, dv_j = _block_bwd(
            qf, k_c.reshape(bh, t, d), v_c.reshape(bh, t, d), of, lse, dof,
            False, scale, block, block,
        )
        if causal:
            skip = kv_idx > my
            dq_j = jnp.where(skip, 0, dq_j)
            dk_j = jnp.where(skip, 0, dk_j)
            dv_j = jnp.where(skip, 0, dv_j)
        dq_acc = dq_acc + dq_j.astype(jnp.float32)
        dk_c = dk_c + dk_j.reshape(b, h, t, d).astype(jnp.float32)
        dv_c = dv_c + dv_j.reshape(b, h, t, d).astype(jnp.float32)
        return (k_c, v_c, dk_c, dv_c, dq_acc), None

    dk_rot = jnp.zeros((b, h, t, d), jnp.float32)
    dv_rot = jnp.zeros((b, h, t, d), jnp.float32)
    dq_acc = dq.astype(jnp.float32)
    if n > 1:
        (k_c, v_c, dk_rot, dv_rot, dq_acc), _ = jax.lax.scan(
            step, (k, v, dk_rot, dv_rot, dq_acc), jnp.arange(1, n)
        )
        # One more hop completes the cycle and brings each accumulator
        # home to its shard's owner.
        dk_rot = jax.lax.ppermute(dk_rot, axis_name, perm)
        dv_rot = jax.lax.ppermute(dv_rot, axis_name, perm)
    dk = dk_diag.reshape(b, h, t, d).astype(jnp.float32) + dk_rot
    dv = dv_diag.reshape(b, h, t, d).astype(jnp.float32) + dv_rot
    return (
        dq_acc.reshape(b, h, t, d).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


ring_attention.defvjp(_ring_fwd, _ring_bwd)
