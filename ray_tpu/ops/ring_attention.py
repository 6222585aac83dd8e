"""Ring attention: exact attention over a sequence-sharded mesh axis.

Net-new vs. the reference (SURVEY.md §5 "Long-context / sequence
parallelism: absent in the reference ... must be first-class"). Each
device holds a [B, H, T/n, D] shard of q/k/v. K/V shards rotate around
the mesh axis with `lax.ppermute` (ICI neighbor exchange) while each
device computes one block of attention per step and folds it into a
running (o, lse) pair — the flash-attention merge — so the full
sequence is never gathered and per-step memory is one block.

On TPU each block runs the pallas flash kernels (fwd AND bwd — see
ops/attention.py); elsewhere a blockwise-XLA fallback computes the same
(o, lse) contract. The whole ring carries a custom VJP: the backward
pass is a second ring pass in which dk/dv accumulators rotate WITH
their k/v shards and arrive home after a full cycle — communication
stays one neighbor hop per step in both directions, riding ICI.

Causality uses the global block index: the diagonal block applies the
in-block causal mask; blocks from higher indices are dropped via an
-inf lse (forward) and zeroed gradients (backward).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from .attention import _flash_bwd_pallas, _flash_fwd_pallas, _on_tpu

NEG_INF = -1e30


def _use_pallas(t: int, d: int) -> bool:
    return _on_tpu() and t >= 128 and d % 8 == 0


def _block_fwd(q, k, v, causal: bool, scale: float):
    """One attention block on [bh, t, d] operands -> (o, lse)."""
    if _use_pallas(q.shape[1], q.shape[2]):
        return _flash_fwd_pallas(
            q, k, v, causal=causal, sm_scale=scale, block_q=512, block_k=512
        )
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        t = s.shape[-1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jax.lax.dot_general(
        (p / l_safe), v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return o, (m + jnp.log(l_safe))[..., 0]


def _block_bwd(q, k, v, o, lse, do, causal: bool, scale: float):
    """Gradients of one block given the GLOBAL (o, lse) — the blockwise
    decomposition of the flash backward: p = exp(s - lse_global)."""
    if _use_pallas(q.shape[1], q.shape[2]):
        return _flash_bwd_pallas(
            q, k, v, o, lse, do, causal=causal, sm_scale=scale,
            block_q=512, block_k=512,
        )
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        t = s.shape[-1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None], s, NEG_INF)
    p = jnp.exp(s - lse[..., :, None])
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


def _merge(o_a, lse_a, o_b, lse_b):
    """Fold two normalized partial results: weights exp(lse_i - lse).
    The running accumulator stays f32 across the whole ring (one final
    downcast) — per-step rounding would cost ~n quantization steps."""
    m = jnp.maximum(lse_a, lse_b)
    lse = m + jnp.log(jnp.exp(lse_a - m) + jnp.exp(lse_b - m))
    w_a = jnp.exp(lse_a - lse)[..., None]
    w_b = jnp.exp(lse_b - lse)[..., None]
    return o_a.astype(jnp.float32) * w_a + o_b.astype(jnp.float32) * w_b, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring(q, k, v, axis_name, causal, scale):
    o, _ = _ring_fwd(q, k, v, axis_name, causal, scale)
    return o


def _ring_fwd(q, k, v, axis_name, causal, scale):
    b, h, t, d = q.shape
    bh = b * h
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qf = q.reshape(bh, t, d)

    # Diagonal block first (the only one with an in-block causal mask).
    o, lse = _block_fwd(
        qf, k.reshape(bh, t, d), v.reshape(bh, t, d), causal, scale
    )
    o = o.astype(jnp.float32)  # f32 accumulator across the ring

    def step(carry, s):
        k_c, v_c, o_acc, lse_acc = carry
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        kv_idx = (my - s) % n
        o_j, lse_j = _block_fwd(
            qf, k_c.reshape(bh, t, d), v_c.reshape(bh, t, d), False, scale
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


def _ring_fwd_rule(q, k, v, axis_name, causal, scale):
    return _ring_fwd(q, k, v, axis_name, causal, scale)


def _ring_bwd_rule(axis_name, causal, scale, res, do):
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
        causal, scale,
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
            False, scale,
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


_ring.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = True,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Per-shard body; call inside shard_map with q/k/v sequence-sharded
    along ``axis_name``. Shapes [B, H, T_local, D] (kv heads already
    broadcast to H)."""
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / d**0.5
    return _ring(q, k, v, axis_name, causal, scale)


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    batch_axes=("data", "fsdp"),
    head_axis: str = "tensor",
    causal: bool = True,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """shard_map wrapper: global [B, H, T, D] arrays, sequence sharded over
    ``seq_axis``, batch over ``batch_axes``, heads over ``head_axis``."""
    hkv = k.shape[1]
    if q.shape[1] != hkv:
        k = jnp.repeat(k, q.shape[1] // hkv, axis=1)
        v = jnp.repeat(v, q.shape[1] // hkv, axis=1)
    spec = P(batch_axes, head_axis, seq_axis, None)
    fn = shard_map(
        lambda q_, k_, v_: ring_attention(
            q_, k_, v_, axis_name=seq_axis, causal=causal, sm_scale=sm_scale
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
