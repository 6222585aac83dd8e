"""Pieces the plain references share: RMSNorm, rotary embedding, causal
grouped-query attention and the gated MLP, as the published Mistral and
Mixtral modelling code defines them. Float32 throughout; callers hold
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
runs in lower precision without it)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
# Query rows per block: [heads, Q_BLOCK, seq] float32 scores stay near
# 0.5 GB at 32 heads and 16,384 keys instead of 34 GB for all rows.
Q_BLOCK = 256


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rotary(x, theta):
    """x [T, heads, d]: the rotate-half form of the published code."""
    t, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_gqa(q, k, v):
    """q [T, heads, d]; k, v [T, kv_heads, d] -> [T, heads, d]. Each group
    of heads // kv_heads query heads shares one key/value head. Query rows
    are taken Q_BLOCK at a time against every key."""
    t, heads, d = q.shape
    kv_heads = k.shape[1]
    block = min(Q_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence {t} is not a multiple of {block}")
    qg = q.reshape(t // block, block, kv_heads, heads // kv_heads, d)
    starts = jnp.arange(t // block) * block
    key_pos = jnp.arange(t)

    def one_block(args):
        qb, start = args  # [block, kv_heads, group, d]
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / jnp.sqrt(F32(d))
        visible = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one_block, (qg, starts))
    return out.reshape(t, heads, d)


def attention(p, x, cfg):
    """One attention block on x [T, hidden]; p holds q_proj [hidden, heads,
    d], k_proj, v_proj [hidden, kv_heads, d], o_proj [heads, d, hidden]."""
    w = {name: p[name]["kernel"].astype(F32)
         for name in ("q_proj", "k_proj", "v_proj", "o_proj")}
    q = jnp.einsum("th,hnd->tnd", x, w["q_proj"])
    k = jnp.einsum("th,hnd->tnd", x, w["k_proj"])
    v = jnp.einsum("th,hnd->tnd", x, w["v_proj"])
    q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    return jnp.einsum("tnd,ndh->th", causal_gqa(q, k, v), w["o_proj"])


def gated_mlp(x, w_gate, w_up, w_down):
    """SwiGLU: down(silu(gate(x)) * up(x)); weights [in, out]."""
    gate, up = x @ w_gate.astype(F32), x @ w_up.astype(F32)
    return (jax.nn.silu(gate) * up) @ w_down.astype(F32)
