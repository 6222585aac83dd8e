"""Plain reference of Kimi-Linear-48B-A3B's decoder (moonshotai,
``modeling_kimi.py`` with ``fla.layers.kda``), given one expert-parallel
rank's share of it: the routed experts ``expert_rank * num_experts`` and the
``num_experts - 1`` that follow, of the ``num_experts_published`` the router
scores, and the first ``vocab_size`` token ids.

Pre-norm layers, RMSNorm, no rotary embedding anywhere. A layer's mixer is
KDA or MLA as the source's ``linear_attn_config`` lists it (layers counted
from 1), its FFN a dense SwiGLU in the first ``first_k_dense_replace`` layers
and the expert layer after them.

KDA, per head with a float32 state S in R^{dk x dv}, token by token in a
``lax.scan`` (no chunks, no kernel):

    q = l2norm(silu(conv4(W_q x))) dk^-1/2,  k = l2norm(silu(conv4(W_k x))),
    v = silu(conv4(W_v x)),  g = -exp(A_log) softplus(W_f2 W_f1 x + dt_bias),
    beta = sigmoid(W_b x)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    out = W_o (RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x + b_g))

MLA: q heads of nope | pe from W_q; [c | k_pe] = W_kva x; [k_nope | v] =
W_kvb RMSNorm(c) per head; k = [k_nope | k_pe], k_pe shared by the heads;
causal softmax attention at scale (nope + pe)^-1/2 with K and V
materialised, query rows in blocks.

Expert layer: s = sigmoid(W_r x) over all the router's experts; the top k of
s + bias are chosen; the gates are s at the chosen, renormalised to sum to
one and times ``routed_scaling_factor``; every held expert sees every token
and a zero gate removes it; what the experts held elsewhere would add is
left out, here as in the program; the shared expert is added.

``forward`` and ``loss`` take the system's parameter tree (flax names) and
the configuration file's own keys. There is no auxiliary loss."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..lib.flops_kimi import layer_kinds
from .common import F32, Q_BLOCK, gated_mlp, rms_norm

# Per-position error ||system - reference|| / ||reference|| over the held
# vocabulary, as the other references have it, on the last 256 positions of a
# 16,384-token sequence. The readings are
# benchmarks/tools/reference_readings_kimi.py's, on the chip at the published
# widths (PERF.md, Findings, PR 31): fourteen seeds.
#
# The system's positions lie in two heaps. Most are bfloat16's noise through
# five layers of this architecture, median 0.0133 to 0.0155 (a gate scale of
# 2.446 and L2-normalised keys pass it on; the kernel's own roundings are a
# fifth of it); the rest, up to 0.07 to 0.15, are flips of the 8th of 256
# sigmoid scores in some layer whose entering or leaving expert is one of
# the 16 held here. Within 0.02 lay 81.6% to 98.8% of positions (within 0.03
# 91.4% to 98.8%); the share asked for is under the worst reading by as much
# as it is over the best reading of a program that has to fail.
#
# What it refuses, same seeds, positions within 0.02: the reference in the
# nearest precision below the configuration's bfloat16 (weights and every
# norm's output rounded to float8 e4m3): none, median 0.289 to 0.305. The
# shared expert left out: none, median 0.48 to 0.50. The gates not times
# 2.446: 0 to 0.4%. A dispatch that drops the pairs past an even share of
# rows: 0 to 56.3%.
#
# What it does not refuse: the reference with KDA's state rounded to
# bfloat16 after every token reads median 0.0148 to 0.0192 and 63.3% to 94.5%
# within 0.02, inside the system's own range on ten of twelve seeds: a
# bfloat16 state is no further from float32 than the rest of a bfloat16
# model is at these sizes, so no limit on these logits tells them apart
# (PERF.md, Open questions). The state is float32 in the program
# (tests/test_kda_op.py holds the kernels to 2e-4 of the recurrence).
TOLERANCE = {"per_position_rel_err": 0.02, "min_share_within": 0.70}

L2_EPS = 1e-6


def _w(p):
    return p["kernel"].astype(F32)


def conv_silu(x, w):
    """Causal depthwise convolution, the last tap on the current token,
    then SiLU. x [T, D]; w [taps, D]."""
    taps, t = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    y = sum(padded[i:i + t] * w[i].astype(F32) for i in range(taps))
    return jax.nn.silu(y)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The recurrence, one token at a time. q, k, g [T, H, dk]; v [T, H,
    dv]; beta [T, H] -> [T, H, dv]."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):  # S [H, dk, dv]
        q, k, v, g, beta = x
        S = jnp.exp(g)[..., None] * S
        read = jnp.einsum("hkv,hk->hv", S, k)
        S = S + jnp.einsum("hk,hv->hkv", beta[:, None] * k, v - read)
        return S, jnp.einsum("hkv,hk->hv", S, q)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), F32), (q, k, v, g, beta))
    return o


def kda(p, x, cfg):
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    t = x.shape[0]

    def branch(name):
        y = conv_silu(x @ _w(p[f"{name}_proj"]), p[f"{name}_conv"])
        return y.reshape(t, heads, d)

    q = l2norm(branch("q")) * d ** -0.5
    k, v = l2norm(branch("k")), branch("v")
    f = (x @ _w(p["f_a_proj"])) @ _w(p["f_b_proj"])
    soft = jax.nn.softplus(f + p["dt_bias"].astype(F32)).reshape(t, heads, d)
    g = -jnp.exp(p["A_log"].astype(F32))[None, :, None] * soft
    beta = jax.nn.sigmoid(x @ _w(p["b_proj"]))
    o = delta_rule(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"]["scale"], cfg["rms_norm_eps"])
    gate = (x @ _w(p["g_a_proj"])) @ _w(p["g_b_proj"]) + p["g_b_proj"]["bias"].astype(F32)
    o = o * jax.nn.sigmoid(gate).reshape(t, heads, d)
    return o.reshape(t, heads * d) @ _w(p["o_proj"])


def causal_attention(q, k, v, scale):
    """q, k [T, H, d]; v [T, H, dv] -> [T, H, dv], query rows a block at a
    time against every key."""
    t = q.shape[0]
    block = min(Q_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence {t} is not a multiple of {block}")
    key_pos = jnp.arange(t)

    def one_block(args):
        qb, start = args  # [block, H, d]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        visible = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(visible[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    starts = jnp.arange(t // block) * block
    out = jax.lax.map(one_block, (q.reshape(t // block, block, *q.shape[1:]), starts))
    return out.reshape(t, *out.shape[2:])


def mla(p, x, cfg):
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    pe = cfg["qk_rope_head_dim"]
    q = jnp.einsum("th,hnd->tnd", x, _w(p["q_proj"]))  # [T, H, nope + pe]
    latent = x @ _w(p["kv_a_proj"])
    c = rms_norm(latent[:, :rank], p["kv_a_norm"]["scale"], cfg["rms_norm_eps"])
    kv = jnp.einsum("tr,rnd->tnd", c, _w(p["kv_b_proj"]))  # [T, H, nope + dv]
    k_pe = jnp.broadcast_to(latent[:, None, rank:], (*kv.shape[:2], pe))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    o = causal_attention(q, k, kv[..., nope:], (nope + pe) ** -0.5)
    return jnp.einsum("tnd,ndh->th", o, _w(p["o_proj"]))


def held_experts(cfg) -> tuple:
    """[first, past the last) of the router's experts that this rank holds."""
    first = cfg.get("expert_rank", 0) * cfg["num_experts"]
    return first, first + cfg["num_experts"]


def router_gates(p, x, cfg):
    """[T, E] gates over all the router's experts: zero where an expert was
    not chosen."""
    n, k = cfg["num_experts_published"], cfg["num_experts_per_token"]
    s = jax.nn.sigmoid(x @ _w(p["router"]))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(F32), k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["moe_renormalize"]:
        top = top / top.sum(axis=-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(idx, n, dtype=F32) * top[..., None], axis=1)


def routed(p, x, cfg, gates, held):
    """The part of the expert layer's result that the experts ``held`` give:
    p's stacked weights are those experts'."""
    def add_expert(out, expert):
        w_gate, w_up, w_down, gate = expert
        return out + gate[:, None] * gated_mlp(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (p["w_gate"], p["w_up"], p["w_down"], gates[:, held[0]:held[1]].T),
    )
    return out


def shared_expert(p, x):
    s = p["shared"]
    return gated_mlp(x, s["gate_proj"]["kernel"], s["up_proj"]["kernel"],
                     s["down_proj"]["kernel"])


def moe(p, x, cfg):
    out = routed(p, x, cfg, router_gates(p, x, cfg), held_experts(cfg))
    return out + shared_expert(p, x) if cfg["num_shared_experts"] else out


def hidden_states(params, ids, cfg: dict):
    """ids [T] -> the final norm's input [T, hidden]."""
    p = params["params"]
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"]["embedding"].astype(F32)[ids]
    for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        layer = p[f"layers_{i}"]
        h = rms_norm(x, layer["input_norm"]["scale"], eps)
        x = x + (kda if mixer == "kda" else mla)(layer[mixer], h, cfg)
        h = rms_norm(x, layer["post_attn_norm"]["scale"], eps)
        if ffn == "moe":
            x = x + moe(layer["moe"], h, cfg)
        else:
            m = layer["mlp"]
            x = x + gated_mlp(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                              m["down_proj"]["kernel"])
    return x


def _logits(params, x, cfg):
    p = params["params"]
    x = rms_norm(x, p["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x @ p["lm_head"]["kernel"].astype(F32)


def forward(params, ids, cfg: dict, last: int):
    """Float32 logits [last, held vocabulary] of one sequence's last
    positions."""
    with jax.default_matmul_precision("highest"):
        return _logits(params, hidden_states(params, ids, cfg)[-last:], cfg)


def loss(params, ids, targets, cfg: dict):
    """Mean next-token cross-entropy of one sequence (``targets`` are the
    ids already shifted)."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(
            _logits(params, hidden_states(params, ids, cfg), cfg), axis=-1
        )
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].mean()
