"""Plain reference of sarvam-105b's decoder (``model_type`` ``sarvam_mla``:
DeepSeek-V2's latent attention without a q latent, over a sigmoid-routed
expert layer), given one expert-parallel rank's share of it: the routed
experts ``expert_rank * num_experts`` and the ``num_experts - 1`` that
follow, of the ``num_experts_published`` the router scores, and the first
``vocab_size`` token ids.

Pre-norm layers, h = RMSNorm(x). Every layer's mixer, per token t and head n:

    q = h W_q                       [T, heads, nope + pe] = [q_nope | q_pe]
    [c | k_pe] = h W_kva            (kv_lora_rank | pe), k_pe one for all heads
    [k_nope | v] = RMSNorm(c) W_kvb a head;  k = [k_nope | k_pe]
    q = RMSNorm_head(q),  k = RMSNorm_head(k)     (use_qk_norm: over a head's
                                                   nope + pe channels, one
                                                   weight shared by the heads)
    q_pe, k_pe <- rotated by position t; q_nope, k_nope pass
    o = softmax(q k^T scale, causal) v,   out = o W_o

The rotation pairs channel i of the pe part with channel i + pe / 2 (the
rotate-half form; the published code's interleaved pairs are this after a
fixed permutation of W_q's and W_kva's columns, which random weights do not
tell apart). Its frequencies are YaRN's (``deepseek_yarn``):

    f_i = theta^(-2i/pe),  g_i = f_i / factor
    pair(r) = pe ln(original / (2 pi r)) / (2 ln theta)
    low = floor(pair(beta_fast)), high = ceil(pair(beta_slow)), in [0, pe - 1]
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = g_i ramp_i + f_i (1 - ramp_i)
    yarn_mscale(s, m) = 0.1 m ln s + 1

cos and sin are times yarn_mscale(factor, mscale) / yarn_mscale(factor,
mscale_all_dim), and scale = (nope + pe)^-1/2 yarn_mscale(factor,
mscale_all_dim)^2. Scores are taken a block of query rows at a time against
every key, an explicit masked softmax.

The first ``first_k_dense_replace`` layers' FFN is a dense SwiGLU. The
others': s = sigmoid(h W_r) over all the router's experts; the top k of s +
bias are chosen; the gates are s at the chosen, renormalised to sum to one
and times ``routed_scaling_factor``; every held expert sees every token and
a zero gate removes it; what the experts held elsewhere would add is left
out, here as in the program; one shared SwiGLU expert is added.

``forward`` and ``loss`` take the system's parameter tree (flax names) and
the configuration file's own keys. There is no auxiliary loss."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, Q_BLOCK, gated_mlp, rms_norm
# One expert-parallel rank's share of an expert layer is the same thing in
# both models that hold one.
from .kimi_linear_decoder import held_experts, routed

# Per-position error ||system - reference|| / ||reference|| over the held
# vocabulary, as the other references have it, on the last 256 positions of a
# 4,096-token sequence. The readings are
# benchmarks/tools/reference_readings_of.py's (wrong_sarvam.py) and the cell's own runs',
# on the chip at the published widths (PERF.md, Findings, PR 37): forty-one
# seeds.
#
# The system's positions lie in two heaps, as Kimi-Linear's do. Most are
# bfloat16's noise through five layers, median 0.0138 to 0.0148 (p90 0.0150
# to 0.0156); the rest, up to 0.11 to 0.18, are flips of the 8th of 128
# sigmoid scores in some layer whose entering or leaving expert is one of
# the 8 held here. Within 0.02 lay 91.0% to 96.9% of positions (within 0.015:
# 82.8% to 89.5%). The share asked for lies between the worst of those and
# the best reading of a program that has to fail, nearer the latter's side
# of the middle, since fresh seeds read lower, not higher.
#
# What it refuses, three seeds, positions within 0.02: the reference in the
# nearest precision below the configuration's bfloat16 (weights and every
# norm's output rounded to float8 e4m3): none, median 0.235 to 0.241. The
# softmax scale without YaRN's mscale squared: none, median 0.75 to 0.76.
# The per-head QK norm left out: none, 0.41 to 0.54. Nothing rotated: none,
# 1.06 to 1.08; q's part rotated and the shared key's not: none, 1.20 to
# 1.21. The shared expert left out: none, 0.61. The gates not times 2.5:
# 10.2% to 18.4%, median 0.080 to 0.093.
#
# What it does not refuse: the reference with the router's inputs, logits
# and sigmoids rounded to bfloat16 reads median 0.0011 and 93.4% to 96.5%
# within 0.02: it flips the same kind of positions the bfloat16 hidden
# states already flip in the system, and as many of them, so no limit on
# these logits tells a bfloat16 router from the rest of a bfloat16 model
# (PERF.md, Open questions). The router runs in float32 in the program
# (tests/test_sarvam_mla_model.py refuses a bfloat16 one in float32).
TOLERANCE = {"per_position_rel_err": 0.02, "min_share_within": 0.60}


def _w(p):
    return p["kernel"].astype(F32)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict | None) -> np.ndarray:
    """The rotation's ``dim // 2`` frequencies, in float64."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    if scaling is None:
        return f
    if scaling["type"] != "deepseek_yarn":
        raise ValueError(scaling["type"])

    def pair(r):
        original = scaling["original_max_position_embeddings"]
        return dim * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(pair(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f / scaling["factor"] * ramp + f * (1.0 - ramp)


def rotate(x, cfg):
    """x [T, heads, pe], token t at position t: channel i turns with channel
    i + pe / 2 by the angle t inv_freq_i."""
    t, _, pe = x.shape
    scaling = cfg.get("rope_scaling")
    inv_freq = jnp.asarray(yarn_inv_freq(pe, cfg["rope_theta"], scaling), F32)
    amplitude = 1.0
    if scaling is not None:
        amplitude = yarn_mscale(scaling["factor"], scaling["mscale"]) / yarn_mscale(
            scaling["factor"], scaling["mscale_all_dim"])
    angle = jnp.arange(t, dtype=F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    a, b = x[..., : pe // 2], x[..., pe // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def softmax_scale(cfg) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling is not None:
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def causal_attention(q, k, v, scale):
    """q, k [T, H, d]; v [T, H, dv] -> [T, H, dv]: an explicit masked softmax
    over every key, query rows a block at a time."""
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
        scores = scores - scores.max(axis=-1, keepdims=True)
        weights = jnp.exp(scores)
        weights = weights / weights.sum(axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", weights, v)

    starts = jnp.arange(t // block) * block
    out = jax.lax.map(one_block, (q.reshape(t // block, block, *q.shape[1:]), starts))
    return out.reshape(t, *out.shape[2:])


def qkv(p, x, cfg):
    """The mixer up to its attention: q, k [T, H, nope + pe], normed and
    rotated, and v [T, H, dv]."""
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    pe, eps = cfg["qk_rope_head_dim"], cfg["rms_norm_eps"]
    q = jnp.einsum("th,hnd->tnd", x, _w(p["q_proj"]))
    latent = x @ _w(p["kv_a_proj"])
    c = rms_norm(latent[:, :rank], p["kv_a_norm"]["scale"], eps)
    kv = jnp.einsum("tr,rnd->tnd", c, _w(p["kv_b_proj"]))  # [T, H, nope + dv]
    k_pe = jnp.broadcast_to(latent[:, None, rank:], (*kv.shape[:2], pe))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    if cfg["use_qk_norm"]:
        q = rms_norm(q, p["q_norm"]["scale"], eps)
        k = rms_norm(k, p["k_norm"]["scale"], eps)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], cfg)], axis=-1)
    k = jnp.concatenate([k[..., :nope], rotate(k[..., nope:], cfg)], axis=-1)
    return q, k, kv[..., nope:]


def mla(p, x, cfg):
    q, k, v = qkv(p, x, cfg)
    o = causal_attention(q, k, v, softmax_scale(cfg))
    return jnp.einsum("tnd,ndh->th", o, _w(p["o_proj"]))


def router_gates(p, x, cfg):
    """[T, E] gates over all the router's experts: zero where an expert was
    not chosen."""
    n, k = cfg["num_experts_published"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ _w(p["router"]))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(F32), k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    top = top / top.sum(axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(idx, n, dtype=F32) * top[..., None], axis=1)


def swiglu(p, x):
    return gated_mlp(x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                     p["down_proj"]["kernel"])


def moe(p, x, cfg):
    out = routed(p, x, cfg, router_gates(p, x, cfg), held_experts(cfg))
    return out + swiglu(p["shared"], x) if cfg["num_shared_experts"] else out


def hidden_states(params, ids, cfg: dict):
    """ids [T] -> the final norm's input [T, hidden]."""
    p = params["params"]
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"]["embedding"].astype(F32)[ids]
    for i in range(cfg["num_hidden_layers"]):
        layer = p[f"layers_{i}"]
        x = x + mla(layer["mla"], rms_norm(x, layer["input_norm"]["scale"], eps), cfg)
        h = rms_norm(x, layer["post_attn_norm"]["scale"], eps)
        if i < cfg["first_k_dense_replace"]:
            x = x + swiglu(layer["mlp"], h)
        else:
            x = x + moe(layer["moe"], h, cfg)
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
