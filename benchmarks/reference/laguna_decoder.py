"""Plain reference of Laguna's decoder (``model_type`` ``laguna``: sliding-
window and full attention layers mixed, each kind with its own head count and
rotation, a gated attention output, over a sigmoid-routed expert layer with a
shared expert), given one expert-parallel rank's share of it: the routed
experts ``expert_rank * num_experts`` and the ``num_experts - 1`` that follow,
of the ``num_experts_published`` the router scores, and the first
``vocab_size`` token ids.

Pre-norm layers, h = RMSNorm(x), no biases. Layer l, with H = its entry of
``num_attention_heads_per_layer``, d = ``head_dim``, ``num_key_value_heads``
K/V heads, token t at position t:

    q = h W_q  [T, H, d];   k = h W_k,  v = h W_v  [T, kv, d]
    full layer (layer_types[l] == "full_attention"):
        channels [0, r) of each head of q and k turn, r = d x
        partial_rotary_factor, channel i with channel i + r/2 by the angle
        t inv_freq_i, cos and sin times attention_factor; channels [r, d)
        pass. inv_freq is YaRN's over r channels:
            f_i = theta^(-2i/r),  g_i = f_i / factor
            pair(n) = r ln(original / (2 pi n)) / (2 ln theta)
            low = floor(pair(beta_fast)), high = ceil(pair(beta_slow)),
                  cut to [0, r - 1]
            ramp_i = clip((i - low) / (high - low), 0, 1)
            inv_freq_i = g_i ramp_i + f_i (1 - ramp_i)
        row i sees keys j <= i.
    sliding layer: the plain table theta^(-2i/d) over the whole head; row i
        sees keys 0 <= i - j < sliding_window (itself and the window - 1
        before it).
    o_n = softmax(q_n k_g^T d^-1/2 + mask) v_g,   g = n // (H / kv)
    o_n <- o_n sigmoid(h W_g)_n      (gating: one gate a head and token)
    out = concat(o) W_o

Scores are taken a block of query rows at a time, an explicit masked
softmax: a full layer's block against every key, a sliding layer's against
the keys its band can reach and no others.

FFN: ``mlp_layer_types[l] == "dense"``: a SwiGLU of ``intermediate_size``.
Else: s = sigmoid(h W_r) over all the router's experts, the largest
``num_experts_per_tok`` chosen, gates s at the chosen renormalised to sum to
one and times ``moe_routed_scaling_factor``; every held expert sees every
token and a zero gate removes it; what the experts held elsewhere would add
is left out, here as in the program; one shared SwiGLU expert of
``shared_expert_intermediate_size`` is added, ungated.

``forward`` and ``loss`` take the system's parameter tree (flax names: the
full layers' mixer is ``attn``, the sliding layers' ``swa``) and the
configuration file's own keys. There is no auxiliary loss."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, Q_BLOCK, gated_mlp, rms_norm
# One expert-parallel rank's share of an expert layer is the same thing in
# every model that holds one.
from .kimi_linear_decoder import held_experts, routed

# Per-position error ||system - reference|| / ||reference|| over the held
# vocabulary, as the other references have it, on the last 256 positions of a
# 16,384-token sequence. The readings are
# benchmarks/tools/reference_readings_of.py's (wrong_laguna.py) and the cell's
# own runs', on the chip at the published widths (PERF.md, Findings, PR 45).
#
# The system's positions lie in two heaps, as the sibling held cells' do. Most
# are bfloat16's noise through eight layers, median 0.0087 to 0.0095 and all
# of them under 0.01; the rest, 0.03 to 0.08, are flips of the 8th of 256
# sigmoid scores in some layer whose entering or leaving expert is one of the
# 32 held here (an eighth of the experts, so more positions than in the
# sixteenth-holding siblings). per_position_rel_err 0.02 stands between the
# heaps, at twice the first one's edge. Within it lay 82.0% to 91.0% of
# positions over fourteen seeds (the same share within 0.01). The share asked for lies between the worst of those and the best
# reading of a program that has to fail, which is none at all, nearer the
# system's side of the middle since every wrong reading is zero and fresh
# seeds can only read lower.
#
# What it refuses, positions within 0.02 (and within 0.05): the reference in
# the nearest precision below the configuration's bfloat16 (weights and every
# norm's output rounded to float8 e4m3): none, median 0.161 to 0.185. The
# sliding layers' window dropped (every key up to the row's own): none,
# median 0.24 to 0.27. The full layers' cos and sin not times
# attention_factor: none, 0.35 to 0.37. The attention's output not gated:
# none, 0.67 to 0.68. The second half of a full layer's head turned in place
# of the first: none, 0.58 to 0.63. (Five seeds each.)
#
# What it does not refuse: a window of 513 (one key more at the band's far
# edge, of 512: median 0.0025 to 0.0027, 95% to 98% within 0.01) and the
# router's matmul and sigmoid in bfloat16 (median 0.0015 to 0.0020, 74% to
# 84% within 0.02: it flips
# the kind of positions the bfloat16 hidden states already flip). Both are
# refused in float32 on the CPU (tests/test_laguna_model.py,
# tests/test_flash_window.py), where a window off by one moves the kernels'
# output past 1e-2.
TOLERANCE = {"per_position_rel_err": 0.02, "min_share_within": 0.50}

MIXER_OF = {"full_attention": "attn", "sliding_attention": "swa"}


def _w(p):
    return p["kernel"].astype(F32)


def inv_freq(rope: dict, turning: int) -> np.ndarray:
    """One kind's ``turning // 2`` frequencies, in float64."""
    i = np.arange(turning // 2, dtype=np.float64)
    f = rope["rope_theta"] ** (-2.0 * i / turning)
    if rope["rope_type"] == "default":
        return f
    if rope["rope_type"] != "yarn":
        raise ValueError(rope["rope_type"])

    def pair(n):
        return turning * math.log(
            rope["original_max_position_embeddings"] / (2 * math.pi * n)
        ) / (2 * math.log(rope["rope_theta"]))

    low = max(math.floor(pair(rope["beta_fast"])), 0)
    high = min(math.ceil(pair(rope["beta_slow"])), turning - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f / rope["factor"] * ramp + f * (1.0 - ramp)


def rotate(x, rope: dict):
    """x [T, heads, d], token t at position t: the leading d x
    ``partial_rotary_factor`` channels turn, the others pass."""
    t, _, d = x.shape
    turning = int(d * rope["partial_rotary_factor"])
    angle = jnp.arange(t, dtype=F32)[:, None, None] * jnp.asarray(
        inv_freq(rope, turning), F32)
    amplitude = rope.get("attention_factor", 1.0)
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    a, b = x[..., : turning // 2], x[..., turning // 2: turning]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., turning:]], axis=-1)


def window_of(cfg: dict, layer: int):
    """The layer's window, None where it sees every key up to its own."""
    sliding = cfg["layer_types"][layer] == "sliding_attention"
    return cfg["sliding_window"] if sliding else None


def banded_gqa(q, k, v, window):
    """q [T, heads, d]; k, v [T, kv_heads, d] -> [T, heads, d]: row i over
    keys j <= i, under a ``window`` over 0 <= i - j < window. Query rows are
    taken Q_BLOCK at a time against the keys at their positions and the
    ``reach`` before them: every key, or the window's."""
    t, heads, d = q.shape
    kv_heads = k.shape[1]
    block = min(Q_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence {t} is not a multiple of {block}")
    reach = t - block if window is None else min(window - 1, t - block)
    k, v = (jnp.pad(a, ((reach, 0), (0, 0), (0, 0))) for a in (k, v))
    qg = q.reshape(t // block, block, kv_heads, heads // kv_heads, d)
    starts = jnp.arange(t // block) * block

    def one_block(args):
        qb, start = args  # [block, kv_heads, group, d]
        # Padded row start + n is position start - reach + n.
        kb, vb = (jax.lax.dynamic_slice_in_dim(a, start, reach + block)
                  for a in (k, v))
        key_pos = start - reach + jnp.arange(reach + block)[None, :]
        back = (start + jnp.arange(block))[:, None] - key_pos
        visible = (key_pos >= 0) & (back >= 0)
        if window is not None:
            visible &= back < window
        scores = jnp.einsum("qhgd,khd->hgqk", qb, kb) / jnp.sqrt(F32(d))
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1), vb)

    return jax.lax.map(one_block, (qg, starts)).reshape(t, heads, d)


def attention(p, x, cfg, layer: int):
    rope = cfg["rope_parameters"][cfg["layer_types"][layer]]
    q = rotate(jnp.einsum("th,hnd->tnd", x, _w(p["q_proj"])), rope)
    k = rotate(jnp.einsum("th,hnd->tnd", x, _w(p["k_proj"])), rope)
    v = jnp.einsum("th,hnd->tnd", x, _w(p["v_proj"]))
    o = banded_gqa(q, k, v, window_of(cfg, layer))
    if cfg["gating"]:
        o = o * jax.nn.sigmoid(x @ _w(p["g_proj"]))[..., None]
    return jnp.einsum("tnd,ndh->th", o, _w(p["o_proj"]))


def router_gates(p, x, cfg):
    """[T, E] gates over all the router's experts: zero where an expert was
    not chosen."""
    n, k = cfg["num_experts_published"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ _w(p["router"]))
    top, idx = jax.lax.top_k(s, k)
    top = top / top.sum(axis=-1, keepdims=True) * cfg["moe_routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(idx, n, dtype=F32) * top[..., None], axis=1)


def swiglu(p, x):
    return gated_mlp(x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                     p["down_proj"]["kernel"])


def moe(p, x, cfg):
    out = routed(p, x, cfg, router_gates(p, x, cfg), held_experts(cfg))
    return out + swiglu(p["shared"], x)


def hidden_states(params, ids, cfg: dict):
    """ids [T] -> the final norm's input [T, hidden]."""
    p = params["params"]
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"]["embedding"].astype(F32)[ids]
    for i in range(cfg["num_hidden_layers"]):
        layer = p[f"layers_{i}"]
        mixer = layer[MIXER_OF[cfg["layer_types"][i]]]
        x = x + attention(
            mixer, rms_norm(x, layer["input_norm"]["scale"], eps), cfg, i)
        h = rms_norm(x, layer["post_attn_norm"]["scale"], eps)
        if cfg["mlp_layer_types"][i] == "dense":
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
