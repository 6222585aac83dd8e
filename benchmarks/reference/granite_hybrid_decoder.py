"""Plain reference of Granite 4.0-H's decoder (``model_type``
``granitemoehybrid``: Mamba-2 state-space mixers with one NoPE softmax
attention layer to nine of them, every layer over a dense SwiGLU, under
Granite's multipliers, the head tied to the embedding), given the first
``vocab_size`` token ids of its vocabulary.

Body (pre-norm; eps ``rms_norm_eps``; no bias but the filter's):

    h_0    = embedding_multiplier * E[ids]
    h      = x + residual_multiplier * mixer(RMSNorm(x))
    out    = h + residual_multiplier * SwiGLU(RMSNorm(h))     shared_intermediate_size; no routed expert
    logits = (E RMSNorm(out_last)) / logits_scaling           the table again: tie_word_embeddings

Layer l, counted from 0, is what ``layer_types[l]`` says.

``mamba`` (H = ``mamba_n_heads`` heads of P = ``mamba_d_head``; state N =
``mamba_d_state``; one group; a float32 state S in R^{P x N} a head, zero
before token 0), token by token in a ``lax.scan``, no chunk and no kernel:

    [z, xBC, dt] = x W_in                    z [T, H P], xBC [T, H P + 2 N], dt [T, H]
    xBC          = SiLU(conv(xBC) + b)       depthwise, causal, mamba_d_conv taps, one bias a channel
    [u, B, C]    = split(xBC)                u [T, H, P]; B, C [T, N], the same for every head
    dl_t,h       = softplus(dt_t,h + dt_bias_h)                 the step
    a_t,h        = exp(dl_t,h A_h),  A_h = -exp(A_log_h)         the decay, in (0, 1)
    S_t,h        = a_t,h S_{t-1,h} + dl_t,h u_t,h B_t^T
    y_t,h        = S_t,h C_t + D_h u_t,h                          the skip
    y_t          = RMSNorm_{H P}(y_t * SiLU(z_t); w)              the gate first, then ONE norm over all H P channels
    mixer(x)     = y W_out

(The program keeps W_in as three matrices, ``z_proj``, ``xbc_proj`` and
``dt_proj``: z's columns, xBC's and dt's of the source's one ``in_proj``.)

``attention`` (H = ``num_attention_heads`` over ``num_key_value_heads`` K/V
heads; d = ``head_dim``; nothing turns: ``position_embedding_type`` ``nope``):

    q, k, v = x W_q, x W_k, x W_v
    o = softmax_{j <= i}(q k^T * attention_multiplier) v;  mixer(x) = concat_h(o) W_o

The scale is ``attention_multiplier`` (1/64 as published), not d^-1/2 (1/8).
Each row's softmax is taken whole over the keys it sees, a block of query rows
at a time (``common.causal_gqa``'s way, at a scale of its own).

Assumed, as the configuration's file lists with the reasons: ``head_dim``
hidden / heads; the gate before the norm and the norm over the whole H P
channels (the family's gated RMSNorm at one group; mamba_ssm's
``norm_before_gate`` false); no clamp on the step; ``mamba_chunk_size`` no part
of the mathematics (nothing here has a chunk); the initialisers. Departure
from the published model, here as in the program: logits and loss are over
the held slice of the vocabulary. The program divides the final norm's output
by ``logits_scaling`` before the tied head where this divides the logits after
it: the same number in exact arithmetic.

``forward`` and ``loss`` take the system's parameter tree (flax names) and the
configuration file's own keys."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, Q_BLOCK, gated_mlp, rms_norm

# Per-position error ||system - reference|| / ||reference|| over the held
# vocabulary, as the other references have it, on the last 256 positions of an
# 8,192-token sequence. The readings are benchmarks/tools/
# reference_readings_of.py's (wrong_granite_hybrid.py, three seeds) and the
# cell's own thirteen runs', on the chip at the published widths (PERF.md,
# Findings, PR 58).
#
# The system's positions lie in one heap, bfloat16's noise through ten pre-norm
# layers whose sublayers enter the stream times 0.22: median 0.0144 to 0.0148
# on every seed, p90 0.0154 to 0.0156, every position within 0.02 (the largest
# over sixteen seeds 0.0179). per_position_rel_err 0.03 stands at twice the
# median and 1.7 times the largest position read; the share asked for lies between
# the system's (1.0 on every seed) and every wrong reading that has to fail
# (none within 0.05), nearer the system's side since fresh seeds can only read
# lower.
#
# What it refuses, positions within 0.03 (and within 0.05): the reference in
# the nearest precision below the configuration's bfloat16 (weights and every
# norm's output rounded to float8 e4m3): none (none), median 0.234 to 0.239.
# No filter bias: none, median 0.94 to 0.96. Each sublayer added as it is and
# not times 0.22: none, 0.90 to 0.92; the embedding not times 12: none, 1.00
# to 1.02; the logits not divided by 8: none, 7.00. The step without its
# softplus: none (a negative step grows the state: not a number). The decay
# without the head's rate: none, 0.46 to 0.58; the input not scaled by the
# step: none, 1.22; no skip: none, 1.16 to 1.24; the norm before the gate:
# none, 0.75; a norm a head: none, 0.66 to 0.67; B and C a head: none, 0.21;
# another table as the head: none, 1.40.
#
# What it does not refuse, and which test does: a soft-max scale of 1/8 for
# 1/64 reads median 0.0165 to 0.0168 and q and k turned 0.0011 to 0.0012 from
# the reference (projections drawn at 0.02 leave q . k near zero at 2,048
# channels: the one attention layer's soft-max is nearly flat whatever scales
# or turns it); tests/test_granite_hybrid_model.py refuses both in float32 on
# projections of unit size. The recurrence's state rounded to bfloat16 after
# every token reads median 0.004 to 0.011, nearer the float32 reference than
# the bfloat16 program is, as in the three other scan cells (PERF.md, Open
# questions); tests/test_kda_op.py test_ssd_state_is_float32 holds the kernels
# to the recurrence where a bfloat16 state is a hundred times further.
TOLERANCE = {"per_position_rel_err": 0.03, "min_share_within": 0.9}


def _w(p):
    return p["kernel"].astype(F32)


def is_attention(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == "attention"


# ------------------------------------------------------ the attention mixer


def softmax_scale(cfg: dict) -> float:
    return cfg["attention_multiplier"]


def turned(q, k, cfg):
    """q and k [T, heads, d] as the attention takes them: as they are."""
    return q, k


def causal_gqa_scaled(q, k, v, scale):
    """``common.causal_gqa`` at ``scale`` in place of d^-1/2: q [T, heads, d];
    k, v [T, kv_heads, d] -> [T, heads, d]."""
    t, heads, d = q.shape
    kv_heads = k.shape[1]
    block = min(Q_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence {t} is not a multiple of {block}")
    qg = q.reshape(t // block, block, kv_heads, heads // kv_heads, d)
    key_pos = jnp.arange(t)

    def one_block(args):
        qb, start = args
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) * scale
        visible = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one_block, (qg, jnp.arange(t // block) * block))
    return out.reshape(t, heads, d)


def attention(p, x, cfg):
    q, k, v = (jnp.einsum("th,hnd->tnd", x, _w(p[n])) for n in ("q_proj", "k_proj", "v_proj"))
    q, k = turned(q, k, cfg)
    o = causal_gqa_scaled(q, k, v, softmax_scale(cfg))
    return jnp.einsum("tnd,ndh->th", o, _w(p["o_proj"]))


# ------------------------------------------------------ the Mamba-2 mixer


def conv_silu(x, taps, bias):
    """x [T, D]; taps [K, D], the last on the current token; bias [D]."""
    n, t = taps.shape[0], x.shape[0]
    past = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), F32), x])
    y = jnp.zeros_like(x) + bias.astype(F32)
    for i in range(n):
        y = y + past[i:i + t] * taps[i].astype(F32)
    return y * jax.nn.sigmoid(y)


def step(p, dt):
    """dl [T, H] > 0 from the projection's dt columns."""
    return jax.nn.softplus(dt + p["dt_bias"].astype(F32))


def log_decay(p, dl):
    """dl_t,h A_h [T, H], <= 0."""
    return -jnp.exp(p["A_log"].astype(F32)) * dl


def written(dl, u):
    """What a token writes into the state, before B: the input times its
    step. dl [T, H]; u [T, H, P]."""
    return dl[..., None] * u


def skipped(p, u):
    """D_h u_t,h."""
    return p["D"].astype(F32)[:, None] * u


def shared(b, heads):
    """B (or C) [T, N] as each of the ``heads`` heads takes it: the one
    group's, as it is."""
    return b


def state(S):
    """The recurrence's state after a token: float32, as it is."""
    return S


def selective_scan(u, dl, g, b, c):
    """u [T, H, P]; dl, g [T, H] (the step and its log-decay); b, c [T, N] or
    [T, H, N] -> S_t C_t [T, H, P]. S_t = e^{g_t} S_{t-1} + (dl_t u_t) B_t^T."""
    heads, p = u.shape[1], u.shape[2]
    b, c = (jnp.broadcast_to(x[:, None], (x.shape[0], heads, x.shape[1]))
            if x.ndim == 2 else x for x in (b, c))

    def token(S, x):
        w, g, b, c = x
        S = state(jnp.exp(g)[:, None, None] * S + w[:, :, None] * b[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, c)

    zero = jnp.zeros((heads, p, b.shape[2]), F32)
    return jax.lax.scan(token, zero, (written(dl, u), g, b, c))[1]


def gated_norm(p, y, z, cfg):
    """y, z [T, H P]: the gate first, then the one norm over all channels."""
    return rms_norm(y * jax.nn.silu(z), p["norm"]["scale"], cfg["rms_norm_eps"])


def mamba(p, x, cfg):
    t = x.shape[0]
    heads, n = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    inner = heads * cfg["mamba_d_head"]
    z = x @ _w(p["z_proj"])
    xbc = conv_silu(x @ _w(p["xbc_proj"]), p["conv"], p["conv_bias"])
    u = xbc[:, :inner].reshape(t, heads, -1)
    dl = step(p, x @ _w(p["dt_proj"]))
    y = selective_scan(u, dl, log_decay(p, dl), shared(xbc[:, inner:inner + n], heads),
                       shared(xbc[:, inner + n:], heads))
    y = (y + skipped(p, u)).reshape(t, inner)
    return gated_norm(p, y, z, cfg) @ _w(p["out_proj"])


# ------------------------------------------------------------------ the model


def residual_scale(cfg: dict) -> float:
    return cfg["residual_multiplier"]


def logit_divisor(cfg: dict) -> float:
    return cfg["logits_scaling"]


def decoder_layer(layer, x, cfg, i):
    """One layer on x [T, hidden]: pre-norm, each sublayer's output times
    ``residual_multiplier``."""
    eps, scale = cfg["rms_norm_eps"], residual_scale(cfg)
    fed = rms_norm(x, layer["input_norm"]["scale"], eps)
    h = x + scale * (attention(layer["attn"], fed, cfg) if is_attention(cfg, i)
                     else mamba(layer["mamba"], fed, cfg))
    m = layer["mlp"]
    return h + scale * gated_mlp(
        rms_norm(h, layer["post_attn_norm"]["scale"], eps),
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"], m["down_proj"]["kernel"])


def hidden_states(params, ids, cfg: dict):
    """ids [T] -> the final norm's input [T, hidden]."""
    p = params["params"]
    x = cfg["embedding_multiplier"] * p["embed_tokens"]["embedding"].astype(F32)[ids]
    for i in range(cfg["num_hidden_layers"]):
        x = decoder_layer(p[f"layers_{i}"], x, cfg, i)
    return x


def head(p):
    """[hidden, vocabulary]: the embedding table again."""
    return p["embed_tokens"]["embedding"].astype(F32).T


def _logits(params, x, cfg):
    p = params["params"]
    x = rms_norm(x, p["final_norm"]["scale"], cfg["rms_norm_eps"])
    return (x @ head(p)) / logit_divisor(cfg)


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
