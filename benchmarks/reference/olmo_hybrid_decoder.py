"""Plain reference of Olmo-Hybrid's decoder (``model_type`` ``olmo_hybrid``:
three Gated DeltaNet linear-attention layers to one full softmax-attention
layer, every layer over a dense SwiGLU, the norms after the sublayers), given
the first ``vocab_size`` token ids of its vocabulary.

Every layer (the OLMo 2/3 order; eps ``rms_norm_eps``; no bias anywhere):

    h   = x + RMSNorm(mixer(x))          the mixer reads the raw stream
    out = h + RMSNorm(SwiGLU(h))         SwiGLU of ``intermediate_size``
    logits = RMSNorm(out_last) W_head    the head untied

Layer l, counted from 0, is what ``layer_types[l]`` says.

``linear_attention`` (H = ``linear_num_key_heads`` = ``linear_num_value_heads``,
dk = ``linear_key_head_dim``, dv = ``linear_value_head_dim``; a float32 state
S in R^{dk x dv} a head, zero before token 0), token by token in a
``lax.scan``, no chunks, no inverse and no kernel:

    q~, k~ = SiLU(conv(x W_q)), SiLU(conv(x W_k))   [T, H, dk]  causal depthwise,
    v      = SiLU(conv(x W_v))                      [T, H, dv]  the last tap on token t
    q = q~ / sqrt(|q~|^2 + 1e-6) dk^-1/2,  k = k~ / sqrt(|k~|^2 + 1e-6)   a head
    g_t    = -exp(A_log_h) softplus(x_t W_a + dt_bias_h)     one scalar a head, <= 0
    beta_t = 2 sigmoid(x_t W_b)   (``linear_allow_neg_eigval``; else sigmoid)
    S_t = (I - beta_t k_t k_t^T) e^{g_t} S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    y   = RMSNorm_dv(o_t; w) * SiLU(x_t W_g)      one weight [dv] for all heads
    mixer(x) = concat_h(y) W_o

(The program keeps W_q and W_k as one matrix, ``qk_proj``, q's columns
first, and their filters as one, ``qk_conv``.)

``full_attention`` (H = ``num_attention_heads``, kv =
``num_key_value_heads``, d = ``head_dim``):

    q = RMSNorm(x W_q), k = RMSNorm(x W_k)   over the whole projection, before
    v = x W_v                                the head split
    q, k turned by the plain rotary table where ``rope_parameters.rope_theta``
    is a number; as published it is null and nothing turns
    o = softmax_{j <= i}(q k^T d^-1/2) v;  mixer(x) = concat(o) W_o

Each row's softmax is taken whole over the keys it sees, a block of query rows
at a time (``common.causal_gqa``, the dense references').

Assumed, as the configuration's file lists with the reasons: ``head_dim``
hidden / heads; the reordered norm in both layer kinds and the QK norm over the
whole projections (OLMo 2/3's modelling code); ``rope_theta`` null read as no
rotation; the Gated DeltaNet as ``fla.layers.gated_deltanet`` builds it
(separate filters with SiLU, one ``A_log`` and ``dt_bias`` a head, the SiLU
gate in the gated RMSNorm, q scaled by dk^-1/2). Departure from the published
model, here as in the program: logits and loss are over the held slice of the
vocabulary.

``forward`` and ``loss`` take the system's parameter tree (flax names) and the
configuration file's own keys."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, causal_gqa, gated_mlp, rms_norm, rotary

# Per-position error ||system - reference|| / ||reference|| over the held
# vocabulary, as the other references have it, on the last 256 positions of an
# 8,192-token sequence. The readings are benchmarks/tools/
# reference_readings_of.py's (wrong_olmo_hybrid.py) and the cell's own runs', on
# the chip at the published widths (PERF.md, Findings, PR 52).
#
# The system's positions lie in one heap, bfloat16's noise through four layers
# whose norms follow the sublayers (each sublayer's error is renormalised with
# its output and not amplified down the stream, so the heap is nearer than the
# pre-norm Solar cell's 0.024 to 0.038): median 0.0153 to 0.0158 on every seed,
# p90 0.0172 to 0.0175, 96.5% to 97.7% of a seed's positions within 0.02,
# 99.6% to 100% within 0.03, every position within 0.05 (the largest 0.0448).
# per_position_rel_err 0.03 stands at twice the median and 1.7 times the p90;
# the share asked for lies between the system's worst (0.996) and every wrong
# reading that has to fail (none within 0.05), nearer the system's side since
# fresh seeds can only read lower.
#
# What it refuses, positions within 0.03 (and within 0.05): the reference in
# the nearest precision below the configuration's bfloat16 (weights and every
# norm's output rounded to float8 e4m3): none (none), median 0.325 to 0.329.
# beta left undoubled: none, median 0.54 to 0.58. The full layer's q and k not
# normed: none, 0.38 to 0.39. A sigmoid for the gate's SiLU: none, 1.11 to
# 1.14; the decay left out: none, 1.08 to 1.11; the norms before the
# sublayers: none, 1.21 to 1.23. The full layer's q and k rotated at theta
# 10,000: 58% and 5% of two seeds' positions within 0.03 (all within 0.05),
# median 0.030 to 0.032: refused on both seeds, by a margin a third seed may
# not leave (the one full layer is the last of four and its output is normed
# before it is added: a rotation moves the logits by twice the system's own
# noise, no more).
#
# What it does not refuse: the reference with the recurrence's state rounded
# to bfloat16 after every token reads median 0.019 to 0.022 and 98.4% to 99.2%
# within 0.03: as near the float32 reference as the bfloat16 program is, as in
# the two KDA cells (PERF.md, Open questions). The state is float32 in the
# program (tests/test_kda_op.py follows the kernels against the recurrence).
TOLERANCE = {"per_position_rel_err": 0.03, "min_share_within": 0.9}

L2_EPS = 1e-6


def _w(p):
    return p["kernel"].astype(F32)


def is_full(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == "full_attention"


# ------------------------------------------------------ the full-attention mixer


def turned(q, k, cfg):
    """q and k [T, heads, d] as the attention takes them: rotated where the
    source names a theta, as they are where it is null."""
    theta = (cfg.get("rope_parameters") or {}).get("rope_theta")
    if theta is None:
        return q, k
    return rotary(q, theta), rotary(k, theta)


def qk_normed(p, q, k, eps):
    """RMSNorm over the whole q and the whole k projection [T, heads * d]."""
    return (rms_norm(q, p["q_norm"]["scale"], eps),
            rms_norm(k, p["k_norm"]["scale"], eps))


def full_attention(p, x, cfg):
    t = x.shape[0]
    flat = lambda w: w.reshape(w.shape[0], -1)  # noqa: E731
    q, k = qk_normed(p, x @ flat(_w(p["q_proj"])), x @ flat(_w(p["k_proj"])),
                     cfg["rms_norm_eps"])
    q, k = q.reshape(t, cfg["num_attention_heads"], -1), k.reshape(
        t, cfg["num_key_value_heads"], -1)
    v = jnp.einsum("th,hnd->tnd", x, _w(p["v_proj"]))
    q, k = turned(q, k, cfg)
    return jnp.einsum("tnd,ndh->th", causal_gqa(q, k, v), _w(p["o_proj"]))


# ------------------------------------------------------ the Gated DeltaNet mixer


def conv_silu(x, taps):
    """x [T, D]; taps [K, D], the last on the current token."""
    n, t = taps.shape[0], x.shape[0]
    past = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), F32), x])
    y = jnp.zeros_like(x)
    for i in range(n):
        y = y + past[i:i + t] * taps[i].astype(F32)
    return y * jax.nn.sigmoid(y)


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def write_strength(p, x, cfg):
    """beta [T, H]."""
    beta = jax.nn.sigmoid(x @ _w(p["b_proj"]))
    return 2.0 * beta if cfg["linear_allow_neg_eigval"] else beta


def log_decay(p, x):
    """g [T, H], one scalar a head and token, <= 0."""
    soft = jax.nn.softplus(x @ _w(p["a_proj"]) + p["dt_bias"].astype(F32))
    return -jnp.exp(p["A_log"].astype(F32)) * soft


def gated_delta_rule(q, k, v, g, beta):
    """q, k [T, H, dk]; v [T, H, dv]; g, beta [T, H] -> o [T, H, dv] and the
    last state [H, dk, dv]. S_t = (I - beta k k^T) e^g S + beta k v^T."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def token(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, None, None] * S
        S = S + beta[:, None, None] * jnp.einsum(
            "hi,hv->hiv", k, v - jnp.einsum("hjv,hj->hv", S, k))
        return S, jnp.einsum("hiv,hi->hv", S, q)

    S, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), F32), (q, k, v, g, beta))
    return o, S


def out_gate(gate):
    """The gated RMSNorm's activation of x W_g."""
    return jax.nn.silu(gate)


def gdn_operands(p, x, cfg):
    """(q, k, v, g, beta) of ``gated_delta_rule`` from the layer's input."""
    heads, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    t = x.shape[0]
    qk = conv_silu(x @ _w(p["qk_proj"]), p["qk_conv"])
    v = conv_silu(x @ _w(p["v_proj"]), p["v_conv"])
    q = unit(qk[:, :heads * dk].reshape(t, heads, dk)) * dk ** -0.5
    k = unit(qk[:, heads * dk:].reshape(t, heads, dk))
    return (q, k, v.reshape(t, cfg["linear_num_value_heads"], -1),
            log_decay(p, x), write_strength(p, x, cfg))


def gdn(p, x, cfg):
    t = x.shape[0]
    o, _ = gated_delta_rule(*gdn_operands(p, x, cfg))
    o = rms_norm(o, p["o_norm"]["scale"], cfg["rms_norm_eps"])
    o = o * out_gate(x @ _w(p["g_proj"])).reshape(o.shape)
    return o.reshape(t, -1) @ _w(p["o_proj"])


# ------------------------------------------------------------------ the model


def decoder_layer(layer, x, cfg, i):
    """One layer on x [T, hidden]: the norms follow the sublayers."""
    eps = cfg["rms_norm_eps"]
    mixed = (full_attention(layer["attn"], x, cfg) if is_full(cfg, i)
             else gdn(layer["gdn"], x, cfg))
    h = x + rms_norm(mixed, layer["post_mixer_norm"]["scale"], eps)
    m = layer["mlp"]
    fed = gated_mlp(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                    m["down_proj"]["kernel"])
    return h + rms_norm(fed, layer["post_ffn_norm"]["scale"], eps)


def hidden_states(params, ids, cfg: dict):
    """ids [T] -> the final norm's input [T, hidden]."""
    p = params["params"]
    x = p["embed_tokens"]["embedding"].astype(F32)[ids]
    for i in range(cfg["num_hidden_layers"]):
        x = decoder_layer(p[f"layers_{i}"], x, cfg, i)
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
