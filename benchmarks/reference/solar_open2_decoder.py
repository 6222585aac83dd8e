"""Plain reference of Solar-Open2's decoder (``model_type`` ``solar_open2``:
gated delta-rule linear attention with negative eigenvalues, one softmax
layer of grouped-query attention without rotation under an element-wise
output gate to every three of them, every layer over a sigmoid-routed expert
layer with a shared expert), given one expert-parallel rank's share of it:
the routed experts ``expert_rank * n_routed_experts`` and the
``n_routed_experts - 1`` that follow, of the ``n_routed_experts_published``
the router scores (its weight's width), and the first ``vocab_size`` token
ids.

Pre-norm layers, x^ = RMSNorm(x) (eps ``rms_norm_eps``), h = x + mixer(x^),
out = h + moe(RMSNorm(h)); no bias but the one named below. Layer l counted
from 0, token t at position t, which no layer is told.

l in ``gqa_layers`` (H = ``num_attention_heads``, kv =
``num_key_value_heads``, d = ``head_dim``):

    q = x^ W_q [T, H, d];  k = x^ W_k, v = x^ W_v [T, kv, d]   (no rotation)
    o_n = softmax_{j <= i}(q_n k_m^T d^-1/2) v_m,   m = n // (H / kv)
    o <- o * sigmoid(x^ W_g),   W_g [hidden, H, d]  (``use_gqa_gate``)
    out = concat(o) W_o

Each row's softmax is taken whole over the keys it sees, a block of query rows
at a time so that [H, block, T] scores fit (``common.causal_gqa``, the dense
references').

Other layers, KDA (H, d = ``linear_attn_config``'s ``num_heads``,
``head_dim``; a float32 state S in R^{d x d} a head, zero before token 0),
token by token in a ``lax.scan``, no chunks and no kernel:

    q, k, v = SiLU(conv4(x^ W_{q,k,v}))   causal, depthwise, tap 3 on token t
    q <- q / sqrt(|q|^2 + 1e-6) d^-1/2,  k <- k / sqrt(|k|^2 + 1e-6)  a head
    a_t = exp(-exp(A_log) softplus(x^ W_f1 W_f2 + dt_bias))   in (0, 1)^d
    beta_t = 2 sigmoid(x^ W_b)  (``kda_allow_neg_eigval``; else sigmoid)
    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    o <- RMSNorm_head(o) * sigmoid(x^ W_g1 W_g2 + b_g);  out = concat(o) W_o

Expert layer: s = sigmoid(x^ W_r) over all the router's experts in float32;
the ``num_experts_per_tok`` largest chosen (no groups); gates s at the chosen
over their sum (``norm_topk_prob``) times ``routed_scaling_factor``; a Python
loop over the held experts, each a SwiGLU of ``moe_intermediate_size`` that
sees every token under its column of the gates (zero where it was not
chosen); one shared SwiGLU expert of ``n_shared_experts`` x that width,
ungated.

Departures from the published model, here as in the program: what the
experts held on other ranks would add to a layer's result is left out;
logits and loss are over the held slice of the vocabulary; the program's
selection bias, which stays zero, is not read; there is no auxiliary loss.
What the source leaves open (the gate's width, the router's score, the
low-rank width) is in the configuration file's ``assumed``.

``forward`` and ``loss`` take the system's parameter tree (flax names) and
the configuration file's own keys."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, causal_gqa, gated_mlp, rms_norm

# Per-position error ||system - reference|| / ||reference|| over the held
# vocabulary, as the other references have it, on the last 256 positions of a
# 4,096-token sequence. The readings are benchmarks/tools/
# reference_readings_of.py's (wrong_solar.py) and the cell's own runs', on the
# chip at the published widths (PERF.md, Findings, PR 48): 29 seeds.
#
# The system's positions lie in two heaps, as the sibling held cells' do, both
# further out than theirs. The first is bfloat16's noise through four layers,
# median 0.0237 to 0.0378 and never under 0.02: each sublayer alone, fed the
# reference's own input, lies 0.004 to 0.007 from the reference's (the KDA
# mixers 0.0055 to 0.0069, of which beta's doubling is a fifth), and the
# stream passes it on amplified, the first layer's attention writing 14 times
# what the embedding holds. The second, 0.03 to 0.12, goes with flips of the
# 8th of 320 sigmoid scores whose entering or leaving expert is one of the 8
# held here: rare (a fortieth is held), but what a flip changes at one token
# the KDA state carries to every later one. Of two seeds taken apart sublayer
# by sublayer, the one with such flips in three expert layers read p90 0.051
# over its last 256 positions and the one with none 0.029. Within 0.03 lay
# 26% to 100% of a seed's positions, within 0.05 77% to 100%, within 0.08
# 97.3% to 100%, within 0.1 99.2% to 100% (one or two positions in 256 past
# it, in six seeds of 29; the largest 0.119). per_position_rel_err 0.1 stands
# past the second heap and under the nearest wrong program's nearest position
# by a factor of 3.4; the share asked for lies between the system's worst
# (0.992) and every wrong reading (none), nearer the system's side since
# fresh seeds can only read lower.
#
# What it refuses, positions within 0.1 (and within 0.2): the reference in
# the nearest precision below the configuration's bfloat16 (weights and every
# norm's output rounded to float8 e4m3): none, nearest position 0.419, median
# 0.462 to 0.473. beta left undoubled: none, nearest 0.345, median 0.378 to
# 0.396. The GQA layer's output not gated: none, median 0.94 to 0.95; one
# gate value a head: none, 0.92 to 0.94; q and k of the GQA layer rotated:
# none, 1.19 to 1.20; a router over the 8 held experts alone: none, 0.71 to
# 0.73. (Three seeds each.)
#
# What it does not refuse: the reference with KDA's state rounded to bfloat16
# after every token reads median 0.0109 to 0.0112 and 94.5% to 99.6% within
# 0.02: nearer the float32 reference than the bfloat16 program is, as in the
# Kimi-Linear cell (PERF.md, Open questions). The state is float32 in the
# program (tests/test_kda_op.py follows it against the recurrence's own).
TOLERANCE = {"per_position_rel_err": 0.1, "min_share_within": 0.9}

L2_EPS = 1e-6


def _w(p):
    return p["kernel"].astype(F32)


def is_gqa(cfg: dict, layer: int) -> bool:
    return layer in cfg["gqa_layers"]


# ------------------------------------------------------------ the GQA mixer


def gqa_gate(p, x, o):
    """o [T, H, d] times the element-wise gate of q's width."""
    return o * jax.nn.sigmoid(jnp.einsum("th,hnd->tnd", x, _w(p["g_proj"])))


def gqa(p, x, cfg):
    if cfg["use_rope"]:
        raise NotImplementedError("the published model turns neither q nor k")
    q = jnp.einsum("th,hnd->tnd", x, _w(p["q_proj"]))
    k = jnp.einsum("th,hnd->tnd", x, _w(p["k_proj"]))
    v = jnp.einsum("th,hnd->tnd", x, _w(p["v_proj"]))
    o = causal_gqa(q, k, v)
    if cfg["use_gqa_gate"]:
        o = gqa_gate(p, x, o)
    return jnp.einsum("tnd,ndh->th", o, _w(p["o_proj"]))


# ------------------------------------------------------------ the KDA mixer


def conv4_silu(x, taps):
    """x [T, D]; taps [4, D], the last on the current token."""
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
    return 2.0 * beta if cfg["kda_allow_neg_eigval"] else beta


def gated_delta_rule(q, k, v, a, beta):
    """q, k, a [T, H, d]; v [T, H, dv]; beta [T, H] -> o [T, H, dv] and the
    last state [H, d, dv]. S_t = (I - beta k k^T) Diag(a) S + beta k v^T."""
    heads, d, dv = q.shape[1], q.shape[2], v.shape[2]

    def token(S, x):
        q, k, v, a, beta = x
        decayed = a[:, :, None] * S
        erased = decayed - beta[:, None, None] * jnp.einsum(
            "hi,hj,hjv->hiv", k, k, decayed)
        S = erased + beta[:, None, None] * jnp.einsum("hi,hv->hiv", k, v)
        return S, jnp.einsum("hiv,hi->hv", S, q)

    S, o = jax.lax.scan(token, jnp.zeros((heads, d, dv), F32), (q, k, v, a, beta))
    return o, S


def kda_operands(p, x, cfg):
    """(q, k, v, a, beta) of ``gated_delta_rule`` from the normed input."""
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    t = x.shape[0]
    branch = lambda n: conv4_silu(  # noqa: E731
        x @ _w(p[f"{n}_proj"]), p[f"{n}_conv"]).reshape(t, heads, d)
    q, k, v = unit(branch("q")) * d ** -0.5, unit(branch("k")), branch("v")
    f = (x @ _w(p["f_a_proj"])) @ _w(p["f_b_proj"]) + p["dt_bias"].astype(F32)
    rate = jnp.exp(p["A_log"].astype(F32))[None, :, None]
    a = jnp.exp(-rate * jax.nn.softplus(f).reshape(t, heads, d))
    return q, k, v, a, write_strength(p, x, cfg)


def kda(p, x, cfg):
    t = x.shape[0]
    o, _ = gated_delta_rule(*kda_operands(p, x, cfg))
    o = rms_norm(o, p["o_norm"]["scale"], cfg["rms_norm_eps"])
    gate = (x @ _w(p["g_a_proj"])) @ _w(p["g_b_proj"]) + p["g_b_proj"]["bias"].astype(F32)
    o = o * jax.nn.sigmoid(gate).reshape(o.shape)
    return o.reshape(t, -1) @ _w(p["o_proj"])


# ---------------------------------------------------------- the expert layer


def router_gates(p, x, cfg):
    """[T, E] gates over all the E experts the router scores (the published
    count: the router's own width): zero where an expert was not chosen."""
    s = jax.nn.sigmoid(x @ _w(p["router"]))
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(top)


def held_range(cfg: dict) -> tuple:
    """[first, past the last) of the router's experts that this rank holds."""
    first = cfg.get("expert_rank", 0) * cfg["n_routed_experts"]
    return first, first + cfg["n_routed_experts"]


def moe(p, x, cfg):
    gates = router_gates(p, x, cfg)
    first, past = held_range(cfg)
    out = jnp.zeros_like(x)
    for slot, expert in enumerate(range(first, past)):
        y = gated_mlp(x, p["w_gate"][slot], p["w_up"][slot], p["w_down"][slot])
        out = out + gates[:, expert, None] * y
    return out + shared_expert(p, x) if cfg["n_shared_experts"] else out


def shared_expert(p, x):
    s = p["shared"]
    return gated_mlp(x, s["gate_proj"]["kernel"], s["up_proj"]["kernel"],
                     s["down_proj"]["kernel"])


# ------------------------------------------------------------------ the model


def hidden_states(params, ids, cfg: dict):
    """ids [T] -> the final norm's input [T, hidden]."""
    p = params["params"]
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"]["embedding"].astype(F32)[ids]
    for i in range(cfg["num_hidden_layers"]):
        layer = p[f"layers_{i}"]
        h = rms_norm(x, layer["input_norm"]["scale"], eps)
        x = x + (gqa(layer["attn"], h, cfg) if is_gqa(cfg, i)
                 else kda(layer["kda"], h, cfg))
        h = rms_norm(x, layer["post_attn_norm"]["scale"], eps)
        if i < cfg["first_k_dense_replace"]:
            m = layer["mlp"]
            x = x + gated_mlp(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                              m["down_proj"]["kernel"])
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
