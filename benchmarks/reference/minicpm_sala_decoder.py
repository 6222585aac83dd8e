"""Plain reference of MiniCPM-SALA's decoder (``model_type`` ``minicpm_sala``:
block-sparse top-k softmax layers, InfLLM-V2, among decay-only Lightning
linear-attention layers, every layer over a dense SwiGLU, under MiniCPM's muP
scaling), given the first ``vocab_size`` token ids of its vocabulary.

Body (L = ``num_hidden_layers_published`` layers, not the layers held; eps
``rms_norm_eps``; no bias anywhere):

    h_0    = scale_emb * E[ids]
    h      = x + (scale_depth / sqrt(L)) * mixer(RMSNorm(x))
    out    = h + (scale_depth / sqrt(L)) * SwiGLU(RMSNorm(h))
    logits = W_head (RMSNorm(out_last) / (hidden_size / dim_model_base))

Layer l, counted from 0, is what ``mixer_types[l]`` says.

``lightning-attn`` (H = ``lightning_nh`` heads of d = ``lightning_head_dim``;
a float32 state S in R^{d x d} a head, zero before token 0), token by token
in a ``lax.scan``, no chunks and no kernel:

    q, k, v = x W_q, x W_k, x W_v                      [T, H, d]
    q, k    = RMSNorm_d(q; w_q), RMSNorm_d(k; w_k)     over a head's channels
    q, k    = rot(q), rot(k)                           the plain table, whole head
    S_t     = exp(-s_h) S_{t-1} + k_t v_t^T
    o_t     = d^-1/2 S_t^T q_t
    y_t     = RMSNorm_d(o_t; w_o) * sigmoid(x_t W_g)
    mixer(x) = concat_h(y) W_o
    s_h     = 2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)

``minicpm4`` (H = ``num_attention_heads`` over ``num_key_value_heads`` K/V
heads, groups of H / kv; d = ``head_dim``; nothing turns unless
``attn_use_rope``), with ``sparse_config``'s sizes:

    q = RMSNorm_d(x W_q), k = RMSNorm_d(x W_k), v = x W_v
    T <= dense_len:  o = softmax_{j <= i}(q k^T d^-1/2) v
    T >  dense_len:  a row i and K/V group g attend the key blocks B(i, g):
      Kc_m       = mean(k_g[stride m : stride m + kernel_size])
      p(i, h, m) = softmax over {m : stride m + kernel_size <= i + 1} of q_{i,h} . Kc_m d^-1/2
      P(i, g, m) = sum over the heads h of group g of p(i, h, m)
      R(i, g, b) = max of P(i, g, m) over the compressed keys m that overlap block b
      forced     : the first init_blocks blocks, and the window_size / block_size
                   blocks that end with the row's own
      B(i, g)    = the forced blocks and the highest R among the other visible
                   ones, topk blocks in all, ties to the lower index (every
                   visible block where fewer than topk are visible)
      o_{i,h}    = softmax over {j <= i, block(j) in B(i, g(h))} of q_{i,h} . k_j d^-1/2, times v_j
    y = o * sigmoid(x W_g);  mixer(x) = concat_h(y) W_o

B is an integer set: no gradient passes through it. The selection is made a
block of rows at a time against every compressed key, the attention a block of
rows at a time against every key, each row's softmax whole; nothing here is a
kernel, a chunk or a cache.

Assumed, as the configuration's file lists with the reasons: every size of the
selection (MiniCPM4's ``sparse_config``), the forced window as whole blocks,
the ties, the slopes (``fla.layers.lightning_attn``), the norms over a head's
channels with one weight [d], the gates as sigmoids of full-rank projections.
Departure from the published model, here as in the program: logits and loss
are over the held slice of the vocabulary.

``forward`` and ``loss`` take the system's parameter tree (flax names) and the
configuration file's own keys."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, Q_BLOCK, causal_gqa, gated_mlp, rms_norm, rotary

# Per-position error ||system - reference|| / ||reference|| over the held
# vocabulary, as the other references have it, on the last 256 positions of a
# 16,384-token sequence. The readings are benchmarks/tools/
# reference_readings_of.py's (wrong_minicpm_sala.py) and the cell's own runs',
# on the chip at the published widths (PERF.md, Findings, PR 54).
#
# The system's positions lie in one heap, bfloat16's noise through four
# pre-norm layers whose sublayers are scaled by 0.2475 before they are added:
# median 0.0116 to 0.0120 on every seed, p90 0.0133 to 0.0135, every position
# within 0.02 on the two reading seeds and within 0.0224 on the worst of the
# cell's own runs. per_position_rel_err 0.03 stands at 2.5 times the median
# and a third over the worst position seen; the share asked for lies between
# the system's (1.0 on every seed) and every wrong reading that has to fail
# (none above 0.004 within 0.03).
#
# What it refuses, positions within 0.03: the reference in the nearest
# precision below the configuration's bfloat16 (weights and every norm's
# output rounded to float8 e4m3): none, median 0.178 to 0.179. The sparse
# layer's q and k rotated: none, median 0.29 to 0.30; the Lightning layers'
# left unrotated: none, 0.34; the residual scale of the 4 layers held: none,
# 0.71 to 0.72; logits not divided by 16: none, 15.0. A block's score the mean
# of its compressed keys' and not the largest: none and 0.4% of two seeds'
# positions, median 0.037; each head choosing by its own scores: none, 0.059
# to 0.061.
#
# What it does not refuse, and which CPU test does (tests/
# test_minicpm_sala_model.py, float32, test_a_wrong_program_or_reference_is_
# refused and test_the_chosen_sets_are_the_references_to_the_index): topk - 1
# reads median 0.0136, a window one block shorter 0.0146 to 0.0149, no initial
# block 0.0146 to 0.0150, the slopes without the layer's factor 0.0132: each
# stands apart from the system's 0.0116 to 0.0118 by a fifth of it, inside
# the heap's own width, one sparse layer of four under a residual scale of a
# quarter. The reference with the recurrence's state rounded to bfloat16 after
# every token reads 0.0059 to 0.0064, nearer the float32 reference than the
# bfloat16 program is (as in the delta-rule cells: tests/test_kda_op.py follows
# the kernels' float32 state against the recurrence); with the selection's
# probabilities rounded to bfloat16, 0.0130 and 98 to 99% within 0.02 (the
# named test sees the sets move; on the chip the bfloat16 program's sets equal
# the float32 reference's in 93% of (row, group) pairs and differ by two
# blocks, one out and one in, where they differ).
TOLERANCE = {"per_position_rel_err": 0.03, "min_share_within": 0.9}

# Rows a block of the selection: [kv, group, rows, compressed keys] float32
# scores and the [kv, rows, compressed keys, blocks] pooling stay near 0.5 GB
# at 16,384 tokens.
SELECT_ROWS = 64


def _w(p):
    return p["kernel"].astype(F32)


def is_sparse(cfg: dict, layer: int) -> bool:
    return cfg["mixer_types"][layer] == "minicpm4"


def published_layers(cfg: dict) -> int:
    return cfg.get("num_hidden_layers_published", cfg["num_hidden_layers"])


def residual_scale(cfg: dict) -> float:
    return cfg["scale_depth"] / math.sqrt(published_layers(cfg))


def logit_divisor(cfg: dict) -> float:
    return cfg["hidden_size"] / cfg["dim_model_base"]


def head_normed(p, q, k, eps):
    """q and k [T, heads, d] through an RMSNorm over a head's channels."""
    return (rms_norm(q, p["q_norm"]["scale"], eps),
            rms_norm(k, p["k_norm"]["scale"], eps))


# --------------------------------------------------------- the Lightning mixer


def lightning_slopes(cfg: dict, layer: int):
    """s_h [H] of published layer ``layer``."""
    heads = cfg["lightning_nh"]
    base = 2.0 ** (-8.0 * (jnp.arange(heads, dtype=F32) + 1.0) / heads)
    return base * (1.0 - layer / (published_layers(cfg) - 1) + 1e-5)


def decayed_sum(q, k, v, slopes):
    """q, k, v [T, H, d]; slopes [H] -> o [T, H, d] = S_t^T q_t, S_t =
    exp(-s) S_{t-1} + k_t v_t^T."""
    heads, d = q.shape[1], q.shape[2]
    keep = jnp.exp(-slopes)[:, None, None]

    def token(S, x):
        q, k, v = x
        S = state(keep * S + jnp.einsum("hi,hv->hiv", k, v))
        return S, jnp.einsum("hiv,hi->hv", S, q)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, v.shape[2]), F32), (q, k, v))
    return o


def state(S):
    """The recurrence's state as it is carried: float32, as it is."""
    return S


def lightning(p, x, cfg, layer):
    heads, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    t = x.shape[0]
    q, k, v = ((x @ _w(p[f"{n}_proj"])).reshape(t, heads, d) for n in "qkv")
    q, k = head_normed(p, q, k, cfg["rms_norm_eps"])
    if cfg["lightning_use_rope"]:
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    o = decayed_sum(q, k, v, lightning_slopes(cfg, layer)) * d ** -0.5
    o = rms_norm(o, p["o_norm"]["scale"], cfg["rms_norm_eps"])
    o = o * jax.nn.sigmoid(x @ _w(p["g_proj"])).reshape(o.shape)
    return o.reshape(t, -1) @ _w(p["o_proj"])


# ------------------------------------------------------------ the sparse mixer


def pool(scores, overlaps):
    """scores [.., m] of the compressed keys and overlaps [m, blocks] bool ->
    [.., blocks]: the largest score among the compressed keys that overlap a
    block."""
    return jnp.where(overlaps, scores[..., None], 0.0).max(-2)


def over_group(p):
    """p [kv, group, rows, m], every head's scores -> [kv, 1, rows, m]: the
    sum over a group's heads, which choose together."""
    return p.sum(1, keepdims=True)


def forced_blocks(own, blocks, sel):
    """own [rows, 1] the rows' own blocks; blocks [1, n] -> [rows, n] bool:
    the blocks every row takes whatever their scores."""
    return (blocks < sel["init_blocks"]) | (
        blocks > own - sel["window_size"] // sel["block_size"])


def block_scores(q, k, sel, start, rows):
    """R [kv, 1, rows, blocks] of rows start .. start + rows, from q [T,
    heads, d] and k [T, kv, d]."""
    t, heads, d = q.shape
    kv = k.shape[1]
    size, stride, block = sel["kernel_size"], sel["kernel_stride"], sel["block_size"]
    m = (t - size) // stride + 1
    first = jnp.arange(m) * stride
    kc = k[first[:, None] + jnp.arange(size)].mean(1)  # [m, kv, d]
    qb = jax.lax.dynamic_slice_in_dim(q, start, rows).reshape(rows, kv, heads // kv, d)
    s = jnp.einsum("qhgd,mhd->hgqm", qb, kc) / jnp.sqrt(F32(d))
    at = start + jnp.arange(rows)
    seen = (first + size)[None, :] <= at[:, None] + 1  # [rows, m]
    s = jnp.where(seen, s, -1e30)  # a row that sees none: every e is 0
    e = jnp.exp(s - jnp.max(s, -1, keepdims=True)) * seen
    p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    edges = jnp.arange(-(-t // block)) * block
    overlaps = (first[:, None] < edges[None, :] + block) & (
        first[:, None] + size > edges[None, :])  # [m, blocks]
    return pool(over_group(p), overlaps)


def chosen_blocks(q, k, sel):
    """B [T, kv, 1, blocks] bool (per head, [T, kv, group, blocks], under a
    wrong ``over_group``). No gradient passes through it."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    t, block = q.shape[0], sel["block_size"]
    rows = math.gcd(SELECT_ROWS, t)
    blocks = jnp.arange(-(-t // block))[None, :]

    def some_rows(start):
        score = block_scores(q, k, sel, start, rows)
        own = ((start + jnp.arange(rows)) // block)[:, None]
        visible = blocks <= own
        score = jnp.where(forced_blocks(own, blocks, sel), jnp.inf, score)
        score = jnp.where(visible, score, -jnp.inf)
        # A block's rank among its row's, the higher score first and of equal
        # scores the lower index (a stable sort).
        rank = jnp.argsort(jnp.argsort(-score, axis=-1), axis=-1)
        return jnp.moveaxis((rank < sel["topk"]) & visible, 2, 0)  # [rows, kv, ., blocks]

    chosen = jax.lax.map(some_rows, jnp.arange(t // rows) * rows)
    return chosen.reshape(t, *chosen.shape[2:])


def block_sparse_gqa(q, k, v, chosen, block):
    """q [T, heads, d]; k, v [T, kv, d]; chosen [T, kv, 1 or group, blocks]
    -> [T, heads, d]: each row's softmax over the keys up to its own that lie
    in its chosen blocks, Q_BLOCK rows at a time against every key."""
    t, heads, d = q.shape
    kv = k.shape[1]
    rows = math.gcd(Q_BLOCK, t)
    qg = q.reshape(t // rows, rows, kv, heads // kv, d)
    cg = chosen.reshape(t // rows, rows, *chosen.shape[1:])
    key_pos = jnp.arange(t)

    def one_block(args):
        qb, cb, start = args
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / jnp.sqrt(F32(d))
        causal = key_pos[None, :] <= (start + jnp.arange(rows))[:, None]
        seen = jnp.moveaxis(cb, 0, 2)[..., key_pos // block] & causal  # [kv, ., rows, T]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one_block, (qg, cg, jnp.arange(t // rows) * rows))
    return out.reshape(t, heads, d)


def sparse_qkv(p, x, cfg):
    """q [T, heads, d], k and v [T, kv, d] as the attention takes them, from
    the layer's normed input: q and k normed a head, turned under
    ``attn_use_rope``."""
    q = jnp.einsum("th,hnd->tnd", x, _w(p["q_proj"]))
    k = jnp.einsum("th,hnd->tnd", x, _w(p["k_proj"]))
    v = jnp.einsum("th,hnd->tnd", x, _w(p["v_proj"]))
    q, k = head_normed(p, q, k, cfg["rms_norm_eps"])
    if cfg["attn_use_rope"]:
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    return q, k, v


def sparse_attention(p, x, cfg):
    t, sel = x.shape[0], cfg["sparse_config"]
    q, k, v = sparse_qkv(p, x, cfg)
    if t <= sel["dense_len"]:
        o = causal_gqa(q, k, v)
    else:
        o = block_sparse_gqa(q, k, v, chosen_blocks(q, k, sel), sel["block_size"])
    o = o * jax.nn.sigmoid(jnp.einsum("th,hnd->tnd", x, _w(p["g_proj"])))
    return jnp.einsum("tnd,ndh->th", o, _w(p["o_proj"]))


# ------------------------------------------------------------------ the model


def decoder_layer(layer, x, cfg, i):
    """One pre-norm layer on x [T, hidden], each sublayer's output scaled."""
    eps, scale = cfg["rms_norm_eps"], residual_scale(cfg)
    fed = rms_norm(x, layer["input_norm"]["scale"], eps)
    mixed = (sparse_attention(layer["sparse"], fed, cfg) if is_sparse(cfg, i)
             else lightning(layer["lightning"], fed, cfg, i))
    h = x + scale * mixed
    m = layer["mlp"]
    return h + scale * gated_mlp(
        rms_norm(h, layer["post_attn_norm"]["scale"], eps),
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"], m["down_proj"]["kernel"])


def hidden_states(params, ids, cfg: dict):
    """ids [T] -> the final norm's input [T, hidden]."""
    p = params["params"]
    x = cfg["scale_emb"] * p["embed_tokens"]["embedding"].astype(F32)[ids]
    for i in range(cfg["num_hidden_layers"]):
        x = decoder_layer(p[f"layers_{i}"], x, cfg, i)
    return x


def _logits(params, x, cfg):
    p = params["params"]
    x = rms_norm(x, p["final_norm"]["scale"], cfg["rms_norm_eps"])
    return (x / logit_divisor(cfg)) @ p["lm_head"]["kernel"].astype(F32)


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
