"""Plain reference of Xing4.0-29B-A4B's decoder (``model_type`` ``xing4_0``),
given one expert-parallel rank's share of it: the routed experts
``expert_rank * num_experts`` and the ``num_experts - 1`` that follow, of the
``n_routed_experts`` the router scores, and the first ``vocab_size`` token ids.

**The residual path** (manifold-constrained hyper-connections, mHC,
arXiv:2512.24880). A token carries n = ``hc_mult`` streams X [n, C]; the
embedding is copied to all of them, and their sum goes to the final norm
(``assumed``: the source gives neither). Around each sublayer F (an RMSNorm
and then the mixer, or an RMSNorm and then the FFN), with its own Phi, a, b:

    x~ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     all n C channels, no weight
    [h_pre | h_post | h_res] = x~ Phi                     Phi [n C, n + n + n n]
    H_pre  = sigmoid(a_pre h_pre + b_pre)                 [n]
    H_post = 2 sigmoid(a_post h_post + b_post)            [n]
    H_res  = SK(clip(a_res mat(h_res) + b_res, -30, 30))  [n, n], mat row-major
    SK(L): M = exp(L); ``hc_sinkhorn_iters`` times: every row over (its sum +
           ``hc_eps``), then every column over (its sum + ``hc_eps``)
    u = sum_i H_pre[i] X[i];  y = F(u);  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

**The mixer** (DeepSeek-V2's latent attention with a q latent), per token and
head, h = RMSNorm(u):

    c_q = RMSNorm(h W_qa)  [q_lora_rank];  q = c_q W_qb  [heads, nope + pe]
    [c | k_pe] = h W_kva   (kv_lora_rank | pe), k_pe one for all heads
    [k_nope | v] = RMSNorm(c) W_kvb a head;  k = [k_nope | k_pe]
    q_pe, k_pe <- rotated by position (YaRN's table, the sarvam reference's
    ``rotate``: ``rope_scaling`` of ``type`` "yarn" with DeepSeek's keys is
    the arithmetic of "deepseek_yarn"); q_nope, k_nope pass; no QK norm
    o = softmax(q k^T (nope + pe)^-1/2 (0.1 mscale_all_dim ln factor + 1)^2, causal) v
    out = o W_o

**The FFN**: a dense SwiGLU in the first ``first_k_dense_replace`` layers. In
the others s = sigmoid(h W_r) over all ``n_routed_experts``; the top
``num_experts_per_tok`` of s + bias are chosen (``noaux_tc``, one group); the
gates are s at the chosen, renormalised to sum to one, times
``routed_scaling_factor``; the held experts' part + one shared SwiGLU expert;
what the experts held elsewhere would add is left out, as in the program.

**The multi-token-prediction module** (DeepSeek-V3, arXiv:2412.19437 §2.2;
``num_nextn_predict_layers`` 1). With h_i the main model's hidden state at
position i before its final norm (the streams' sum) and t the tokens:

    h'_i = M [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))]       M [2 C, C] applied on the right
    one more expert layer (mixer + expert FFN, hyper-connected as above: h'
    copied to the streams, their sum taken after) at the same positions
    logits_i = RMSNorm(.) W_head                           predicts t_{i+2}

Embedding and head are the main model's. loss = CE_main + ``mtp_loss_weight``
CE_mtp, each the mean over the positions that have a target: every position
for the main head (the corpus gives each its next token), every position but
the last for the module.

``forward``, ``mtp_logits`` and ``loss`` take the system's parameter tree (flax
names) and the configuration file's own keys."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, rms_norm
from .kimi_linear_decoder import held_experts, routed
from .sarvam_mla_decoder import causal_attention, rotate, softmax_scale, swiglu

# Per-position error ||system - reference|| / ||reference|| over the held
# vocabulary on the last 256 positions of a 4,096-token sequence, as the
# other references have it. The readings it is set from are
# benchmarks/tools/reference_readings_of.py's with wrong_xing4.py and the
# cell's own runs', on the chip at the published widths (PERF.md, Findings,
# PR 39, has every number).
TOLERANCE = {"per_position_rel_err": 0.02, "min_share_within": 0.60}


def _w(p):
    return p["kernel"].astype(F32)


def _deepseek_keys(cfg):
    """The configuration as the sarvam reference's rotation reads it: YaRN of
    ``type`` "yarn" with DeepSeek's keys is its "deepseek_yarn"."""
    scaling = cfg.get("rope_scaling")
    if scaling is None or scaling["type"] != "yarn":
        return cfg
    return {**cfg, "rope_scaling": {**scaling, "type": "deepseek_yarn"}}


# ------------------------------------------------------- the residual path


def sinkhorn(logits, iters: int, eps: float):
    """logits [T, n, n] -> exp of them after ``iters`` rounds of rows, then
    columns, each over (its sum + eps)."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def connection_maps(p, streams, cfg):
    """H_pre [T, n], H_post [T, n], H_res [T, n, n] of streams [T, n, C]."""
    t, n, _ = streams.shape
    flat = streams.reshape(t, -1)
    flat = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    h = flat @ p["phi"].astype(F32)
    a = p["alpha"].astype(F32)
    pre = jax.nn.sigmoid(a[0] * h[:, :n] + p["b_pre"].astype(F32))
    post = 2.0 * jax.nn.sigmoid(a[1] * h[:, n:2 * n] + p["b_post"].astype(F32))
    logits = a[2] * h[:, 2 * n:].reshape(t, n, n) + p["b_res"].astype(F32)
    logits = jnp.clip(logits, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    return pre, post, sinkhorn(logits, cfg["hc_sinkhorn_iters"], cfg["hc_eps"])


def hyper_connected(p, streams, sublayer, cfg):
    pre, post, res = connection_maps(p, streams, cfg)
    y = sublayer(jnp.einsum("tn,tnc->tc", pre, streams))
    return jnp.einsum("tij,tjc->tic", res, streams) + post[:, :, None] * y[:, None, :]


# ------------------------------------------------------------- the sublayers


def q_latent(p, x, cfg):
    """c_q [T, q_lora_rank]: the down-projection, normed."""
    return rms_norm(x @ _w(p["q_a_proj"]), p["q_a_norm"]["scale"], cfg["rms_norm_eps"])


def qkv(p, x, cfg):
    """q, k [T, H, nope + pe], rotated, and v [T, H, dv]."""
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    pe, eps = cfg["qk_rope_head_dim"], cfg["rms_norm_eps"]
    turned = _deepseek_keys(cfg)
    q = jnp.einsum("tr,rnd->tnd", q_latent(p, x, cfg), _w(p["q_b_proj"]))
    latent = x @ _w(p["kv_a_proj"])
    c = rms_norm(latent[:, :rank], p["kv_a_norm"]["scale"], eps)
    kv = jnp.einsum("tr,rnd->tnd", c, _w(p["kv_b_proj"]))  # [T, H, nope + dv]
    k_pe = jnp.broadcast_to(latent[:, None, rank:], (*kv.shape[:2], pe))
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], turned)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], rotate(k_pe, turned)], axis=-1)
    return q, k, kv[..., nope:]


def mla(p, x, cfg):
    q, k, v = qkv(p, x, cfg)
    o = causal_attention(q, k, v, softmax_scale(_deepseek_keys(cfg)))
    return jnp.einsum("tnd,ndh->th", o, _w(p["o_proj"]))


def router_gates(p, x, cfg):
    """[T, E] gates over all the router's experts: zero where an expert was
    not chosen."""
    n, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ _w(p["router"]))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(F32), k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(idx, n, dtype=F32) * top[..., None], axis=1)


def moe(p, x, cfg):
    out = routed(p, x, cfg, router_gates(p, x, cfg), held_experts(cfg))
    return out + swiglu(p["shared"], x) if cfg["n_shared_experts"] else out


def decoder_layer(p, streams, cfg, dense: bool):
    eps = cfg["rms_norm_eps"]
    streams = hyper_connected(
        p["mixer_hc"], streams,
        lambda u: mla(p["mla"], rms_norm(u, p["input_norm"]["scale"], eps), cfg), cfg)
    ffn = (lambda h: swiglu(p["mlp"], h)) if dense else (lambda h: moe(p["moe"], h, cfg))
    return hyper_connected(
        p["ffn_hc"], streams,
        lambda u: ffn(rms_norm(u, p["post_attn_norm"]["scale"], eps)), cfg)


def through(x, layers, cfg):
    """x [T, C] copied to the streams, through ``layers`` (parameters, dense),
    and the streams' sum."""
    streams = jnp.broadcast_to(x[:, None, :], (x.shape[0], cfg["hc_mult"], x.shape[1]))
    for p, dense in layers:
        streams = decoder_layer(p, streams, cfg, dense)
    return streams.sum(axis=1)


# ------------------------------------------------------------------ the model


def hidden_states(params, ids, cfg: dict):
    """ids [T] -> the final norm's input [T, hidden]."""
    p = params["params"]
    x = p["embed_tokens"]["embedding"].astype(F32)[ids]
    return through(x, [
        (p[f"layers_{i}"], i < cfg["first_k_dense_replace"])
        for i in range(cfg["num_hidden_layers"])
    ], cfg)


def further_hidden_states(params, hidden, next_ids, cfg: dict):
    """The module's last hidden state [T, hidden] from the main model's
    ``hidden`` (before its final norm) and the token after each position."""
    p, eps = params["params"], cfg["rms_norm_eps"]
    joined = jnp.concatenate([
        rms_norm(hidden, p["mtp_hidden_norm"]["scale"], eps),
        rms_norm(p["embed_tokens"]["embedding"].astype(F32)[next_ids],
                 p["mtp_embed_norm"]["scale"], eps),
    ], axis=-1)
    return through(joined @ _w(p["mtp_proj"]), [(p["mtp_layer"], False)], cfg)


def _logits(params, x, norm: str, cfg):
    p = params["params"]
    x = rms_norm(x, p[norm]["scale"], cfg["rms_norm_eps"])
    return x @ p["lm_head"]["kernel"].astype(F32)


def forward(params, ids, cfg: dict, last: int):
    """Float32 logits [last, held vocabulary] of the main head at one
    sequence's last positions."""
    with jax.default_matmul_precision("highest"):
        return _logits(params, hidden_states(params, ids, cfg)[-last:], "final_norm", cfg)


def mtp_logits(params, ids, next_ids, cfg: dict, last: int):
    """Float32 logits [last, held vocabulary] of the module's pass of the
    head at the last positions: position i's are over the token after
    ``next_ids[i]``."""
    with jax.default_matmul_precision("highest"):
        further = further_hidden_states(
            params, hidden_states(params, ids, cfg), next_ids, cfg)
        return _logits(params, further[-last:], "mtp_norm", cfg)


def _cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def loss_terms(params, ids, targets, cfg: dict):
    """(the main head's mean cross-entropy, the module's) of one sequence;
    ``targets`` are the ids already shifted by one."""
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(params, ids, cfg)
        main = _cross_entropy(_logits(params, hidden, "final_norm", cfg), targets)
        further = further_hidden_states(params, hidden, targets, cfg)
        mtp = _cross_entropy(
            _logits(params, further[:-1], "mtp_norm", cfg), targets[1:])
        return main.mean(), mtp.mean()


def loss(params, ids, targets, cfg: dict):
    main, mtp = loss_terms(params, ids, targets, cfg)
    return main + cfg["mtp_loss_weight"] * mtp
