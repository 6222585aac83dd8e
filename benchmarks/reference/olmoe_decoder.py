"""Plain reference of OLMoE-1B-7B's decoder (allenai, ``OlmoeForCausalLM``):
pre-norm layers of causal multi-head attention with QK-norm and a top-k
mixture of SwiGLU experts, an untied output head.

As the published modelling code has it: ``q_norm`` and ``k_norm`` are
RMSNorms over the whole q and the whole k projection (all heads at once),
applied before the split into heads and before rope; the router is a softmax
over all experts, the top-k probabilities are the gates as they are
(``norm_topk_prob`` false: they do not sum to one), and every expert's output
is weighted by its gate. No capacity, no sort, no kernel: every expert sees
every token and a zero gate removes it. ``loss`` adds the published
``load_balancing_loss_func`` at ``router_aux_loss_coef``.

``forward`` and ``loss`` take the system's parameter tree (flax names) and
the configuration file's own keys."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, causal_gqa, gated_mlp, rms_norm, rotary

# Per-position error ||system - reference|| / ||reference|| over the
# vocabulary, as the other references have it. The readings are
# benchmarks/tools/reference_readings.py's, on the chip at the published
# widths with the configuration's own draw (every matrix normal(0, 0.02)):
# twelve seeds, 4,096 positions each (PERF.md, Findings, PR 26).
#
# Top-k routing is not continuous, and here a flip falls on the 8th of 64
# gates, which is small and not renormalised: a flipped position moves by
# 0.01 to 0.03, not by Mixtral's 0.1 to 0.8, and one position in ten flips
# in some layer. So the positions lie in two heaps: 82.8% to 91.4% within
# 0.005 (median 0.0041 to 0.0045), the flipped ones up to 0.023 to 0.032.
# Within 0.01 lay 88.7% to 93.0%; the share asked for is that worst reading
# less a margin (1.3 times its misses).
#
# What it refuses, same seeds: the reference in the nearest precision below
# the configuration's bfloat16, weights and every norm's output rounded to
# float8 e4m3: median 0.099 to 0.108, no position within 0.05. The expert
# matrices alone in e4m3: 5.2% to 32.3% within 0.01. A dispatch that drops
# pairs (capacity at factor 1.25): 13.8% to 14.9%. Renormalised gates: none.
TOLERANCE = {"per_position_rel_err": 0.01, "min_share_within": 0.85}


def attention(p, x, cfg):
    """x [T, hidden] -> [T, hidden]; q_proj [hidden, heads, d], k_proj and
    v_proj [hidden, kv_heads, d], o_proj [heads, d, hidden], q_norm and
    k_norm scales of heads * d and kv_heads * d."""
    t = x.shape[0]
    eps = cfg["rms_norm_eps"]
    w = {name: p[name]["kernel"].astype(F32)
         for name in ("q_proj", "k_proj", "v_proj", "o_proj")}
    q = jnp.einsum("th,hnd->tnd", x, w["q_proj"])
    k = jnp.einsum("th,hnd->tnd", x, w["k_proj"])
    v = jnp.einsum("th,hnd->tnd", x, w["v_proj"])
    q = rms_norm(q.reshape(t, -1), p["q_norm"]["scale"], eps).reshape(q.shape)
    k = rms_norm(k.reshape(t, -1), p["k_norm"]["scale"], eps).reshape(k.shape)
    q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    return jnp.einsum("tnd,ndh->th", causal_gqa(q, k, v), w["o_proj"])


def moe(p, x, cfg):
    """x [T, hidden] -> ([T, hidden], the layer's load-balancing term
    E * sum_e f_e * p_e: f_e the share of tokens that chose expert e among
    their top k, p_e the mean router probability of e)."""
    n, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ p["router"]["kernel"].astype(F32), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(idx, n, dtype=F32)  # [T, k, E]
    gates = jnp.sum(chosen * top[..., None], axis=1)  # [T, E]

    def add_expert(out, expert):
        w_gate, w_up, w_down, gate = expert
        return out + gate[:, None] * gated_mlp(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (p["w_gate"], p["w_up"], p["w_down"], gates.T),
    )
    balance = n * jnp.sum(chosen.sum(1).mean(0) * probs.mean(0))
    return out, balance


def hidden_states(params, ids, cfg: dict):
    """ids [T] -> (final-norm input [T, hidden], mean over layers of the
    load-balancing term)."""
    p = params["params"]
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"]["embedding"].astype(F32)[ids]
    balance = 0.0
    for i in range(cfg["num_hidden_layers"]):
        layer = p[f"layers_{i}"]
        x = x + attention(
            layer["attn"], rms_norm(x, layer["input_norm"]["scale"], eps), cfg
        )
        out, term = moe(
            layer["moe"], rms_norm(x, layer["post_attn_norm"]["scale"], eps), cfg
        )
        x, balance = x + out, balance + term
    return x, balance / cfg["num_hidden_layers"]


def _logits(params, x, cfg):
    p = params["params"]
    x = rms_norm(x, p["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x @ p["lm_head"]["kernel"].astype(F32)


def forward(params, ids, cfg: dict, last: int):
    """Float32 logits [last, vocab] of one sequence's last positions."""
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(params, ids, cfg)
        return _logits(params, x[-last:], cfg)


def loss(params, ids, targets, cfg: dict):
    """Mean next-token cross-entropy of one sequence (``targets`` are the
    ids already shifted) + router_aux_loss_coef x the load-balancing term."""
    with jax.default_matmul_precision("highest"):
        x, balance = hidden_states(params, ids, cfg)
        logp = jax.nn.log_softmax(_logits(params, x, cfg), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return nll.mean() + cfg["router_aux_loss_coef"] * balance
