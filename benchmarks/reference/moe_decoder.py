"""Plain reference of a top-k mixture-of-experts decoder (Mixtral-8x7B's
layer): the dense decoder's attention, and in place of the MLP a router
(softmax over all experts, top-k, renormalised over the chosen) with one
SwiGLU expert per choice. No capacity, so no token is ever dropped.

Departure from the published model, mirrored from the program because the
parameter tree has no separate head: the output head is the embedding
table, transposed (published: untied)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, attention, gated_mlp, rms_norm

# As the dense reference, with one difference: top-k routing is not
# continuous. Where a token's k-th and (k+1)-th router probabilities lie
# closer than bfloat16's rounding of the layer input, system and reference
# send it to different experts and that position's logits differ by far more
# than rounding. With random weights that is under one token in a hundred
# over the cell's two layers, so a small share of positions may miss the
# tolerance; the others must meet it. On the chip (21 runs, PR 22) the median
# position read 0.0042 to 0.0045 and 99.1% to 99.9% of 4,096 positions lay
# within 0.02. The share asked for is that worst reading less a margin: a
# path that drops or misroutes 2% of the tokens is not correct, and with
# ``capacity_factor`` 4.0 the system may drop none.
TOLERANCE = {"per_position_rel_err": 0.03, "min_share_within": 0.985}


def moe(p, x, cfg):
    """x [T, hidden] -> [T, hidden]. Every expert sees every token and the
    gate, zero for the experts a token was not sent to, weights the sum."""
    n, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ p["router"]["kernel"].astype(F32), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / top.sum(axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, n, dtype=F32) * top[..., None], axis=1)
    out = jnp.zeros_like(x)
    for e in range(n):
        out = out + gates[:, e:e + 1] * gated_mlp(
            x, p["w_gate"][e], p["w_up"][e], p["w_down"][e]
        )
    return out


def forward(params, ids, cfg: dict, last: int):
    p = params["params"]
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        table = p["embed_tokens"]["embedding"].astype(F32)
        x = table[ids]
        for i in range(cfg["num_hidden_layers"]):
            layer = p[f"layers_{i}"]
            x = x + attention(
                layer["attn"], rms_norm(x, layer["input_norm"]["scale"], eps), cfg
            )
            x = x + moe(
                layer["moe"], rms_norm(x, layer["post_attn_norm"]["scale"], eps), cfg
            )
        x = rms_norm(x[-last:], p["final_norm"]["scale"], eps)
        return x @ table.T
