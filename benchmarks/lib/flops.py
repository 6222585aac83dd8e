"""Operations and bytes the mathematics requires, computed from shapes.

"Required" means what the forward and backward passes of the published
architecture need for one token: recomputation, capacity padding, work
duplicated across chips and the masked half of causal attention count for
nothing. The functions take the configuration file's own keys (the source's
``config.json`` names), so they never read the program's classes.

A configuration names its function as ``benchmarks.lib.flops:<name>``; a new
architecture brings a file of its own. What one call of a kernel needs is
counted here and in ``flops_gmm.py``; which calls a configuration's step
holds, at which shapes, its ``kernels`` function says (``kernels_*.py``).
"""
from __future__ import annotations


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def attention_matmul_params(cfg: dict) -> int:
    """q, k, v and o projections of one layer."""
    h, d = cfg["hidden_size"], _head_dim(cfg)
    q = h * cfg["num_attention_heads"] * d
    kv = h * cfg["num_key_value_heads"] * d
    return 2 * q + 2 * kv


def causal_attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Scores and weighted values of every layer, forward and backward, for
    one token of a ``seq``-token sequence: 2 matmuls x 2 FLOPs x seq x
    (heads x head_dim), halved by the causal mask, times 3 for the backward
    pass: 6 x layers x seq x (heads x head_dim)."""
    width = cfg["num_attention_heads"] * _head_dim(cfg)
    return 6.0 * cfg["num_hidden_layers"] * seq * width


def dense_decoder(cfg: dict, seq: int) -> float:
    """Required FLOPs per token of a dense pre-norm decoder with a gated
    MLP: 6 x the matmul parameters a token passes through (the output head
    included, the embedding gather excluded) + causal attention."""
    mlp = 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    body = cfg["num_hidden_layers"] * (attention_matmul_params(cfg) + mlp)
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return 6.0 * (body + head) + causal_attention_flops_per_token(cfg, seq)


def moe_decoder(cfg: dict, seq: int) -> float:
    """As ``dense_decoder`` with a top-k expert layer in place of the MLP:
    the router and the ``num_experts_per_tok`` experts a token is sent to."""
    h = cfg["hidden_size"]
    experts = cfg["num_experts_per_tok"] * 3 * h * cfg["intermediate_size"]
    router = h * cfg["num_local_experts"]
    body = cfg["num_hidden_layers"] * (
        attention_matmul_params(cfg) + router + experts
    )
    head = h * cfg["vocab_size"]
    return 6.0 * (body + head) + causal_attention_flops_per_token(cfg, seq)


# Matmuls of [T, T] extent in one flash-attention kernel call, as (those that
# contract or produce q/k's head dim, those of v's): the forward computes
# scores and weighted values; the dk/dv kernel recomputes scores and computes
# dK, and dP and dV; the dq kernel recomputes scores and computes dQ, and dP.
FLASH_MATMULS = {"_fwd_kernel": (1, 1), "_bwd_dkv_kernel": (2, 2),
                 "_bwd_dq_kernel": (2, 1)}


def flash_call(kernel: str, bh: int, tq: int, tk: int, d: int,
               causal: bool, itemsize: int = 2,
               d_v: int | None = None) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one call of a flash kernel needs: its [tq, tk]
    matmuls, the masked half of a causal block not counted; every operand
    and result moved once. ``d`` is q's and k's head dim and ``d_v`` that of
    v, o and do, ``d`` where it is not given."""
    d_v = d if d_v is None else d_v
    pairs = tq * tk / 2 if causal else tq * tk
    n_qk, n_v = FLASH_MATMULS[kernel]
    flops = 2.0 * bh * pairs * (n_qk * d + n_v * d_v)
    qk_rows, v_rows = {
        # q, k, v in; o out (+ f32 lse)
        "_fwd_kernel": (tq + tk, tk + tq),
        # q, k, v, do in; dk, dv out
        "_bwd_dkv_kernel": (tq + 2 * tk, 2 * tk + tq),
        # q, k, v, do in; dq out
        "_bwd_dq_kernel": (2 * tq + tk, tk + tq),
    }[kernel]
    return flops, float(
        bh * (qk_rows * d + v_rows * d_v) * itemsize + bh * tq * 4
    )
