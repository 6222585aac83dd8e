"""Required FLOPs per token of MiniCPM-SALA's decoder as one pipeline stage
holds it, and what one call of each of its kernels needs, from the source's
own keys.

6 x the matmul parameters a token passes through: each kept ``minicpm4``
layer's q, output gate and o at ``num_attention_heads``, k and v at
``num_key_value_heads``; each kept ``lightning-attn`` layer's q, k, v, gate
and o at ``lightning_nh``; every layer's SwiGLU of ``intermediate_size``; the
head over the held vocabulary; no embedding gather. Plus the sparse layers'
attention at the (row, key) pairs a row attends and no other: past
``dense_len`` a row takes ``topk`` blocks of ``block_size`` keys, or every
block up to its own where there are fewer, and of its own block the keys up
to itself, so the count is a function of the length and the two sizes and not
of the data (the forced window and the initial blocks are among the ``topk``);
the selection's products of every head's row with the compressed keys it
sees, forward only (nothing is differentiated through it); and the Lightning
layers' recurrence at its chunk-free count. The norms, the rotation, the
soft-max of the selection, its pooling and its top-k are no matmuls and count
for nothing."""
from __future__ import annotations

import numpy as np

from .flops import FLASH_MATMULS

# Each kernel beside the causal one of the same pass, whose matmuls it has.
SPARSE_KERNELS = {"_sparse_fwd_kernel": "_fwd_kernel",
                  "_bwd_dkv_sparse_kernel": "_bwd_dkv_kernel",
                  "_bwd_dq_sparse_kernel": "_bwd_dq_kernel"}
LIGHTNING_KERNELS = ("_lightning_fwd_kernel", "_lightning_bwd_kernel")


def layer_kinds(cfg: dict) -> list:
    """"sparse" or "lightning" for each layer kept (counted from 0)."""
    kinds = {"minicpm4": "sparse", "lightning-attn": "lightning"}
    return [kinds[t] for t in cfg["mixer_types"][:cfg["num_hidden_layers"]]]


def is_dense(cfg: dict, seq: int) -> bool:
    """At or under ``dense_len`` a ``minicpm4`` layer is causal attention."""
    return seq <= cfg["sparse_config"]["dense_len"]


def attended_pairs(seq: int, topk: int, block: int) -> int:
    """(row, key) pairs of one head over a sequence: row i attends min(i //
    block + 1, topk) blocks, its own up to itself and the others whole."""
    i = np.arange(seq, dtype=np.int64)
    blocks = np.minimum(i // block + 1, topk)
    return int(((blocks - 1) * block + i % block + 1).sum())


def scored_pairs(seq: int, kernel_size: int, kernel_stride: int) -> int:
    """(row, compressed key) pairs of one head: row i scores the compressed
    keys that end at or before it."""
    i = np.arange(seq, dtype=np.int64)
    return int(np.maximum((i + 1 - kernel_size) // kernel_stride + 1, 0).sum())


def lightning_recurrence_per_token(dk: int, dv: int) -> float:
    """FLOPs of one head for one token, forward and backward: the state S in
    R^{dk x dv} is decayed (dk dv), written with k v^T (2 dk dv) and read with
    q (2 dk dv): 5 dk dv forward, and twice that again backward."""
    return 3.0 * 5 * dk * dv


def sparse_matmul_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return h * d * (3 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def lightning_matmul_params(cfg: dict) -> int:
    return 5 * cfg["hidden_size"] * cfg["lightning_nh"] * cfg["lightning_head_dim"]


def minicpm_sala_decoder(cfg: dict, seq: int) -> float:
    kinds = layer_kinds(cfg)
    n_sparse = kinds.count("sparse")
    n_lightning = len(kinds) - n_sparse
    h, sel = cfg["hidden_size"], cfg["sparse_config"]
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    params = (
        n_sparse * sparse_matmul_params(cfg)
        + n_lightning * lightning_matmul_params(cfg)
        + len(kinds) * 3 * h * cfg["intermediate_size"] + h * cfg["vocab_size"]
    )
    if is_dense(cfg, seq):
        pairs, scored = seq * seq / 2, 0
    else:
        pairs = attended_pairs(seq, sel["topk"], sel["block_size"])
        scored = scored_pairs(seq, sel["kernel_size"], sel["kernel_stride"])
    # Scores and weighted values, forward and backward: 2 matmuls x 2 FLOPs x
    # 3 a pair and channel; the selection's scores 2 FLOPs a pair and channel.
    attention = n_sparse * heads * d * (12.0 * pairs + 2.0 * scored) / seq
    recurrence = n_lightning * cfg["lightning_nh"] * lightning_recurrence_per_token(
        cfg["lightning_head_dim"], cfg["lightning_head_dim"])
    return 6.0 * params + attention + recurrence


def sparse_call(kernel: str, bh: int, bkv: int, seq: int, topk: int,
                block: int, d: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one call of a sparse kernel needs, whatever design
    implements it: the matmuls of the causal kernel of the same pass
    (``FLASH_MATMULS``) over the attended (row, key) pairs and no other;
    every operand and result moved once (q, o, do and dq at ``bh`` = batch x
    heads, K, V, dK and dV at their own ``bkv`` = batch x K/V heads, the
    float32 log-sum-exp written by the forward, it and the rows' delta read
    by each backward kernel, and the chosen blocks a bit a row, group and
    block)."""
    n_qk, n_v = FLASH_MATMULS[SPARSE_KERNELS[kernel]]
    flops = 2.0 * bh * attended_pairs(seq, topk, block) * (n_qk + n_v) * d
    q_rows, kv_rows, f32_rows = {
        "_sparse_fwd_kernel": (2, 2, 1),      # q in, o out; k, v in; lse out
        "_bwd_dkv_sparse_kernel": (2, 4, 2),  # q, do in; k, v in, dk, dv out
        "_bwd_dq_sparse_kernel": (3, 2, 2),   # q, do in, dq out; k, v in
    }[kernel]
    bits = bkv * seq * -(-seq // block) / 8
    return flops, float(
        (bh * q_rows + bkv * kv_rows) * seq * d * itemsize
        + bh * f32_rows * seq * 4 + bits
    )


def lightning_call(kernel: str, bh: int, seq: int, dk: int, dv: int,
                   itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call of a Lightning scan kernel over ``bh``
    (batch x head) sequences, whatever chunk it works in: the recurrence's own
    5 dk dv a token forward; backward the forward again from a saved state and
    twice its FLOPs for the gradients. Forward it reads q, k, v and the output
    gate and writes o; backward it reads those and dO and writes the
    cotangents of q, k, v and the gate. The float32 states between the two,
    whose number follows the chunk, are not counted (a floor may not be too
    high)."""
    if kernel not in LIGHTNING_KERNELS:
        raise KeyError(kernel)
    forward = 5.0 * dk * dv
    rows = (2 * dk + 2 * dv) * itemsize
    if kernel == "_lightning_fwd_kernel":
        flops, nbytes = forward, rows + dv * itemsize
    else:
        flops, nbytes = 3 * forward, 2 * rows + dv * itemsize
    return bh * seq * flops, float(bh * seq * nbytes)
