"""Required FLOPs per token of Granite 4.0-H's decoder as one pipeline stage
holds it, and what one call of a Mamba-2 scan kernel needs, from the source's
own keys.

6 x the matmul parameters a token passes through: each kept ``mamba`` layer's
input projection (z and the convolved x at ``mamba_n_heads`` x
``mamba_d_head`` channels, B and C at ``mamba_d_state`` each, one step a head)
and its output projection; each kept ``attention`` layer's q and o at
``num_attention_heads``, k and v at ``num_key_value_heads``; every layer's
SwiGLU of ``shared_intermediate_size``; the tied head over the held
vocabulary, once; no embedding gather. Plus the causal attention of the
attention layers and the recurrence of the mamba layers at its chunk-free
count. The filter and its bias, the step's softplus, the gate and the norm are
no matmuls and count for nothing."""
from __future__ import annotations

SSD_KERNELS = ("_ssd_fwd_kernel", "_ssd_bwd_kernel")
CHUNK = 256


def layer_kinds(cfg: dict) -> list:
    """"mamba" or "attn" for each layer kept (the source counts them from 0)."""
    kinds = {"mamba": "mamba", "attention": "attn"}
    return [kinds[t] for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def mamba_channels(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def recurrence_per_token(n: int, p: int) -> float:
    """FLOPs of one head for one token, forward and backward: the state S in
    R^{P x N} is decayed (N P), written with (dl u) B^T (2 N P) and read with C
    (2 N P): 5 N P forward, and twice that again backward."""
    return 3.0 * 5 * n * p


def mamba_matmul_params(cfg: dict) -> int:
    h, inner = cfg["hidden_size"], mamba_channels(cfg)
    return h * (2 * inner + 2 * cfg["mamba_d_state"] + cfg["mamba_n_heads"]) + inner * h


def attention_matmul_params(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * head_dim(cfg) * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def granite_hybrid_decoder(cfg: dict, seq: int) -> float:
    kinds = layer_kinds(cfg)
    n_mamba = kinds.count("mamba")
    n_attn = len(kinds) - n_mamba
    h = cfg["hidden_size"]
    params = (
        n_mamba * mamba_matmul_params(cfg) + n_attn * attention_matmul_params(cfg)
        + len(kinds) * 3 * h * cfg["shared_intermediate_size"] + h * cfg["vocab_size"]
    )
    # Scores and weighted values, the causal half, forward and backward.
    attention = 6.0 * n_attn * seq * cfg["num_attention_heads"] * head_dim(cfg)
    recurrence = n_mamba * cfg["mamba_n_heads"] * recurrence_per_token(
        cfg["mamba_d_state"], cfg["mamba_d_head"])
    return 6.0 * params + attention + recurrence


def ssd_call(kernel: str, batch: int, seq: int, heads: int, p: int, n: int,
             itemsize: int = 2, chunk: int = CHUNK) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call of a scan kernel over ``batch``
    sequences of ``seq`` tokens and ``heads`` heads that share one B and C,
    whatever kernel design implements it, by the chunked form's own
    mathematics at chunks of C rows.

    Forward, a chunk: C B^T once for the group of all heads and not once a
    head, the causal half (C^2 N); a head's masked product with its inputs,
    the causal half (C^2 P), Y += (C S^T) and S' += X^T B (2 C N P each) and
    the state's decay (N P). It reads u at ``itemsize`` bytes, B and C once,
    the step at 4 bytes a head and token, and writes y; the float32 state of
    every chunk, which only the call under a gradient writes, is not counted
    (the two calls share the kernel's name, and a floor may not be too high).

    Backward, a chunk: the forward again from the saved state, and twice its
    FLOPs for the gradients. It reads the forward's inputs and dy, and writes
    the cotangents of u, B, C and the step."""
    if kernel not in SSD_KERNELS:
        raise KeyError(kernel)
    chunks = batch * (seq // chunk)
    forward = chunk * chunk * n + heads * (chunk * chunk * p + 4.0 * chunk * n * p + n * p)
    inputs = chunk * (heads * p * itemsize + 2 * n * itemsize + heads * 4)
    rows = chunk * heads * p * itemsize
    if kernel == "_ssd_fwd_kernel":
        flops, nbytes = forward, inputs + rows
    else:
        flops, nbytes = 3 * forward, 2 * inputs + rows
    return chunks * flops, float(chunks * nbytes)
