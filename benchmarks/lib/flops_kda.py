"""Operations and bytes the gated delta rule needs (``ray_tpu/ops/kda.py``),
from shapes.

``recurrence_per_token`` is what the mathematics requires of one head for one
token, chunked or not: the state S in R^{dk x dv} is decayed (dk dv), read
with k (2 dk dv), written with a rank-one update (2 dk dv) and read with q
(2 dk dv): 7 dk dv forward, and twice that again backward.

``kda_call`` is what one call of a kernel needs, over every (batch, head,
chunk) of C tokens, by the chunked form's own mathematics: each entry of the
chunk's two causal [C, C] blocks once (not once a level of the kernel's
safe-exponent scheme), the unit triangular system solved by substitution
(not inverted by doubling), the masked half of every causal block counted
for nothing, as in ``flops.flash_call``."""
from __future__ import annotations

KDA_KERNELS = ("_kda_fwd_kernel", "_kda_bwd_kernel")
CHUNK = 64


def recurrence_per_token(dk: int, dv: int) -> float:
    """FLOPs of one head for one token, forward and backward."""
    return 3.0 * 7 * dk * dv


def kda_call(kernel: str, bh: int, seq: int, dk: int, dv: int,
             itemsize: int = 2, chunk: int = CHUNK) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call over ``bh`` (batch x head) sequences
    of ``seq`` tokens.

    Forward, a chunk: A = k k^T and Aqk = q k^T with their decays, the causal
    half of each (C^2 dk each); W and U0 from the unit triangular system (C^2
    (dk + dv)); U = U0 - W S, O = Qg S, S' += Kd^T U (2 C dk dv each), O +=
    Aqk U (C^2 dv) and the state's decay (dk dv). It reads q, k, v, the
    float32 g and beta, and writes O; the float32 state of every chunk, which
    only the call under a gradient writes, is not counted (the two calls
    share the kernel's name, and a floor may not be too high).

    Backward, a chunk: the forward again from the saved state, and twice
    its FLOPs for the gradients. It reads the forward's inputs, the saved
    float32 state and dO, and writes the cotangents of q, k, v, g and
    beta."""
    if kernel not in KDA_KERNELS:
        raise KeyError(kernel)
    chunks = bh * (seq // chunk)
    forward = (chunk * chunk * (3.0 * dk + 2 * dv) + 6 * chunk * dk * dv
               + dk * dv)
    inputs = chunk * ((2 * dk + dv) * itemsize + dk * 4 + 4)
    if kernel == "_kda_fwd_kernel":
        flops, nbytes = forward, inputs + chunk * dv * itemsize
    else:
        flops = 3 * forward
        nbytes = 2 * inputs + dk * dv * 4 + chunk * dv * itemsize
    return chunks * flops, float(chunks * nbytes)
