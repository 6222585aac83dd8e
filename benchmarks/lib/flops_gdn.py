"""Operations and bytes the scalar-decay gated delta rule needs
(``ray_tpu/ops/kda.py`` ``chunk_gdn``: Gated DeltaNet), from shapes, at the
published key and value head dims whatever lanes a kernel pads them to.

The recurrence a token is ``flops_kda.recurrence_per_token``'s at dk != dv.
``gdn_call`` is what one call of a scan kernel needs over every (batch, head,
chunk) of C tokens, by the chunked form's own mathematics: the operations
``flops_kda.kda_call`` counts for KDA (each entry of the chunk's two causal
[C, C] blocks once, the unit triangular system solved by substitution, the
masked half of every causal block counted for nothing) and bytes of its own.
The decay is one scalar a head and token: its products with the [C, C] blocks
and the rows are no matmuls and count for nothing."""
from __future__ import annotations

from .flops_kda import CHUNK, kda_call

# Each scan kernel beside KDA's of the same pass, whose products it shares.
GDN_KERNELS = {"_gdn_fwd_kernel": "_kda_fwd_kernel",
               "_gdn_bwd_kernel": "_kda_bwd_kernel"}


def gdn_call(kernel: str, bh: int, seq: int, dk: int, dv: int,
             itemsize: int = 2, chunk: int = CHUNK) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call over ``bh`` (batch x head) sequences of
    ``seq`` tokens.

    FLOPs are ``kda_call``'s of the same pass at these head dims: forward, a
    chunk, k k^T and q k^T, the causal half of each (C^2 dk each); W and U0
    from the unit triangular system (C^2 (dk + dv)); U = U0 - W S, O = Qg S,
    S' += Kd^T U (2 C dk dv each), O += Aqk U (C^2 dv) and the state's decay
    (dk dv); backward the forward again from the saved state and twice its
    FLOPs for the gradients.

    Bytes are this kernel's own. Forward it reads q, k, v and the output gate
    at two bytes an element (a floor: the kernels read q and k as the
    convolution leaves them, float32, and HBM pads 96 lanes to 128), g and
    beta at 4 bytes a head and token each, and writes O; the float32 state and
    the inverse of every chunk, which only the call under a gradient writes,
    are not counted (the two calls share the kernel's name, and a floor may
    not be too high). Backward it reads the forward's inputs, the saved
    float32 state and dO, and writes the cotangents of q, k, v, the gate, g
    and beta."""
    flops, _ = kda_call(GDN_KERNELS[kernel], bh, seq, dk, dv, itemsize, chunk)
    chunks = bh * (seq // chunk)
    inputs = chunk * ((2 * dk + 2 * dv) * itemsize + 4 + 4)
    if kernel == "_gdn_fwd_kernel":
        nbytes = inputs + chunk * dv * itemsize
    else:
        nbytes = 2 * inputs + dk * dv * 4 + chunk * dv * itemsize
    return flops, float(chunks * nbytes)
