"""Operations and bytes one call of the Pallas grouped matmul needs
(``ray_tpu/ops/gmm.py``), from shapes."""
from __future__ import annotations

GMM_KERNELS = ("_gmm_kernel", "_tgmm_kernel")


def gmm_call(kernel: str, pairs: int, k: int, n: int, experts: int,
             itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call over ``pairs`` rows that hold a
    (token, expert) pair; the rows that pad an expert's segment to whole
    tiles count for nothing. ``_gmm_kernel`` is rows [pairs, k] times each
    row's expert matrix [k, n]; ``_tgmm_kernel`` is the weights' gradient,
    per expert rows^T [k, pairs_e] times their cotangent [pairs_e, n]. Both
    are 2 * pairs * k * n FLOPs and move the two [pairs, .] arrays and every
    expert's [k, n] block once."""
    if kernel not in GMM_KERNELS:
        raise KeyError(kernel)
    flops = 2.0 * pairs * k * n
    return flops, float((pairs * (k + n) + experts * k * n) * itemsize)
