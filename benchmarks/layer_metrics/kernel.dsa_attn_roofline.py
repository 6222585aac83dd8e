"""The least time the chip could take for the calls it executed of the flash
kernels under the chosen keys' mask (_fwd_select_kernel,
_bwd_dkv_select_kernel, _bwd_dq_select_kernel), over the time they took on
device 0.

Each call is counted at what the configuration's ``kernels`` function states
(``benchmarks/lib/kernels_dots3.py``, through ``lib/flops_dots3.py
masked_call``): the matmuls over the (row, key) pairs a row chose and no
other, every operand and result moved once, a bit a pair read: the same
whatever kernel design implements it. The rest of a tile, which a masked
kernel computes and discards, is work the floor does not have: at 8k tokens
and top-2048 the chosen pairs are 44% of the causal half. Its floor is the
larger of FLOPs over the bf16 peak and bytes over the HBM peak
(``lib/kernel_readers.py``). A remat replay the compiler keeps is an executed
call and counts."""
from benchmarks.lib.flops_dots3 import SELECT_KERNELS
from benchmarks.lib.kernel_readers import roofline_share


def read(run):
    return roofline_share(run, SELECT_KERNELS, "kernel.dsa_attn_roofline")
