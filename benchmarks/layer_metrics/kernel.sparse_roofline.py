"""The least time the chip could take for the sparse-attention calls it
executed, over the time they took on device 0.

Each call is counted at what the configuration's ``kernels`` function states
for its kernel (``benchmarks/lib/kernels_minicpm_sala.py``, through
``lib/flops_minicpm_sala.py sparse_call``): the matmuls over the (row, key)
pairs a row attends and no other, every operand and result moved once, K and
V at their own heads, the chosen blocks a bit each: the same whatever kernel
design implements it. The part of a tile outside a row's blocks that a kernel
computes and masks is work the floor does not have. Its floor is the larger
of FLOPs over the bf16 peak and bytes over the HBM peak
(``lib/kernel_readers.py``). A remat replay the compiler keeps is an executed
call and counts."""
from benchmarks.lib.flops_minicpm_sala import SPARSE_KERNELS
from benchmarks.lib.kernel_readers import roofline_share


def read(run):
    return roofline_share(run, SPARSE_KERNELS, "kernel.sparse_roofline")
