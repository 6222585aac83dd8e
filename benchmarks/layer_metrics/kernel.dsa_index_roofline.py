"""The least time the chip could take for the indexer-kernel calls it
executed, over the time they took on device 0.

Each call is counted at what the configuration's ``kernels`` function states
(``benchmarks/lib/kernels_dots3.py``, through ``lib/flops_dots3.py
index_call``): every index head's products over the (row, key <= row) pairs,
the operands in and a bit a pair out. The weighted ReLU sum over the heads and
the bisection for each row's threshold are no matmuls and the floor has
nothing for them, so the share says how much of the kernel's time they take.
Its floor is the larger of FLOPs over the bf16 peak and bytes over the HBM
peak (``lib/kernel_readers.py``)."""
from benchmarks.lib.flops_dots3 import INDEX_KERNEL
from benchmarks.lib.kernel_readers import roofline_share


def read(run):
    return roofline_share(run, (INDEX_KERNEL,), "kernel.dsa_index_roofline")
