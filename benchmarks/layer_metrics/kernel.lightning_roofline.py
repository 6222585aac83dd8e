"""The least time the chip could take for the Lightning scan-kernel calls it
executed, over the time they took on device 0.

Each call is counted at what the configuration's ``kernels`` function states
for its kernel (``benchmarks/lib/kernels_minicpm_sala.py``, through
``lib/flops_minicpm_sala.py lightning_call``): the recurrence's own 5 dk dv a
token and head whatever chunk the kernel works in, every operand and result
moved once, the float32 states between the two kernels not counted. Its floor
is the larger of FLOPs over the bf16 peak and bytes over the HBM peak
(``lib/kernel_readers.py``). A remat replay the compiler keeps is an executed
call and counts."""
from benchmarks.lib.flops_minicpm_sala import LIGHTNING_KERNELS
from benchmarks.lib.kernel_readers import roofline_share


def read(run):
    return roofline_share(run, LIGHTNING_KERNELS, "kernel.lightning_roofline")
