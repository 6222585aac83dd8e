"""The least time the chip could take for the Mamba-2 scan-kernel calls it
executed, over the time they took on device 0.

Each call is counted at what the configuration's ``kernels`` function states
for its kernel (``benchmarks/lib/kernels_granite_hybrid.py``, through
``lib/flops_granite_hybrid.py ssd_call``): the chunked form's own mathematics
at chunks of 256 rows whatever kernel design implements it, C B^T once for the
group of all heads, u and y moved once at two bytes, B and C once, the step at
four bytes a head and token, the float32 states between the two kernels not
counted. Its floor is the larger of FLOPs over the bf16 peak and bytes over
the HBM peak (``lib/kernel_readers.py``). A remat replay the compiler keeps is
an executed call and counts."""
from benchmarks.lib.flops_granite_hybrid import SSD_KERNELS
from benchmarks.lib.kernel_readers import roofline_share


def read(run):
    return roofline_share(run, SSD_KERNELS, "kernel.ssd_roofline")
