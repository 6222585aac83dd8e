"""The least time the chip could take for the gated-convolution calls it
executed, over the time they took on device 0.

Each call is counted at what the configuration's ``kernels`` function states
for its kernel (``benchmarks/lib/kernels_lfm2.py``, through
``lib/flops_lfm2.py gated_conv_call``): forward 4 and backward 7 arrays of
[tokens, hidden] at two bytes moved once (the projection's three thirds and y;
those, y's cotangent and the thirds' cotangents), a few FLOPs an element. Its
floor is the larger of FLOPs over the bf16 peak and bytes over the HBM peak
(``lib/kernel_readers.py``): the bytes'. A remat replay the compiler keeps is
an executed call and counts."""
from benchmarks.lib.flops_lfm2 import GATED_CONV_KERNELS
from benchmarks.lib.kernel_readers import roofline_share


def read(run):
    return roofline_share(run, GATED_CONV_KERNELS, "kernel.shortconv_roofline")
