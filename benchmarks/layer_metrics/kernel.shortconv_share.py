"""Device time of the Pallas kernels of the gated short convolution
(_gated_conv_fwd_kernel: C * conv(B * x~) in one pass over the projection's
three thirds; _gated_conv_bwd_kernel: the three thirds' cotangents and the
filter's) over device busy time, device 0. Nothing to read in a step that runs
neither."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_lfm2 import GATED_CONV_KERNELS
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(
        run, lambda event: tracing.kernel_of(event) in GATED_CONV_KERNELS)
