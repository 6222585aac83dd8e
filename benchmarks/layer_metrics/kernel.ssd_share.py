"""Device time of the Pallas scan kernels of the Mamba-2 mixer
(_ssd_fwd_kernel: every head's state carried over a sequence's chunks, C B^T
made once a chunk for all heads; _ssd_bwd_kernel: the states' cotangents
carried back) over device busy time, device 0. Nothing to read in a step that
runs neither."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_granite_hybrid import SSD_KERNELS
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(
        run, lambda event: tracing.kernel_of(event) in SSD_KERNELS)
