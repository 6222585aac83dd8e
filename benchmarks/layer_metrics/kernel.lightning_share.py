"""Device time of the Pallas scan kernels of the decay-only linear attention
(_lightning_fwd_kernel: a head's state carried over its chunks;
_lightning_bwd_kernel: the state's cotangent carried back) over device busy
time, device 0. Nothing to read in a step that runs neither."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_minicpm_sala import LIGHTNING_KERNELS
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(
        run, lambda event: tracing.kernel_of(event) in LIGHTNING_KERNELS)
