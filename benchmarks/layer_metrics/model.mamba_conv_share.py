"""Device time of operations under the Mamba-2 mixer's scope ``conv``
(/mamba/ and /conv/: the one causal depthwise convolution over x, B and C with
its bias and SiLU, its two Pallas kernels and what XLA leaves around them;
forward, backward and replay) over device busy time, device 0: what the
filter costs beside the scan. Nothing to read in a model without the mixer."""
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(
        run, lambda event: "/mamba/" in event.path and "/conv/" in event.path)
