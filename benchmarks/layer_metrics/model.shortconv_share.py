"""Device time of operations whose metadata path holds the flax scope of the
gated short-convolution mixer (/shortconv/: the input projection to the two
gates and the convolved x~, the gated convolution with its two Pallas kernels,
the output projection; forward, backward and replay) over device busy time,
device 0. Nothing to read in a model without one."""
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(run, lambda event: "/shortconv/" in event.path)
