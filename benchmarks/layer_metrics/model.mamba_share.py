"""Device time of operations whose metadata path holds the flax scope of the
Mamba-2 state-space mixer (/mamba/: the three input projections, the
convolution with its bias and SiLU, the step's softplus, the chunked scan with
its Pallas kernels and the slices around it, the gate with the norm over every
head's channels, the output projection; forward, backward and replay) over
device busy time, device 0. Nothing to read in a model without one."""
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(run, lambda event: "/mamba/" in event.path)
