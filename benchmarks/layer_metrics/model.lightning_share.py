"""Device time of operations whose metadata path holds the flax scope of the
decay-only linear-attention mixer (/lightning/: projections, the norm of q and
k over a head's channels, the rotation, the chunked scan with its Pallas
kernels and the transpositions around it, output projection; forward,
backward and replay) over device busy time, device 0. Nothing to read in a
model without one."""
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(run, lambda event: "/lightning/" in event.path)
