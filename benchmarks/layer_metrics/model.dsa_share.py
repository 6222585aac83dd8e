"""Device time of learned sparse attention in the full latent layers (/mla/
with an indexer: the index projections under ``indexer``, the indexer kernel
and the pads around it under ``select``, and the selection's three flash
kernels, forward and both backward) over device busy time, device 0: what
choosing keys and attending the chosen costs, beside the mixer's projections.
Nothing to read in a model whose latent layers choose no keys."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_dots3 import SELECT_KERNELS
from benchmarks.lib.kernel_readers import share_of_busy


def in_dsa(event):
    return "/mla/" in event.path and (
        "/indexer/" in event.path or "/select/" in event.path
        or tracing.kernel_of(event) in SELECT_KERNELS)


def read(run):
    return share_of_busy(run, in_dsa)
