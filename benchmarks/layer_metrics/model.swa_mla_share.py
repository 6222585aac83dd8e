"""Device time of operations whose metadata path holds the flax scope of a
sliding layer's latent mixer (/swa_mla/: both latents' projections at the
sliding kind's widths, the rotation, the windowed flash kernels at q/k heads
of 256, the output gate, the output projection; forward, backward and replay)
over device busy time, device 0. The full layers' mixer is /mla/ and is not in
it. Nothing to read in a model without such a layer."""
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(run, lambda event: "/swa_mla/" in event.path)
