"""Device time of operations whose metadata path holds the flax scope of the
latent-attention mixer (/mla/: the q projection, the latent's down-projection,
norm and up-projection, the flash kernels it calls, the output projection;
forward, backward and replay) over device busy time, device 0. Nothing to
read in a model without one."""
from benchmarks.lib import trace as tracing


def in_mla(event):
    return "/mla/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_mla(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_mla)
    return None if share is None else 100.0 * share
