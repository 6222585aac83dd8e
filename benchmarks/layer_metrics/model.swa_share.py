"""Device time of operations whose metadata path holds the flax scope of a
sliding-window layer's mixer (/swa/: the projections at the sliding layers'
head count, the rotation, the windowed flash kernels, the output gate;
forward, backward and replay) over device busy time, device 0. The full
layers' mixer is /attn/ and is not in it. Nothing to read in a model without
a sliding layer."""
from benchmarks.lib import trace as tracing


def in_swa(event):
    return "/swa/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_swa(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_swa)
    return None if share is None else 100.0 * share
