"""Device time of operations whose metadata path holds the flax scope of the
gated delta-rule mixer (/kda/: projections, short convolutions, gates, the
chunked scan with its Pallas kernels, output norm; forward, backward and
replay) over device busy time, device 0. Nothing to read in a model without
one."""
from benchmarks.lib import trace as tracing


def in_kda(event):
    return "/kda/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_kda(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_kda)
    return None if share is None else 100.0 * share
