"""Device time of operations whose metadata path holds the flax scope of the
scalar-decay gated delta-rule mixer (/gdn/: projections, short convolutions,
gates, the chunked scan with its Pallas kernels and the transpositions around
it, output projection; forward, backward and replay) over device busy time,
device 0. Nothing to read in a model without one."""
from benchmarks.lib import trace as tracing


def in_gdn(event):
    return "/gdn/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_gdn(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_gdn)
    return None if share is None else 100.0 * share
