"""Device time of operations under a hyper-connection's scope (/hc/: the
streams' norm and maps, the read before the sublayer, Sinkhorn's iterations,
the write after it; forward, backward and replay; the sublayer itself is
outside) over device busy time, device 0. Nothing to read in a model with one
residual stream."""
from benchmarks.lib import trace as tracing


def in_hc(event):
    return "/hc/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_hc(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_hc)
    return None if share is None else 100.0 * share
