"""Device time of operations whose metadata path holds the flax scope of the
expert layer (/moe/: router, dispatch, expert FFN, combine, forward and
backward) over device busy time. Nothing to read in a model without one."""
from benchmarks.lib import trace as tracing


def in_moe(event):
    return "/moe/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_moe(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_moe)
    return None if share is None else 100.0 * share
