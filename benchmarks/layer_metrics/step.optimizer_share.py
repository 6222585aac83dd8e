"""Device time of operations outside the model's forward (jvp) and backward
(transpose) scopes, the optimizer update and apply_updates, over device busy
time. Left out where the compiled step's metadata carries no such scope."""
from benchmarks.lib import trace as tracing


def in_model(event):
    return "jvp(" in event.path or "transpose(" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_model(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, lambda e: not in_model(e))
    return None if share is None else 100.0 * share
