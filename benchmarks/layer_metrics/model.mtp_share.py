"""Device time of operations of the multi-token-prediction module (its two
norms, its projection and its layer under /mtp/, and its pass of the shared
head and loss, which the loss function scopes and JAX renders as jvp(mtp) and
transpose(jvp(mtp)); forward, backward and replay) over device busy time,
device 0. Nothing to read in a model without the module."""
from benchmarks.lib import trace as tracing


def in_mtp(event):
    return "/mtp/" in event.path or "(mtp)" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_mtp(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_mtp)
    return None if share is None else 100.0 * share
