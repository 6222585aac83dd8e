"""Device time of operations whose metadata path holds the flax name of the
dense SwiGLU FFN (/mlp/: its three projections and the activation; forward,
backward and replay) over device busy time, device 0. The shared expert is an
MLP under /moe/shared/ and stays model.moe_share's. Nothing to read in a model
whose every layer is an expert layer."""
from benchmarks.lib import step_table, trace as tracing

MARK = f"/{step_table.MLP}/"


def in_mlp(event):
    return MARK in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_mlp(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_mlp)
    return None if share is None else 100.0 * share
