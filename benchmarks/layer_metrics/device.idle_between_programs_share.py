"""The part of device.idle_share that lies outside every execution of the
train step on the device (its "XLA Modules" events): what the host can give
back. The part inside a running step, bubbles between two of its operations,
is said on an earlier line; the two sum to device.idle_share."""
from benchmarks.lib import program_trace, trace as tracing


def read(run):
    split = program_trace.idle_split(run)
    if split is None:
        return None
    between, inside, window = split
    seconds = window[1] - window[0]
    program_trace.note_turns(run)
    program_trace.note_once(run, "idle_split", (
        f"device idle, % of the window: between programs "
        f"{100 * tracing.measure(between) / seconds:.4f}, inside a running "
        f"step {100 * tracing.measure(inside) / seconds:.4f}"
    ))
    return 100.0 * tracing.measure(between) / seconds
