"""The part of device 0's collective time during which no other operation
runs on it, over the traced window: communication that compute does not
hide. Nothing to read on one chip."""
from benchmarks.lib import trace as tracing


def read(run):
    found = tracing.traced_device(run)
    if found is None or run["cell"]["chips"] == 1:
        return None
    trace, device, window = found
    exposed = tracing.exposed_collective_seconds(trace, device, window)
    return 100.0 * exposed / (window[1] - window[0])
