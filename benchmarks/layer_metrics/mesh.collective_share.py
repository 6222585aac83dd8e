"""Device 0's time in all-gather, all-reduce, reduce-scatter, all-to-all and
collective-permute over the traced window. Nothing to read on one chip."""
from benchmarks.lib import trace as tracing


def read(run):
    found = tracing.traced_device(run)
    if found is None or run["cell"]["chips"] == 1:
        return None
    trace, device, window = found
    seconds = tracing.collective_seconds(trace, device, window)
    return 100.0 * seconds / (window[1] - window[0])
