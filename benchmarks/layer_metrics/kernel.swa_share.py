"""Device time of the windowed flash-attention kernels
(_fwd_window_kernel and its remat replay, _bwd_dkv_window_kernel,
_bwd_dq_window_kernel: the three kernels whose grids walk a sliding
window's band) over device busy time, device 0. Nothing to read in a step
that runs none."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_laguna import WINDOW_KERNELS


def is_windowed(event):
    return tracing.kernel_of(event) in WINDOW_KERNELS


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(is_windowed(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, is_windowed)
    return None if share is None else 100.0 * share
