"""Device time of the Pallas flash-attention kernels (_fwd_kernel,
_bwd_dkv_kernel, _bwd_dq_kernel) over device busy time, device 0."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops import FLASH_MATMULS


def is_flash(event):
    return tracing.kernel_of(event) in FLASH_MATMULS


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(is_flash(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, is_flash)
    return None if share is None else 100.0 * share
