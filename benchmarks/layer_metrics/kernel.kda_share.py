"""Device time of the Pallas scan kernels of the gated delta rule
(_kda_fwd_kernel: the state carried over a sequence's chunks, and its remat
replay; _kda_bwd_kernel: the state's cotangent carried back) over device busy
time, device 0. Nothing to read in a step that runs neither."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_kda import KDA_KERNELS


def is_kda(event):
    return tracing.kernel_of(event) in KDA_KERNELS


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(is_kda(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, is_kda)
    return None if share is None else 100.0 * share
