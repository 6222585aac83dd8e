"""Device time of the Pallas grouped-matmul kernels (_gmm_kernel: the three
expert matmuls and the gradients of their inputs; _tgmm_kernel: the
gradients of their weights) over device busy time, device 0. Nothing to read
in a step that runs neither."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_gmm import GMM_KERNELS


def is_gmm(event):
    return tracing.kernel_of(event) in GMM_KERNELS


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(is_gmm(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, is_gmm)
    return None if share is None else 100.0 * share
