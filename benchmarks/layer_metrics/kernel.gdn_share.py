"""Device time of the Pallas scan kernels of the scalar-decay gated delta rule
(_gdn_fwd_kernel: the state carried over a sequence's chunks;
_gdn_bwd_kernel: the state's cotangent carried back) over device busy time,
device 0. Nothing to read in a step that runs neither."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_gdn import GDN_KERNELS


def is_gdn(event):
    return tracing.kernel_of(event) in GDN_KERNELS


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(is_gdn(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, is_gdn)
    return None if share is None else 100.0 * share
