"""Device time of operations under the latent-attention mixer's scopes
``rope`` (the frequency table, the rotation of q's and k's 64-wide parts,
the slices and concatenations around it) and ``qk_norm`` (the RMSNorm over
each head's channels of q and of k), forward, backward and replay, over
device busy time, device 0: what the partial rotation and the norm cost
beside the projections and the flash kernels. Nothing to read in a model
whose MLA layers have neither (Kimi-Linear's) or that has no MLA layer."""
from benchmarks.lib import trace as tracing


def in_rotary(event):
    return "/mla/rope/" in event.path or "/mla/qk_norm/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_rotary(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_rotary)
    return None if share is None else 100.0 * share
