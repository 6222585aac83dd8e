"""Device time of operations whose metadata path holds the flax scope of a
full softmax-attention layer's mixer (/attn/: the q, k, v, gate and o
projections, a rotation where the kind has one, the causal flash kernels,
the output gate; forward, backward and replay) over device busy time, device
0: beside ``model.kda_share`` (or ``model.swa_share``) in a model whose layers
differ, what its softmax layers cost. Nothing to read in a step without such
a scope."""
from benchmarks.lib import trace as tracing


def in_attn(event):
    return "/attn/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_attn(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_attn)
    return None if share is None else 100.0 * share
