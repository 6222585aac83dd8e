"""Device time of operations under the softmax-attention mixer's scope
``out_gate`` (the gate's projection from the layer's normed input, its
sigmoid and the product with the attention's output; forward, backward and
replay; in /attn/ and in /swa/) over device busy time, device 0: what a
gated output costs beside the projections and the flash kernels, the more
where the gate has q's width and its projection is a fifth matrix of the
mixer. Nothing to read in a model whose attention has no gate."""
from benchmarks.lib import trace as tracing


def in_gate(event):
    return "/out_gate/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_gate(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_gate)
    return None if share is None else 100.0 * share
