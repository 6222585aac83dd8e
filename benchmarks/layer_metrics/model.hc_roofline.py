"""The least time the chip could take for the hyper-connections of the steps
it executed, over the time their operations took on device 0.

The floor is ``benchmarks/lib/flops_hc.py``'s: the HBM bytes a step's
hyper-connections need, from the tokens of a step, the number of streams and
the hidden size (streams read once and written once forward, read twice and
written once backward, whatever implements them), over the HBM peak. The time
is the self time of operations whose path holds /hc/ inside the window's
``bench.step`` spans, a replay's included: what a fused kernel for the
hyper-connection would be judged by. Nothing to read in a model with one
residual stream."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_hc import step_bytes
from benchmarks.lib.peaks import peaks_for


def in_hc(event):
    return "/hc/" in event.path


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, (lo, hi) = found
    inside = [e for e in trace.devices[device] if e.end > lo and e.start < hi]
    seconds = sum(t for e, t in tracing.self_times(inside) if in_hc(e))
    if not seconds:
        return None
    cell = run["cell"]
    steps = sum(e.name == "bench.step" and e.start >= lo and e.end <= hi
                for e in trace.host)
    tokens = cell["traffic"]["batch"] * cell["traffic"]["seq"]
    nbytes = steps * step_bytes(cell["config"], tokens)
    floor = nbytes / peaks_for(run["setup"]["device_kind"])["hbm_bytes_per_s"]
    run["notes"].append(
        f"model.hc_roofline: {steps} steps, {nbytes / 1e9:.3f} GB needed, "
        f"floor {floor:.4f} s of {seconds:.4f} s under /hc/"
    )
    return 100.0 * floor / seconds
