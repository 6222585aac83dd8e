"""What the per-layer readers of one architecture's metrics share: a share of
device busy time by a predicate over the traced events, and a kernel family's
roofline share from what the configuration's ``kernels`` function states of
each call. A reader file says which events are its own and nothing else."""
from __future__ import annotations

from . import trace as tracing
from .cells import stated_kernels
from .peaks import peaks_for


def share_of_busy(run, mine):
    """100 x device 0's time in events ``mine`` takes over its busy time in
    the traced window; None where the run has no trace or no such event."""
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(mine(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, mine)
    return None if share is None else 100.0 * share


def roofline_share(run, kernels, metric: str):
    """100 x the least time the chip could take for the executed calls of
    ``kernels`` (the larger of stated FLOPs over the bf16 peak and stated
    bytes over the HBM peak, a call) over the time they took on device 0, with
    a note under ``metric``'s name; None where none of them ran."""
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, (lo, hi) = found
    calls = [(e, tracing.kernel_of(e)) for e in trace.devices[device]
             if e.start >= lo and e.end <= hi]
    calls = [(e, k) for e, k in calls if k in kernels]
    if not calls:
        return None
    peaks = peaks_for(run["setup"]["device_kind"])
    stated = stated_kernels(run["cell"])
    floors = {}  # kernel -> (seconds by FLOPs, seconds by bytes) of one call
    for kernel in {k for _, k in calls}:
        flops, nbytes = stated[kernel]["call"]
        floors[kernel] = (flops / peaks["bf16_flops_per_s"],
                          nbytes / peaks["hbm_bytes_per_s"])
    floor = sum(max(floors[k]) for _, k in calls)
    compute_bound = sum(floors[k][0] >= floors[k][1] for _, k in calls)
    seconds = {k: sum(e.dur for e, kernel in calls if kernel == k) for k in kernels}
    total = sum(seconds.values())
    per_kernel = ", ".join(
        f"{k} {s:.4f} s at {100 * max(floors[k]) * sum(kk == k for _, kk in calls) / s:.1f}%"
        for k, s in seconds.items() if s
    )
    run["notes"].append(
        f"{metric}: {len(calls)} calls, {compute_bound} of them bound by "
        f"compute, the rest by bytes; floor {floor:.4f} s of {total:.4f} s "
        f"({per_kernel})"
    )
    return 100.0 * floor / total
