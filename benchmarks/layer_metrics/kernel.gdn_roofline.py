"""The least time the chip could take for the scalar-decay scan-kernel calls
it executed, over the time they took on device 0.

Each call is counted at what the configuration's ``kernels`` function states
for its kernel (``benchmarks/lib/kernels_olmo_hybrid.py``, through
``lib/flops_gdn.py gdn_call``): the chunk's matmuls at the published key and
value head dims with the masked half of its causal blocks left out, every
operand and result moved once, the decay four bytes a head and token. Its
floor is the larger of FLOPs over the bf16 peak and bytes over the HBM peak.
A remat replay the compiler keeps is an executed call and counts."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.cells import stated_kernels
from benchmarks.lib.flops_gdn import GDN_KERNELS
from benchmarks.lib.peaks import peaks_for


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, (lo, hi) = found
    calls = [(e, tracing.kernel_of(e)) for e in trace.devices[device]
             if e.start >= lo and e.end <= hi]
    calls = [(e, k) for e, k in calls if k in GDN_KERNELS]
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
    seconds = {k: sum(e.dur for e, kernel in calls if kernel == k)
               for k in GDN_KERNELS}
    total = sum(seconds.values())
    per_kernel = ", ".join(
        f"{k} {s:.4f} s at {100 * max(floors[k]) * sum(kk == k for _, kk in calls) / s:.1f}%"
        for k, s in seconds.items() if s and k in floors
    )
    run["notes"].append(
        f"kernel.gdn_roofline: {len(calls)} calls, {compute_bound} of them "
        f"bound by compute, the rest by bytes; floor {floor:.4f} s of "
        f"{total:.4f} s ({per_kernel})"
    )
    return 100.0 * floor / total
