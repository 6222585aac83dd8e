"""The least time the chip could take for the flash-attention calls it
executed, over the time they took on device 0.

Each call is counted at what the configuration's ``kernels`` function states
for its kernel (``benchmarks/lib/kernels_*.py``, through ``lib/flops.py
flash_call``): the [T, T] matmuls of its kernel with the masked half of a
causal block left out, every operand moved once; its floor is the larger of
FLOPs over the bf16 peak and bytes over the HBM peak. A remat replay of the
forward that the compiler keeps is an executed call and counts (XLA removed
it in PR 22's cells)."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.cells import stated_kernels
from benchmarks.lib.flops import FLASH_MATMULS
from benchmarks.lib.peaks import peaks_for


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, (lo, hi) = found
    calls = [(e, tracing.kernel_of(e)) for e in trace.devices[device]
             if e.start >= lo and e.end <= hi]
    calls = [(e, k) for e, k in calls if k in FLASH_MATMULS]
    if not calls:
        return None
    peaks = peaks_for(run["setup"]["device_kind"])
    stated = stated_kernels(run["cell"])
    floor = compute_bound = 0.0
    for _, kernel in calls:
        flops, nbytes = stated[kernel]["call"]
        by_flops = flops / peaks["bf16_flops_per_s"]
        by_bytes = nbytes / peaks["hbm_bytes_per_s"]
        floor += max(by_flops, by_bytes)
        compute_bound += by_flops >= by_bytes
    seconds = sum(e.dur for e, _ in calls)
    run["notes"].append(
        f"kernel.flash_roofline: {len(calls)} calls, {compute_bound:g} of them "
        f"bound by compute, the rest by bytes; floor {floor:.4f} s of "
        f"{seconds:.4f} s"
    )
    return 100.0 * floor / seconds
