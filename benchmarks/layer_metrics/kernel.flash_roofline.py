"""The least time the chip could take for the flash-attention calls it
executed, over the time they took on device 0.

Each call is counted at what its block needs (benchmarks/lib/flops.py
flash_call): the [T, T] matmuls of its kernel with the masked half of a
causal block left out, every operand moved once; its floor is the larger of
FLOPs over the bf16 peak and bytes over the HBM peak. A remat replay of the
forward that the compiler keeps is an executed call and counts (XLA removed
it in PR 22's cells). On a mesh with a seq axis every call
is one [T/seq, T/seq] block of the ring and is counted as a causal one: exact
for the diagonal block, and the average over the devices for the others,
which half of the devices need whole and the other half not at all."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops import FLASH_MATMULS, flash_call
from benchmarks.lib.peaks import peaks_for


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, (lo, hi) = found
    cell = run["cell"]
    config, mesh = cell["config"], cell["traffic"].get("mesh", {})
    calls = [(e, tracing.kernel_of(e)) for e in trace.devices[device]
             if e.start >= lo and e.end <= hi]
    calls = [(e, k) for e, k in calls if k in FLASH_MATMULS]
    if not calls:
        return None
    peaks = peaks_for(run["setup"]["device_kind"])
    rows = cell["traffic"]["batch"] // (mesh.get("data", 1) * mesh.get("fsdp", 1))
    heads = config["num_attention_heads"] // mesh.get("tensor", 1)
    block = cell["traffic"]["seq"] // mesh.get("seq", 1)
    floor = compute_bound = 0.0
    for _, kernel in calls:
        flops, nbytes = flash_call(
            kernel, rows * heads, block, block, config["head_dim"], causal=True
        )
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
