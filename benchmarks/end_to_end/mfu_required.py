"""tokens_per_s_per_chip x the FLOPs the forward and backward passes
require per token (the configuration's own function, benchmarks/lib/flops.py)
over the chip's bf16 peak (benchmarks/lib/peaks.py). It moves with
tokens_per_s_per_chip inside a cell by construction; it is the number that
compares cells, chips and papers."""
from benchmarks.lib.cells import resolve
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.spans import tokens_per_s_per_chip


def read(run):
    rate = tokens_per_s_per_chip(run)
    if rate is None:
        return None
    cell = run["cell"]
    flops = resolve(cell["config"]["required_flops"])(
        cell["config"], cell["traffic"]["seq"]
    )
    peak = peaks_for(run["setup"]["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rate * flops / peak
