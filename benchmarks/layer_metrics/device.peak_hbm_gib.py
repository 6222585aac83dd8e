"""The peak on the fullest device: the larger of the backend's
peak_bytes_in_use after the window and what the compiled step holds while it
runs (benchmarks/lib/result.py memory_peak_bytes). The room a PR may spend."""
from benchmarks.lib.result import memory_peak_bytes


def read(run):
    peak = memory_peak_bytes(run)
    return peak / 2**30 if peak else None
