"""Seconds of set-up in which some thread was tracing a function to a jaxpr:
the union of the program's ray_tpu.compile.trace spans (JAX's
jaxpr_trace_duration, one a jit traced, an inner jit's inside its caller's) that
end before set-up's report. Python's time, paid warm or cold; the functions it
goes to are on an earlier line (set-up by function)."""
from benchmarks.lib import setup_events


def read(run):
    return setup_events.read(run, "step.trace_s")
