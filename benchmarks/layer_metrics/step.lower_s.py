"""Seconds of set-up spent lowering jaxprs to their modules, the Mosaic bodies
of every Pallas call inside them: the union of the program's
ray_tpu.compile.lower spans (JAX's jaxpr_to_mlir_module_duration) that end
before set-up's report. Paid warm or cold: the persistent cache's key is made
from the lowered module."""
from benchmarks.lib import setup_events


def read(run):
    return setup_events.read(run, "step.lower_s")
