"""Seconds of set-up spent making executables: the union of the program's
ray_tpu.compile.backend spans (JAX's backend_compile_duration: the backend's
compile on a miss of the persistent cache, the cache's read and the load on a
hit) that end before set-up's report. Every executable's, init's, the
reference's two, the eager operations' and the step's (step.compile_s, the
benchmark's own clock around lowered.compile()) among them."""
from benchmarks.lib import setup_events


def read(run):
    return setup_events.read(run, "step.executables_s")
