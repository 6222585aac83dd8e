"""Seconds the host spent in parallel.shard_params placing the parameters'
leaves on the mesh: the sum of the program's ray_tpu.parallel.shard_params
spans before set-up's report. device_put returns before a copy ends, so this is
the host's side of the placing, apart from jit(init)'s trace, compile and run,
which the loop's init_params_s phase holds too."""
from benchmarks.lib import setup_events


def read(run):
    return setup_events.read(run, "mesh.shard_params_s")
