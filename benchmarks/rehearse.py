"""The third rehearsal: compile a cell's train step at its real size for a
described v5e topology, with no chip attached.

    JAX_PLATFORMS=cpu python benchmarks/rehearse.py <cell> [<cell> ...]

What the chip's compiler would refuse (memory, a kernel it cannot tile or
partition) it refuses here, at no chip time. Prints the compiler's memory
analysis per device, the Pallas kernels the configuration states as the
lowered step holds them, beside the least it states of each, and the
collectives in the compiled one; exits 1 where a stated kernel is short.
Nothing runs, so nothing here is a time.

The program takes its kernels only where ``jax.default_backend()`` is the
TPU, and here it is the CPU: this script steers them on by standing in for
that one probe, not through an option of the program. The first two
rehearsals are ``benchmarks/run.py --rehearse`` (with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for a four-chip cell).
"""
from __future__ import annotations

import importlib
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_cell(name: str, topo) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.lib import cells, checks
    from benchmarks.loops.train_lm import make_loss_fn, make_optimizer
    from ray_tpu import train
    from ray_tpu.parallel import MeshSpec, logical_sharding
    from ray_tpu.parallel.mesh import spec_for_param

    cell = cells.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    mesh = MeshSpec(**traffic["mesh"]).build(topo.devices[: cell["chips"]])
    cfg = cells.program_config(config)
    model_cls = cells.resolve(config["program"]["model"])
    model = model_cls(cfg, mesh=mesh)
    if "resolve_dispatch" in config["program"]:
        cells.resolve(config["program"]["resolve_dispatch"])(
            cfg, tokens=traffic["batch"] * traffic["seq"], mesh=mesh
        )
    tx = make_optimizer(traffic)

    def placed(path, leaf):
        """As shard_params places a parameter; optimizer moments follow the
        parameter whose path ends theirs, scalars are replicated."""
        keys = tuple(getattr(p, "key", getattr(p, "name", getattr(p, "idx", "")))
                     for p in path)
        keys = keys[keys.index("params"):] if "params" in keys else keys
        spec = spec_for_param(keys, leaf.shape) if leaf.ndim else PartitionSpec()
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        )

    shapes = jax.eval_shape(
        model_cls(cfg).init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )
    params = jax.tree_util.tree_map_with_path(placed, shapes)
    opt_state = jax.tree_util.tree_map_with_path(
        placed, jax.eval_shape(tx.init, shapes)
    )
    batch = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"]), np.int32,
        sharding=logical_sharding(mesh, ("batch", "seq")),
    )
    with jax.set_mesh(mesh):
        step = train.make_train_step(make_loss_fn(traffic, model), tx)
        lowered = step.lower(params, opt_state, batch, batch)
        stated = cells.stated_kernels(cell)
        kernels = checks.count_pallas_kernels(lowered.as_text(), stated)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        seconds = time.perf_counter() - t0
    memory = compiled.memory_analysis()
    gib = 2.0 ** 30
    return {
        "cell": name, "compile_s": round(seconds, 1),
        "arguments_gib": round(memory.argument_size_in_bytes / gib, 2),
        "outputs_gib": round(memory.output_size_in_bytes / gib, 2),
        "aliased_gib": round(memory.alias_size_in_bytes / gib, 2),
        "temporaries_gib": round(memory.temp_size_in_bytes / gib, 2),
        "pallas_kernels": kernels,
        "stated_least": {k: s["least"] for k, s in stated.items()},
        "holds_stated_kernels": checks.holds_stated_kernels(kernels, stated),
        "collectives": checks.count_collectives(compiled.as_text()),
    }


def main(names) -> int:
    import jax
    from jax.experimental import topologies

    # import_module, not "import ... as": ray_tpu.ops re-exports a function
    # named ring_attention over the submodule's attribute.
    attention = importlib.import_module("ray_tpu.ops.attention")
    ring_attention = importlib.import_module("ray_tpu.ops.ring_attention")

    # A compile written to the persistent cache here cannot be read back
    # without a chip; keep the rehearsal out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    attention._on_tpu = ring_attention._on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    held = True
    for name in names:
        found = compile_cell(name, topo)
        print(found, flush=True)
        held &= found["holds_stated_kernels"]
    return int(not held)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
