"""The language-model train loop of the benchmark. It plays the user.

It runs inside the ``TrainWorker``, the one process that holds the chips,
and does what a user's loop does with the system's own pieces: seeded
parameters placed with ``shard_params``, an optax optimizer,
``train.make_train_step``, and per step the next host batch,
``device_put`` with the batch sharding, the step, the loss fetched and
``train.report``-ed. Everything a cell varies comes from its configuration
and traffic files. One warm-up step ends set-up; then steps run until the
window's seconds have passed, and a step that started inside the window is
finished and counted. A traced run then takes the traffic file's
``trace_steps`` more steps under the profiler.

Spans are the benchmark's own: a host clock around each phase of a step,
and the same phases as ``TraceAnnotation``s so that a traced run carries
them on the device's clock. The loop leaves the interpreter as a user's loop
finds it: nothing is tuned here that ``JaxTrainer``'s users do not tune.
"""
from __future__ import annotations

import importlib
import os
import time


def make_optimizer(traffic: dict):
    """The optax transform the traffic file names (dtypes by name)."""
    import jax.numpy as jnp
    import optax

    opt = dict(traffic["optimizer"])
    if "mu_dtype" in opt:
        opt["mu_dtype"] = getattr(jnp, opt["mu_dtype"])
    return getattr(optax, opt.pop("name"))(**opt)


def make_loss_fn(traffic: dict, model):
    """``loss_fn(params, ids, targets)`` from the traffic file's loss: one
    that takes the model's logits, or one that takes the model itself."""
    from benchmarks.lib.cells import resolve

    loss = resolve(traffic["loss"]["fn"])
    args = traffic["loss"].get("args", {})
    if traffic["loss"]["takes"] == "logits":
        return lambda p, ids, targets: loss(model.apply(p, ids), targets, **args)
    return lambda p, ids, targets: loss(model, p, ids, targets, **args)


def reference_check(model, params, ids_row, cell, mesh):
    """Logits of the system's forward on one seeded sequence against the
    configuration's plain reference, on the last ``compare_last``
    positions. Runs before the optimizer state exists."""
    import jax

    from benchmarks.lib.checks import logits_agreement
    from ray_tpu.parallel import logical_sharding

    config, traffic = cell["config"], cell["traffic"]
    reference = importlib.import_module(config["reference"])
    last = min(traffic.get("compare_last", traffic["seq"]), traffic["seq"])
    ids = jax.device_put(
        ids_row[None], logical_sharding(mesh, ("batch", "seq"))
    )
    system = jax.jit(lambda p, ids: model.apply(p, ids)[0, -last:])(params, ids)
    expected = jax.jit(
        lambda p, ids: reference.forward(p, ids, config, last)
    )(params, ids_row)
    return logits_agreement(system, expected, reference.TOLERANCE)


def train_loop(run: dict) -> None:
    t_loop = time.time()  # the worker is up: ends control.worker_ready_s

    import jax
    from benchmarks.lib.cells import program_config, resolve, stated_kernels
    from benchmarks.lib.checks import (
        CompileCounter, count_collectives, count_pallas_kernels,
    )
    from benchmarks.lib.corpus import make_corpus
    from benchmarks.lib.peaks import peaks_for
    from ray_tpu import train
    from ray_tpu.parallel import MeshSpec, logical_sharding, shard_params

    # Keep every program in the persistent cache, the small ones too (JAX
    # leaves out what compiled in under a second): each run is a new process
    # and would compile them again. Where the cache lives is the system's
    # choice (place_compile_cache), not made here.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter().install()
    cell, rehearsal, traced = run["cell"], run["rehearsal"], run["trace"]
    config, traffic, chips = cell["config"], cell["traffic"], cell["chips"]
    program = config["program"]
    phases = {}  # set-up phases, seconds each

    def phase(name, t0):
        phases[name] = time.perf_counter() - t0
        return time.perf_counter()

    t0 = time.perf_counter()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not rehearsal:
        if platform != "tpu":
            raise RuntimeError(f"TPU worker came up on platform {platform!r}")
        peaks_for(kind)  # an unknown kind is an error before any work
    if jax.local_device_count() != chips:
        raise RuntimeError(
            f"worker was granted {chips} chip(s) and sees "
            f"{jax.local_device_count()}: {devices}"
        )
    spec = MeshSpec(**traffic["mesh"])
    if spec.num_devices != chips:
        raise RuntimeError(f"{spec} spans {spec.num_devices} device(s), not {chips}")
    mesh = spec.build()
    t0 = phase("backend_s", t0)

    cfg = program_config(config)
    model_cls = resolve(program["model"])
    model = model_cls(cfg, mesh=mesh)
    batch, seq, n_batches = traffic["batch"], traffic["seq"], traffic["batches"]
    moe_dispatch = None
    if "resolve_dispatch" in program:
        moe_dispatch = resolve(program["resolve_dispatch"])(
            cfg, tokens=batch * seq, mesh=mesh
        )
    ids_all, targets_all = make_corpus(run["seed"], traffic, config["vocab_size"])
    # Init outside the mesh context: its [1, 8] trace takes no constraints.
    params = jax.jit(model_cls(cfg).init)(
        jax.random.PRNGKey(run["seed"]), ids_all[0, :1, :8]
    )
    tx = make_optimizer(traffic)
    loss_fn = make_loss_fn(traffic, model)

    with jax.set_mesh(mesh):
        params = shard_params(params, mesh)
        jax.block_until_ready(params)
        t0 = phase("init_params_s", t0)
        reference = reference_check(model, params, ids_all[0, 0], cell, mesh)
        t0 = phase("reference_s", t0)

        opt_state = tx.init(params)
        step = train.make_train_step(loss_fn, tx)
        sharding = logical_sharding(mesh, ("batch", "seq"))
        ids, targets = jax.device_put((ids_all[0], targets_all[0]), sharding)
        lowered = step.lower(params, opt_state, ids, targets)
        pallas_kernels = count_pallas_kernels(
            lowered.as_text(), stated_kernels(cell)
        )
        t0 = phase("lower_s", t0)
        compiled = lowered.compile()
        t0 = phase("compile_s", t0)
        memory = compiled.memory_analysis()
        collectives = None
        if traced:
            # The compiled text names every instruction the trace shows.
            hlo = compiled.as_text()
            collectives = count_collectives(hlo)
            with open(os.path.join(run["out_dir"], "step.hlo.txt"), "w") as f:
                f.write(hlo)
            del hlo
            t0 = phase("hlo_text_s", t0)
        params, opt_state, value = compiled(params, opt_state, ids, targets)
        warmup_loss = float(value)  # waits for the device
        phase("warmup_s", t0)
        t_ready = time.time()  # ends setup_s
        train.report({
            "kind": "setup", "rehearsal": rehearsal,
            "platform": platform, "device_kind": kind,
            "device_count": len(devices),
            "mesh": {a: s for a, s in mesh.shape.items() if s > 1},
            "moe_dispatch": moe_dispatch, "pallas_kernels": pallas_kernels,
            "collectives": collectives, "reference": reference,
            # What the step holds on each device while it runs, as the
            # compiler planned it; donated arguments alias the outputs.
            "step_bytes": memory.argument_size_in_bytes
            + memory.temp_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes,
            "phases": phases, "t_loop": t_loop, "t_ready": t_ready,
            "warmup_loss": warmup_loss,
            "cache_dir": jax.config.jax_compilation_cache_dir,
            "compiles": compiles.snapshot(),
        })

        span = jax.profiler.TraceAnnotation
        steps, traced_steps, error = [], 0, None

        def one_step(i, params, opt_state):
            t_start = time.perf_counter()
            with span("bench.step"):
                with span("bench.next_batch"):
                    b = (i + 1) % n_batches
                    host = (ids_all[b], targets_all[b])
                with span("bench.device_put"):
                    ids, targets = jax.device_put(host, sharding)
                t_dispatch = time.perf_counter()
                with span("bench.dispatch"):
                    params, opt_state, value = compiled(
                        params, opt_state, ids, targets
                    )
                with span("bench.wait_loss"):
                    value = float(value)
                t_done = time.perf_counter()
                with span("bench.report"):
                    train.report({"kind": "step", "step": i, "loss": value})
            return params, opt_state, {
                "t_start": t_start - t_window,
                "t_dispatch": t_dispatch - t_window,
                "t_done": t_done - t_window, "loss": value,
            }

        compiles_before = compiles.snapshot()
        t_window = time.perf_counter()
        try:
            while time.perf_counter() - t_window < run["seconds"]:
                params, opt_state, record = one_step(len(steps), params, opt_state)
                steps.append(record)
            if traced:
                # A few more steps under the profiler, after the window and
                # not of it: starting and stopping the profiler stalls the
                # loop, and the window's spans stay those of an untraced run.
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(
                    os.path.join(run["out_dir"], "trace"),
                    profiler_options=options,
                )
                try:
                    for _ in range(traffic["trace_steps"]):
                        params, opt_state, _ = one_step(
                            len(steps) + traced_steps, params, opt_state
                        )
                        traced_steps += 1
                finally:
                    jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - the step's buffers were donated: the run ends here
            error = repr(e)
        compiles_after = compiles.snapshot()

    # A failed step leaves donated, deleted buffers behind.
    leaves = [] if error else jax.tree_util.tree_leaves(params)
    stats = [d.memory_stats() or {} for d in devices]
    train.report({
        "kind": "final", "steps": steps, "traced_steps": traced_steps,
        "failed": int(error is not None), "error": error,
        "compiles_before": compiles_before, "compiles_after": compiles_after,
        "param_devices": sorted(
            {s.device.id for leaf in leaves for s in leaf.addressable_shards}
        ),
        "params_split": any(
            s.data.shape != leaf.shape
            for leaf in leaves for s in leaf.addressable_shards
        ),
        "peak_bytes_in_use": [s.get("peak_bytes_in_use", 0) for s in stats],
        "bytes_in_use": [s.get("bytes_in_use", 0) for s in stats],
    })
