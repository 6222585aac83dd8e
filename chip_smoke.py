"""The quickest proof that the system still starts on the chip.

Drives the normal path once — ``ray_tpu.init`` -> ``JaxTrainer`` -> mesh ->
step — at the full width and depth of llama-1b
(bf16 params, b2 x s2048, adamw with bf16 mu, donated buffers, full remat),
with random weights from a seed:

1. a ``JaxTrainer`` worker holding one chip compiles the step, proves the
   Pallas flash kernels are in it, and takes a warm-up and STEPS more steps
   on a fixed batch;
2. on a host with four chips, the same loop in one worker holding all four,
   at b2 x s4096 on ``MeshSpec(fsdp=2, seq=2)`` (FSDP collectives plus the
   Pallas ring path inside shard_map);
3. a TPU task started after the trainer's worker is gone checks the kernels
   against their references: flash fwd+bwd, gmm fwd+bwd, and a MoELayer
   under gmm and capacity dispatch against the ragged oracle.

One process per chip: this driver never initialises a JAX backend (it checks
so at exit) and every device program runs in a TPU worker. It refuses to
start where the workers would inherit the CPU or interpret mode, exits
non-zero if any phase fails, and on success prints a ``chip_smoke: summary:``
line (every phase; its timings are smoke timings, not measurements) and then,
as the last line of stdout, ``{"ok": true, "device": {"platform", "kind",
"count"}}`` and nothing more.
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import sys
import time

STEPS = 4  # after the warm-up step
# Normalised max error, max|a - ref| / max|ref|, allowed between a kernel
# in bf16 and its float32 reference: a few roundings of bf16's 8-bit
# mantissa (2**-9 relative each).
TOLERANCE = 2e-2
DEADLINE_S = 1100  # the contract allows 1200 s, compilation included


def train_loop(config):
    """Runs inside the TrainWorker, the one process that holds the chips."""
    import time
    from dataclasses import replace

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models import CONFIGS
    from ray_tpu.models.llama import LlamaForCausalLM, causal_lm_loss
    from ray_tpu.parallel import logical_sharding, shard_params

    cache = {"hits": 0, "requests": 0}

    def count_cache_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1

    jax.monitoring.register_event_listener(count_cache_event)

    chips, spec = config["chips"], config["mesh"]
    batch, seq, steps = config["batch"], config["seq"], config["steps"]
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"TPU worker came up on backend {jax.default_backend()!r}"
        )
    devices = jax.devices()
    if jax.local_device_count() != chips:
        raise RuntimeError(
            f"worker was granted {chips} chip(s) but sees "
            f"{jax.local_device_count()}: {devices}"
        )
    if spec.num_devices != chips:
        # MeshSpec.build() keeps devices[:num_devices] without a word.
        raise RuntimeError(
            f"{spec} spans {spec.num_devices} device(s), worker holds {chips}"
        )
    mesh = spec.build()

    cfg = replace(CONFIGS["llama-1b"], param_dtype=jnp.bfloat16)
    model = LlamaForCausalLM(cfg, mesh=mesh)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    targets = np.roll(ids, -1, axis=1)
    # Init outside the mesh context: its [1, 8] trace takes no constraints.
    params = jax.jit(LlamaForCausalLM(cfg).init)(
        jax.random.PRNGKey(0), ids[:1, :8]
    )
    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, mu_dtype=jnp.bfloat16)
    with jax.set_mesh(mesh):
        params = shard_params(params, mesh)
        opt_state = tx.init(params)
        ids, targets = jax.device_put(
            (ids, targets), logical_sharding(mesh, ("batch", "seq"))
        )
        step = train.make_train_step(
            lambda p, ids, targets: causal_lm_loss(
                model.apply(p, ids), targets
            ),
            tx,
        )
        lowered = step.lower(params, opt_state, ids, targets)
        text = lowered.as_text()
        pallas_calls = {
            kernel: text.count(f'kernel_name = "{kernel}"')
            for kernel in ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel")
        }
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        hlo = compiled.as_text()
        train.report({
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "num_layers": cfg.num_layers,
            "pallas_calls": pallas_calls,
            "collectives": {
                op: hlo.count(op + "(") + hlo.count(op + "-start(")
                for op in ("all-gather", "all-reduce", "reduce-scatter",
                           "collective-permute")
            },
            "compile_s": compile_s,
            "cache_dir": jax.config.jax_compilation_cache_dir,
            "cache_hits": cache["hits"],
            "cache_requests": cache["requests"],
        })
        for i in range(1 + steps):  # step 0 is the warm-up
            t0 = time.perf_counter()
            params, opt_state, loss = compiled(params, opt_state, ids, targets)
            loss = float(loss)  # waits for the device
            train.report(
                {"step": i, "loss": loss, "step_s": time.perf_counter() - t0}
            )
    leaves = jax.tree_util.tree_leaves(params)
    train.report({
        "param_devices": sorted(
            {s.device.id for leaf in leaves for s in leaf.addressable_shards}
        ),
        "params_split": any(
            s.data.shape != leaf.shape
            for leaf in leaves for s in leaf.addressable_shards
        ),
        "bytes_in_use": [d.memory_stats()["bytes_in_use"] for d in devices],
        "peak_bytes_in_use": [
            d.memory_stats()["peak_bytes_in_use"] for d in devices
        ],
    })


def kernel_checks(tolerance):
    """Runs as a TPU task: each Pallas kernel against its reference on the
    chip, as normalised max errors. Raises if a check's program holds no
    Pallas call."""
    from dataclasses import replace

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.mixtral import CONFIGS, MoELayer
    from ray_tpu.ops.attention import attention_reference, flash_attention
    from ray_tpu.ops.gmm import aligned_group_layout, gmm

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"TPU task came up on backend {jax.default_backend()!r}"
        )
    rng = np.random.RandomState(0)
    errors = {}

    def rand(shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def compare(name, fn, ref_fn, args, ref_args, pallas=True):
        """fn(*args) and its vjp under a random cotangent, against ref_fn
        in float32 at highest matmul precision."""
        fwd_bwd = jax.jit(lambda args, ct: (fn(*args), jax.vjp(fn, *args)[1](ct)))
        out_shape = jax.eval_shape(fn, *args)
        ct = rand(out_shape.shape, out_shape.dtype)
        if pallas and "tpu_custom_call" not in fwd_bwd.lower(args, ct).as_text():
            raise RuntimeError(f"{name}: no Pallas call in the lowered program")
        out, grads = fwd_bwd(args, ct)
        with jax.default_matmul_precision("highest"):
            ref_out, ref_vjp = jax.vjp(ref_fn, *ref_args)
            ref_grads = ref_vjp(ct.astype(ref_out.dtype))
        pairs = [("fwd", out, ref_out)] + [
            (f"grad{i}", g, r) for i, (g, r) in enumerate(zip(
                jax.tree_util.tree_leaves(grads),
                jax.tree_util.tree_leaves(ref_grads),
            ))
        ]
        for tag, got, ref in pairs:
            got, ref = np.asarray(f32(got)), np.asarray(f32(ref))
            errors[f"{name}/{tag}"] = float(
                np.max(np.abs(got - ref)) / np.max(np.abs(ref))
            )

    # Flash attention: blocks 1024 as the model uses them, 512 (and the
    # unmasked off-diagonal case) as ring attention uses them.
    for d in (64, 128):
        for block, causal in ((1024, True), (512, True), (512, False)):
            qkv = tuple(rand((1, 4, 2048, d)) for _ in range(3))
            compare(
                f"flash_d{d}_b{block}_{'causal' if causal else 'full'}",
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, block_q=block, block_k=block
                ),
                lambda q, k, v: attention_reference(q, k, v, causal=causal),
                qkv, f32(qkv),
            )

    # Grouped matmul at mixtral-small shapes: b2 x s2048 tokens, top-2 of
    # 8 experts, hidden 1024 -> expert width 3584.
    cfg = replace(CONFIGS["mixtral-small"], param_dtype=jnp.bfloat16)
    n_pairs = 2 * 2048 * cfg.num_experts_per_tok
    e_flat = jnp.asarray(rng.randint(0, cfg.num_experts, n_pairs), jnp.int32)
    _, dst, tile_group, m_pad = aligned_group_layout(e_flat, cfg.num_experts)
    lhs = jnp.zeros((m_pad, cfg.hidden_size), jnp.bfloat16).at[dst].set(
        rand((n_pairs, cfg.hidden_size))
    )
    rhs = rand((cfg.num_experts, cfg.hidden_size, cfg.intermediate_size),
               scale=cfg.hidden_size ** -0.5)

    def gmm_reference(lhs, rhs):
        tiles = lhs.reshape(tile_group.shape[0], -1, lhs.shape[1])
        return jnp.einsum("tmk,tkn->tmn", tiles, rhs[tile_group]).reshape(
            lhs.shape[0], -1
        )

    compare("gmm", lambda lhs, rhs: gmm(lhs, rhs, tile_group), gmm_reference,
            (lhs, rhs), f32((lhs, rhs)))

    # One MoE layer under each dispatch against the exact ragged oracle.
    # capacity_factor E/k gives every expert room for every token, so the
    # capacity path drops nothing and computes the same function.
    x = rand((2, 2048, cfg.hidden_size))
    oracle = MoELayer(replace(cfg, moe_dispatch="ragged", dtype=jnp.float32))
    params = jax.jit(oracle.init)(jax.random.PRNGKey(0), x[:, :256])
    for dispatch in ("gmm", "capacity"):
        layer = MoELayer(replace(
            cfg, moe_dispatch=dispatch,
            capacity_factor=cfg.num_experts / cfg.num_experts_per_tok,
        ))
        compare(f"moe_{dispatch}", layer.apply, oracle.apply,
                (params, x), f32((params, x)), pallas=dispatch == "gmm")

    failed = {k: v for k, v in errors.items() if not v <= tolerance}
    return {"errors": errors, "failed": failed,
            "device_kind": jax.devices()[0].device_kind}


class SmokeFailure(Exception):
    pass


def check(ok, message):
    if not ok:
        raise SmokeFailure(message)


def run_trainer(name, chips, mesh, batch, seq):
    """One JaxTrainer fit in a worker holding ``chips`` chips; returns the
    phase summary after checking what the loop reported."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    t0 = time.perf_counter()
    result = JaxTrainer(
        train_loop,
        train_loop_config={"chips": chips, "mesh": mesh, "batch": batch,
                           "seq": seq, "steps": STEPS},
        scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker={"TPU": float(chips)}
        ),
        run_config=RunConfig(name=f"chip_smoke_{name}"),
    ).fit()
    check(result.error is None, f"{name}: trainer failed: {result.error!r}")
    setup, *steps, final = result.metrics_history
    losses = [s["loss"] for s in steps]
    summary = {
        **setup, **final, "chips": chips, "batch": batch, "seq": seq,
        "steps": len(steps), "losses": losses,
        "step_s": [round(s["step_s"], 4) for s in steps],
        "compile_s": round(setup["compile_s"], 2),
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    print(f"chip_smoke: {name}: {json.dumps(summary)}", flush=True)
    check(setup["platform"] == "tpu", f"{name}: platform {setup['platform']}")
    check(setup["device_count"] == chips, f"{name}: {setup['device_count']} devices")
    # The flash forward and both backward kernels in every layer, not
    # attention_reference.
    check(all(n >= setup["num_layers"] for n in setup["pallas_calls"].values()),
          f"{name}: Pallas calls in the lowered step: {setup['pallas_calls']}")
    check(len(steps) == 1 + STEPS, f"{name}: {len(steps)} steps reported")
    check(all(l == l and abs(l) != float("inf") for l in losses),
          f"{name}: non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"{name}: loss did not fall: {losses}")
    check(len(final["param_devices"]) == chips,
          f"{name}: parameters live on devices {final['param_devices']}")
    check(all(b > 0 for b in final["bytes_in_use"]),
          f"{name}: a device holds no memory: {final['bytes_in_use']}")
    if chips > 1:
        check(final["params_split"], f"{name}: no parameter is split")
        check(setup["collectives"]["collective-permute"] > 0
              and setup["collectives"]["all-gather"]
              + setup["collectives"]["all-reduce"]
              + setup["collectives"]["reduce-scatter"] > 0,
              f"{name}: collectives in the compiled step: {setup['collectives']}")
    return summary


def run(ray_tpu) -> dict:
    from ray_tpu._private import fastpath, native_store
    from ray_tpu.parallel import MeshSpec

    native = {"store": native_store.native_available(),
              "codec": fastpath.available()}
    print(f"chip_smoke: native store live: {native['store']}, "
          f"native codec live: {native['codec']}", flush=True)
    check(all(native.values()) or not (shutil.which("gcc") and shutil.which("g++")),
          f"native build failed with a toolchain present: {native}")

    chips = int(ray_tpu.cluster_resources().get("TPU", 0))
    check(chips > 0,
          "ray_tpu.init() found no TPU chip: detection looks at "
          f"RAY_TPU_NUM_CHIPS (here {os.environ.get('RAY_TPU_NUM_CHIPS')!r}), "
          "then /dev/accel*, then numeric /dev/vfio/* entries")

    phases = {"one_chip": run_trainer("one_chip", 1, MeshSpec(), 2, 2048)}
    if chips >= 4:
        phases["four_chips"] = run_trainer(
            "four_chips", 4, MeshSpec(fsdp=2, seq=2), 2, 4096
        )
    # The trainer's worker was killed, not waited for: this task gets the
    # chip only if the runtime hands it over once that process is gone.
    t0 = time.perf_counter()
    kernels = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(kernel_checks).remote(TOLERANCE)
    )
    kernels["wall_s"] = round(time.perf_counter() - t0, 1)
    print(f"chip_smoke: kernels: {json.dumps(kernels)}", flush=True)
    check(not kernels["failed"],
          f"kernels outside tolerance {TOLERANCE}: {kernels['failed']}")
    phases["kernels"] = kernels

    # The device as JAX reports it to the worker that holds the whole host.
    widest = phases.get("four_chips", phases["one_chip"])
    return {
        "ok": True,
        "device": {"platform": widest["platform"],
                   "kind": widest["device_kind"],
                   "count": widest["device_count"]},
        "native": native,
        "phases": phases,
        "claim": None,
    }


def result_line(device) -> str:
    """The last line of stdout on success: these keys and no others (the
    summary line before it carries everything else)."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])},
    })


def main() -> int:
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms.split(",")[0].strip().lower() not in ("", "tpu"):
        print(f"chip_smoke: JAX_PLATFORMS={platforms!r} does not put the TPU "
              "first; TPU workers inherit this environment and would never "
              "open the chip. This script needs a TPU chip.", file=sys.stderr)
        return 1
    if os.environ.get("RAY_TPU_PALLAS_INTERPRET"):
        print("chip_smoke: RAY_TPU_PALLAS_INTERPRET is set; interpret mode "
              "is for the CPU tests, not the chip.", file=sys.stderr)
        return 1

    def on_deadline(signum, frame):
        raise SmokeFailure(f"not done after {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)

    import ray_tpu

    ray_tpu.init()
    try:
        result = run(ray_tpu)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        ray_tpu.shutdown()

    from jax._src import xla_bridge  # no public probe that does not initialise

    if xla_bridge.backends_are_initialized():
        print("chip_smoke: FAILED: the driver process initialised a JAX "
              "backend; the chip belongs to the workers", file=sys.stderr)
        return 1
    # Worker output reaches sys.stdout from a client thread: a line still
    # in flight after shutdown goes to stderr, not after the result.
    out, sys.stdout = sys.stdout, sys.stderr
    print(f"chip_smoke: summary: {json.dumps(result)}", file=out, flush=True)
    print(result_line(result["device"]), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
