"""The readings Kimi-Linear's ``TOLERANCE`` is set from, on the chip, one
process for all seeds: not a run of the benchmark and no metric. The general
tool (``reference_readings.py``) has OLMoE's wrong programs; this one has
this architecture's.

    python3 benchmarks/tools/reference_readings_kimi.py --seeds 1,2,3 [--only NAME] [--rehearse]

For each seed it makes the cell's parameters and first sequence as the loop
does, computes the reference's float32 logits once, and prints one JSON line
of how far from them lie, over the last ``compare_last`` positions:

- ``system``: the program as the cell runs it;
- ``reference_e4m3``: the reference with every weight and every norm's output
  rounded to float8 e4m3, the nearest precision below the stated bfloat16;
- ``reference_state_bf16``: the reference with KDA's state rounded to
  bfloat16 after every token;
- ``system_no_shared_expert``, ``system_no_scaling``: the program without the
  shared expert, and with the gates not multiplied by 2.446;
- ``reference_drops_past_average``: the reference with a dispatch that gives
  each held expert the buffer an even routing would fill (tokens x top-k /
  experts scored) and drops the pairs that arrive past it.

Lines also go to ``chiprun_out/readings/<cell>.jsonl``."""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

WORKLOAD = "kimi-linear-48b-a3b-l5.longctx-16k"
WITHIN = (0.005, 0.01, 0.015, 0.02, 0.03, 0.05)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--only", default=None, help="one entry's name")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default=None, help="directory of the .jsonl")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmarks.lib import cells
    from benchmarks.lib.corpus import make_corpus
    from benchmarks.reference import kimi_linear_decoder as reference
    from ray_tpu.parallel import MeshSpec, logical_sharding, shard_params

    cell = cells.load_cell(WORKLOAD)
    if args.rehearse:
        cell = cells.rehearsed(cell)
    config, traffic = cell["config"], cell["traffic"]
    cfg = cells.program_config(config)
    model_cls = cells.resolve(config["program"]["model"])
    mesh = MeshSpec(**traffic["mesh"]).build()
    last = min(traffic.get("compare_last", traffic["seq"]), traffic["seq"])

    def e4m3(a):
        return a.astype(jnp.float32).astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def spread(logits, expected):
        rel = jnp.linalg.norm(logits.astype(jnp.float32) - expected, axis=-1)
        rel = rel / jnp.linalg.norm(expected, axis=-1)
        out = {"median": float(jnp.median(rel)), "p90": float(jnp.percentile(rel, 90)),
               "max": float(jnp.max(rel))}
        out.update({f"within_{w}": float(jnp.mean(rel <= w)) for w in WITHIN})
        return out

    def system(c):
        return jax.jit(lambda p, i: model_cls(c, mesh=mesh).apply(p, i)[0, -last:])

    def patched(name, replacement):
        """The reference's forward with one of its functions replaced while
        it is traced (a trace is all it takes)."""
        def forward(p, i):
            plain = getattr(reference, name)
            setattr(reference, name, replacement(plain))
            try:
                return reference.forward(p, i, config, last)
            finally:
                setattr(reference, name, plain)

        return jax.jit(forward)

    def state_bf16(_):
        def delta_rule(q, k, v, g, beta):
            heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

            def step(S, x):
                q, k, v, g, beta = x
                S = jnp.exp(g)[..., None] * S
                read = jnp.einsum("hkv,hk->hv", S, k)
                S = S + jnp.einsum("hk,hv->hkv", beta[:, None] * k, v - read)
                # Not astype there and back: XLA allows itself excess precision
                # and drops the pair of converts.
                S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
                return S, jnp.einsum("hkv,hk->hv", S, q)

            return jax.lax.scan(
                step, jnp.zeros((heads, dk, dv), jnp.float32), (q, k, v, g, beta))[1]

        return delta_rule

    def drops(plain):
        def router_gates(p, x, c):
            gates = plain(p, x, c)
            room = x.shape[0] * c["num_experts_per_token"] // c["num_experts_published"]
            arrived = jnp.cumsum(gates > 0, axis=0)  # in token order, per expert
            return jnp.where(arrived <= room, gates, 0.0)

        return router_gates

    expect = jax.jit(lambda p, i: reference.forward(p, i, config, last))
    references = {
        "reference_e4m3": patched(
            "rms_norm", lambda plain: lambda x, s, eps: e4m3(plain(x, s, eps))),
        "reference_state_bf16": patched("delta_rule", state_bf16),
        "reference_drops_past_average": patched("router_gates", drops),
    }
    programs = {
        "system": system(cfg),
        "system_no_shared_expert": system(dataclasses.replace(cfg, num_shared_experts=0)),
        "system_no_scaling": system(dataclasses.replace(cfg, routed_scaling_factor=1.0)),
    }
    if args.only:
        programs = {k: v for k, v in programs.items() if k == args.only}
        references = {k: v for k, v in references.items() if k == args.only}
    out_dir = args.out or os.path.join(cells.ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        ids_all, _ = make_corpus(seed, traffic, config["vocab_size"])
        ids_row = ids_all[0, 0]
        params = jax.jit(model_cls(cfg).init)(
            jax.random.PRNGKey(seed), ids_all[0, :1, :8])
        line = {"workload": WORKLOAD, "seed": seed,
                "device": jax.devices()[0].device_kind, "positions": int(last)}
        with jax.set_mesh(mesh):
            params = shard_params(params, mesh)
            ids = jax.device_put(ids_row[None], logical_sharding(mesh, ("batch", "seq")))
            expected = expect(params, ids_row)
            for name, program in programs.items():
                line[name] = spread(program(params, ids), expected)
            for name, forward in references.items():
                rounded = jax.tree_util.tree_map(e4m3, params) if name.endswith("e4m3") else params
                line[name] = spread(forward(rounded, ids_row), expected)
        del params
        text = json.dumps(line)
        print(text, flush=True)
        with open(os.path.join(out_dir, WORKLOAD + ".jsonl"), "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
