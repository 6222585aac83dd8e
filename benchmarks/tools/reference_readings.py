"""The readings a reference's ``TOLERANCE`` is set from, on the chip, one
process for all seeds: not a run of the benchmark and no metric.

    python3 benchmarks/tools/reference_readings.py --workload <cell> --seeds 1,2,3 [--wrong 1] [--lowprec 0] [--expert-scale F] [--rehearse]

For each seed it makes the cell's parameters and first sequence as the loop
does (``benchmarks/loops/train_lm.py``), computes the reference's float32
logits once, and prints one JSON line of how far from them lie:

- ``system``: the program as the cell runs it;
- with ``--expert-scale F``, ``system_experts_scaled``: the same with the
  expert matrices multiplied by F, against those weights' own reference
  (sqrt(experts) turns flax's draw of a stacked [E, in, out], whose fan-in
  is experts x in, into one draw per expert);
- ``reference_e4m3``: the reference with every weight and every norm's output
  rounded to float8 e4m3, the nearest precision below bfloat16;
- ``reference_e4m3_experts``: the reference with only the expert matrices
  rounded so;
- with ``--wrong 1``, programs of another function: a capacity dispatch that
  drops pairs (factor 1.25) and the other gate normalisation.

Each entry holds the median, the 90th and 99th percentile and the maximum
of the per-position error, and the share of positions within each of
``WITHIN``. Lines also go to ``<--out or chiprun_out/readings>/<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

WITHIN = (0.005, 0.01, 0.015, 0.02, 0.03, 0.05)
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--wrong", type=int, default=0)
    parser.add_argument("--lowprec", type=int, default=1)
    parser.add_argument("--expert-scale", type=float, default=None)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default=None, help="directory of the .jsonl")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmarks.lib import cells
    from benchmarks.lib.corpus import make_corpus
    from ray_tpu.parallel import MeshSpec, logical_sharding, shard_params

    cell = cells.load_cell(args.workload)
    if args.rehearse:
        cell = cells.rehearsed(cell)
    config, traffic = cell["config"], cell["traffic"]
    reference = importlib.import_module(config["reference"])
    cfg = cells.program_config(config)
    model_cls = cells.resolve(config["program"]["model"])
    mesh = MeshSpec(**traffic["mesh"]).build()
    last = min(traffic.get("compare_last", traffic["seq"]), traffic["seq"])
    experts = hasattr(cfg, "num_experts")

    def on_experts(fn, params):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: fn(a)
            if any(getattr(k, "key", None) in EXPERT_LEAVES for k in path) else a,
            params,
        )

    def e4m3(a):
        return a.astype(jnp.float32).astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def spread(logits, expected):
        rel = jnp.linalg.norm(logits.astype(jnp.float32) - expected, axis=-1)
        rel = rel / jnp.linalg.norm(expected, axis=-1)
        out = {"median": float(jnp.median(rel)), "p90": float(jnp.percentile(rel, 90)),
               "p99": float(jnp.percentile(rel, 99)), "max": float(jnp.max(rel))}
        out.update({f"within_{w}": float(jnp.mean(rel <= w)) for w in WITHIN})
        return out

    out_dir = args.out or os.path.join(cells.ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, args.workload + ".jsonl")

    def system(c):
        return jax.jit(lambda p, i: model_cls(c, mesh=mesh).apply(p, i)[0, -last:])

    def forward_rounded(p, i):
        # The patch holds while this is traced, and a trace is all it takes.
        plain = reference.rms_norm
        reference.rms_norm = lambda x, scale, eps: e4m3(plain(x, scale, eps))
        try:
            return reference.forward(p, i, config, last)
        finally:
            reference.rms_norm = plain

    expect = jax.jit(lambda p, i: reference.forward(p, i, config, last))
    expect_rounded = jax.jit(forward_rounded)
    programs = {"system": system(cfg)}
    if args.wrong:
        programs["system_capacity_1.25_drops"] = system(dataclasses.replace(
            cfg, moe_dispatch="capacity", capacity_factor=1.25))
        if hasattr(cfg, "norm_topk_prob"):
            programs["system_other_gate_normalisation"] = system(
                dataclasses.replace(cfg, norm_topk_prob=not cfg.norm_topk_prob))

    for seed in (int(s) for s in args.seeds.split(",")):
        ids_all, _ = make_corpus(seed, traffic, config["vocab_size"])
        ids_row = ids_all[0, 0]
        params = jax.jit(model_cls(cfg).init)(
            jax.random.PRNGKey(seed), ids_all[0, :1, :8]
        )
        line = {"workload": args.workload, "seed": seed,
                "device": jax.devices()[0].device_kind, "positions": int(last)}
        with jax.set_mesh(mesh):
            params = shard_params(params, mesh)
            ids = jax.device_put(ids_row[None], logical_sharding(mesh, ("batch", "seq")))
            expected = expect(params, ids_row)
            for name, program in programs.items():
                line[name] = spread(program(params, ids), expected)
                print(seed, name, line[name], file=sys.stderr, flush=True)
            if args.lowprec:
                if experts:
                    line["reference_e4m3_experts"] = spread(
                        expect(on_experts(e4m3, params), ids_row), expected)
                line["reference_e4m3"] = spread(expect_rounded(
                    jax.tree_util.tree_map(e4m3, params), ids_row), expected)
            if args.expert_scale:
                # In the place of ``params``, not beside them: the reference
                # of a sharded cell needs the memory the loop leaves it.
                del expected
                params = on_experts(
                    lambda a: (a.astype(jnp.float32) * args.expert_scale).astype(a.dtype),
                    params,
                )
                line["system_experts_scaled"] = spread(
                    programs["system"](params, ids), expect(params, ids_row))
        del params
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
