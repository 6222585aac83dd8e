"""The readings a reference's ``TOLERANCE`` is set from, on the chip, one
process for all seeds, for any cell whose architecture brings its wrong
programs as a module: not a run of the benchmark and no metric.

    python3 benchmarks/tools/reference_readings_of.py --workload sarvam-105b-l5.pretrain-4k \\
        --wrong benchmarks.tools.wrong_sarvam --seeds 1,2,3 [--only NAME] [--rehearse]

For each seed it makes the cell's parameters and first sequence as the loop
does, computes the reference's float32 logits once (the module the
configuration's file names under ``reference``), and prints one JSON line of
how far from them lie, over the last ``compare_last`` positions:

- ``system``: the program as the cell runs it;
- ``reference_e4m3``: the reference with every weight and every norm's output
  rounded to float8 e4m3, the nearest precision below the stated bfloat16;
- what ``--wrong`` names, a module with ``programs(cfg)``: name -> (the
  program's config, the mixer's parameters that program lacks), and
  ``references(rounding)``: name -> (one of the reference's functions, what
  replaces it while the reference is traced). Its docstring says what each is.

Lines also go to ``chiprun_out/readings/<cell>.jsonl``. (OLMoE's and
Kimi-Linear's wrong programs are older and live inside their own tools,
``reference_readings.py`` and ``reference_readings_kimi.py``.)"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

WITHIN = (0.005, 0.01, 0.015, 0.02, 0.03, 0.05)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--wrong", required=True, help="module of wrong programs")
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--only", default=None, help="one entry's name")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default=None, help="directory of the .jsonl")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmarks.lib import cells
    from benchmarks.lib.corpus import make_corpus
    from ray_tpu.parallel import MeshSpec, logical_sharding, shard_params
    from ray_tpu.util.tracing import MIXERS

    cell = cells.load_cell(args.workload)
    if args.rehearse:
        cell = cells.rehearsed(cell)
    config, traffic = cell["config"], cell["traffic"]
    reference = importlib.import_module(config["reference"])
    wrong = importlib.import_module(args.wrong)
    cfg = cells.program_config(config)
    model_cls = cells.resolve(config["program"]["model"])
    mesh = MeshSpec(**traffic["mesh"]).build()
    last = min(traffic.get("compare_last", traffic["seq"]), traffic["seq"])

    def e4m3(a):
        # Every e4m3 value is a bfloat16 value: the copy keeps a's dtype.
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

    def bf16(a):
        # Not astype there and back: XLA allows itself excess precision and
        # drops the pair of converts.
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def spread(logits, expected):
        rel = jnp.linalg.norm(logits.astype(jnp.float32) - expected, axis=-1)
        rel = rel / jnp.linalg.norm(expected, axis=-1)
        out = {"median": float(jnp.median(rel)), "p90": float(jnp.percentile(rel, 90)),
               "max": float(jnp.max(rel))}
        out.update({f"within_{w}": float(jnp.mean(rel <= w)) for w in WITHIN})
        return out

    def system(c, drop=()):
        def forward(p, i):
            if drop:  # parameters the other program does not have
                p = {"params": {
                    name: {mixer: {k: v for k, v in sub.items() if k not in drop}
                           if mixer in MIXERS else sub
                           for mixer, sub in layer.items()}
                    if name.startswith("layers_") else layer
                    for name, layer in p["params"].items()}}
            return model_cls(c, mesh=mesh).apply(p, i)[0, -last:]

        return jax.jit(forward)

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

    expect = jax.jit(lambda p, i: reference.forward(p, i, config, last))
    references = {
        "reference_e4m3": patched(
            "rms_norm", lambda plain: lambda x, s, eps: e4m3(plain(x, s, eps))),
        **{name: patched(*entry) for name, entry in wrong.references(bf16).items()},
    }
    programs = {
        "system": system(cfg),
        **{name: system(*entry) for name, entry in wrong.programs(cfg).items()},
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
        line = {"workload": args.workload, "seed": seed,
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
                del rounded
        del params
        text = json.dumps(line)
        print(text, flush=True)
        with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
