"""How far the bfloat16 program's chosen blocks lie from the float32
reference's, on the chip, for the MiniCPM-SALA cell: not a run of the
benchmark and no metric.

    python3 benchmarks/tools/sala_selection_agreement.py --seeds 1,2 [--rehearse]

For each seed it makes the cell's parameters and first sequence as the loop
does, takes the sparse layer's normed q and k as the program computes them
(bfloat16) and as the reference does (float32, "highest"), chooses with
``select_blocks`` and with the reference's ``chosen_blocks``, and prints one
JSON line: the share of (row, group) pairs whose sets are equal, over the
sequence and over its last ``compare_last`` rows, and by how many blocks the
others differ. bfloat16 moves near-ties; the number says how far."""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

CELL = "minicpm-sala-9b-l4.long16k"


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import cells
    from benchmarks.lib.corpus import make_corpus
    from ray_tpu.ops.attention import select_blocks

    cell = cells.load_cell(CELL)
    if args.rehearse:
        cell = cells.rehearsed(cell)
    config, traffic = cell["config"], cell["traffic"]
    reference = importlib.import_module(config["reference"])
    # One layer, no remat: the first layer's mixer is all that is read.
    cfg = dataclasses.replace(cells.program_config(config), remat=False)
    sel = cfg.sparse
    model_cls = cells.resolve(config["program"]["model"])
    last = min(traffic.get("compare_last", traffic["seq"]), traffic["seq"])

    def normed(mdl, _):
        return mdl.name in ("q_norm", "k_norm") and "sparse" in mdl.path

    @jax.jit
    def program_sets(params, ids):
        _, state = model_cls(cfg).apply(
            params, ids[None], capture_intermediates=normed, mutable=["intermediates"])
        taken = state["intermediates"]["layers_0"]["sparse"]
        q, k = (taken[n]["__call__"][0].transpose(0, 2, 1, 3) for n in ("q_norm", "k_norm"))
        return select_blocks(
            q, k, block_size=sel.block_size, topk=sel.topk, window=sel.window_size,
            init_blocks=sel.init_blocks, kernel_size=sel.kernel_size,
            kernel_stride=sel.kernel_stride)[0].transpose(1, 0, 2)  # [T, kv, blocks]

    @jax.jit
    def reference_sets(params, ids):
        with jax.default_matmul_precision("highest"):
            p = params["params"]
            layer = p["layers_0"]
            x = config["scale_emb"] * p["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
            x = reference.rms_norm(x, layer["input_norm"]["scale"], config["rms_norm_eps"])
            q, k, _ = reference.sparse_qkv(layer["sparse"], x, config)
            return reference.chosen_blocks(q, k, config["sparse_config"])[:, :, 0]

    for seed in (int(s) for s in args.seeds.split(",")):
        ids_all, _ = make_corpus(seed, traffic, config["vocab_size"])
        ids = ids_all[0, 0]
        params = jax.jit(model_cls(cfg).init)(jax.random.PRNGKey(seed), ids_all[0, :1, :8])
        ours = np.asarray(program_sets(params, ids))
        theirs = np.asarray(reference_sets(params, ids))
        differ = (ours != theirs).sum(-1)  # [T, kv]: blocks in one set and not the other
        line = {"workload": CELL, "seed": seed, "device": jax.devices()[0].device_kind,
                "rows": int(ours.shape[0]), "groups": int(ours.shape[1])}
        for name, part in (("all_rows", differ), (f"last_{last}", differ[-last:])):
            line[name] = {
                "equal_share": float((part == 0).mean()),
                "mean_blocks_differing_where_not": float(part[part > 0].mean()) if (part > 0).any() else 0.0,
                "most_blocks_differing": int(part.max()),
            }
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
