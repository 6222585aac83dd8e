"""Xing4.0-29B-A4B's wrong programs, for ``reference_readings_of.py --wrong
benchmarks.tools.wrong_xing4``: each has to read far from the reference. And,
run as a script, the readings ``reference_check`` cannot take, since it sees
the main head alone.

- ``system_one_sinkhorn_iteration``: the program with one round of rows and
  columns where the source says 20; ``system_no_scaling``: the gates not
  times 2; ``system_no_mscale``: YaRN's table and the softmax scale without
  ``mscale`` squared;
- ``reference_rows_only``: the reference's Sinkhorn normalising rows and never
  columns; ``reference_post_without_2``: H_post = sigmoid(.) without its 2;
  ``reference_constant_maps``: the three maps without their input-dependent
  term (the gating factors zero: sigmoid(b) and SK(b) for every token);
  ``reference_no_q_latent_norm``: q's latent not normed.

    python3 benchmarks/tools/wrong_xing4.py --seeds 1,2,3 [--rehearse]

prints, a seed a JSON line (also appended to
``chiprun_out/readings/<cell>.mtp.jsonl``): how far the module's logits (the
program's hidden states through the shared head, as its loss takes them) lie
from the reference's ``mtp_logits`` over the last ``compare_last`` positions,
and the program's first loss (what a run reports as ``warmup_loss``) beside
the reference's ``loss`` and its two terms."""
from __future__ import annotations

import dataclasses

CELL = "xing4-29b-a4b-l5.pretrain-mtp-4k"


def programs(cfg) -> dict:
    """name -> (the program's config,)."""
    without_mscale = dataclasses.replace(cfg, rope_scaling=dataclasses.replace(
        cfg.rope_scaling, mscale=0.0, mscale_all_dim=0.0))
    one_iteration = dataclasses.replace(cfg, hyper_connections=dataclasses.replace(
        cfg.hyper_connections, sinkhorn_iters=1))
    return {
        "system_one_sinkhorn_iteration": (one_iteration,),
        "system_no_scaling": (dataclasses.replace(cfg, routed_scaling_factor=1.0),),
        "system_no_mscale": (without_mscale,),
    }


def references(bf16) -> dict:
    """name -> (one of the reference's functions, what replaces it given the
    plain one). ``bf16`` is not used: no entry here is a matter of precision."""
    import jax.numpy as jnp

    def rows_only(plain):
        def sinkhorn(logits, iters, eps):
            m = jnp.exp(logits)
            for _ in range(iters):
                m = m / (m.sum(axis=-1, keepdims=True) + eps)
            return m

        return sinkhorn

    def post_without_2(plain):
        def connection_maps(p, streams, cfg):
            pre, post, res = plain(p, streams, cfg)
            return pre, post / 2.0, res

        return connection_maps

    def constant_maps(plain):
        return lambda p, streams, cfg: plain(
            {**p, "alpha": jnp.zeros_like(p["alpha"])}, streams, cfg)

    def no_q_latent_norm(plain):
        return lambda p, x, cfg: x @ p["q_a_proj"]["kernel"].astype(jnp.float32)

    return {
        "reference_rows_only": ("sinkhorn", rows_only),
        "reference_post_without_2": ("connection_maps", post_without_2),
        "reference_constant_maps": ("connection_maps", constant_maps),
        "reference_no_q_latent_norm": ("q_latent", no_q_latent_norm),
    }


def main() -> None:
    import argparse
    import importlib
    import json
    import os

    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default=None, help="directory of the .jsonl")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmarks.lib import cells
    from benchmarks.lib.checks import logits_agreement
    from benchmarks.lib.corpus import make_corpus
    from benchmarks.loops.train_lm import make_loss_fn
    from ray_tpu.parallel import MeshSpec, logical_sharding, shard_params

    cell = cells.load_cell(CELL)
    if args.rehearse:
        cell = cells.rehearsed(cell)
    config, traffic = cell["config"], cell["traffic"]
    reference = importlib.import_module(config["reference"])
    cfg = cells.program_config(config)
    model_cls = cells.resolve(config["program"]["model"])
    mesh = MeshSpec(**traffic["mesh"]).build()
    model = model_cls(cfg, mesh=mesh)
    last = min(traffic.get("compare_last", traffic["seq"]), traffic["seq"])
    loss_fn = jax.jit(make_loss_fn(traffic, model))

    def further(p, ids, targets):
        """The module's logits as ``mtp_chunked_lm_loss`` makes them."""
        _, predicted = model.apply(p, ids, return_hidden=True, next_ids=targets)
        head = p["params"]["lm_head"]["kernel"]
        return jnp.matmul(predicted[0, -last:].astype(head.dtype), head,
                          preferred_element_type=jnp.float32)

    further = jax.jit(further)
    expect_logits = jax.jit(lambda p, i, t: reference.mtp_logits(p, i, t, config, last))
    expect_loss = jax.jit(lambda p, i, t: reference.loss_terms(p, i, t, config))
    out_dir = args.out or os.path.join(cells.ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        ids_all, targets_all = make_corpus(seed, traffic, config["vocab_size"])
        params = jax.jit(model_cls(cfg).init)(
            jax.random.PRNGKey(seed), ids_all[0, :1, :8])
        with jax.set_mesh(mesh):
            params = shard_params(params, mesh)
            ids, targets = jax.device_put(
                (ids_all[0], targets_all[0]), logical_sharding(mesh, ("batch", "seq")))
            agreement = logits_agreement(
                further(params, ids, targets),
                expect_logits(params, ids_all[0, 0], targets_all[0, 0]),
                reference.TOLERANCE)
            first_loss = float(loss_fn(params, ids, targets))
            main_term, mtp_term = (float(v) for v in expect_loss(
                params, ids_all[0, 0], targets_all[0, 0]))
        expected = main_term + config["mtp_loss_weight"] * mtp_term
        line = {"workload": CELL, "seed": seed, "positions": int(last),
                "device": jax.devices()[0].device_kind,
                "mtp_logits": agreement, "first_loss": first_loss,
                "reference_loss": expected, "reference_main": main_term,
                "reference_mtp": mtp_term,
                "loss_rel_err": abs(first_loss - expected) / expected}
        text = json.dumps(line)
        print(text, flush=True)
        with open(os.path.join(out_dir, CELL + ".mtp.jsonl"), "a") as f:
            f.write(text + "\n")
        del params


if __name__ == "__main__":
    import os
    import sys

    # Run as a script the repository is not on the path yet. Not in main():
    # a test that calls it must keep its process's sys.path[0], which
    # ray_tpu hands to the workers it spawns.
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    main()
