"""Run one benchmark cell once, through the system's normal path.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process calls ``ray_tpu.init()``, builds a ``JaxTrainer`` with one
worker holding the cell's chips, calls ``fit()``, reads ``metrics_history``
and prints one JSON object as the last line of its standard output. It never
initialises a JAX backend: the ``TrainWorker`` is the one process that holds
the chips, so the loop, the reference check and the profiler run there. The
cell's loop, model, reference, traffic and metrics are found by the names in
``BENCHMARK.json`` (``benchmarks/lib/cells.py``).

Without a chip, with an unknown device kind, or with another device count
than the cell names, it exits non-zero and prints no result line.
``--rehearse`` runs the cell's control flow at its files' tiny ``rehearsal``
sizes on the CPU and prints a line that holds no metric value.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

T_COMMAND = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchFailure(Exception):
    pass


def worker_entry(run: dict) -> None:
    """Pickled by value (it lives in ``__main__``) and run by the
    TrainWorker: makes the checkout importable there, then hands over to
    the loop the cell's traffic file names."""
    import importlib
    import sys

    if run["root"] not in sys.path:
        sys.path.insert(0, run["root"])
    module, _, name = run["cell"]["traffic"]["loop"].partition(":")
    getattr(importlib.import_module(module), name)(run)


def check_environment(rehearse: bool) -> None:
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    if rehearse:
        if first != "cpu":
            raise BenchFailure("--rehearse runs on the CPU: set JAX_PLATFORMS=cpu")
        return
    if first not in ("", "tpu"):
        raise BenchFailure(
            f"JAX_PLATFORMS={os.environ['JAX_PLATFORMS']!r} does not put the "
            "TPU first; the TPU worker inherits it and would never open the "
            "chip. The benchmark measures the chip and does not fall back."
        )
    if os.environ.get("RAY_TPU_PALLAS_INTERPRET"):
        raise BenchFailure(
            "RAY_TPU_PALLAS_INTERPRET is set; interpret mode is for CPU tests"
        )


def run_cell(cell: dict, args, out_dir: str) -> dict:
    """``init -> JaxTrainer -> fit`` for one cell; returns the run record
    the metric readers take."""
    try:
        import ray_tpu
        from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    except ImportError as e:
        raise BenchFailure(f"the system under test is not in this checkout: {e}")

    ray_tpu.init()
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if chips < cell["chips"]:
            raise BenchFailure(
                f"ray_tpu.init() found {chips} TPU chip(s); "
                f"{cell['name']} needs {cell['chips']}"
            )
        result = JaxTrainer(
            worker_entry,
            train_loop_config={
                "cell": cell, "seed": args.seed, "seconds": args.seconds,
                "trace": bool(args.trace), "rehearsal": args.rehearse,
                "root": ROOT, "out_dir": out_dir,
            },
            scaling_config=ScalingConfig(
                num_workers=1,
                resources_per_worker={"TPU": float(cell["chips"])},
            ),
            run_config=RunConfig(
                name="bench_" + cell["name"],
                storage_path=os.path.join(out_dir, "results"),
            ),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise BenchFailure(f"trainer failed: {result.error!r}")
    history = result.metrics_history
    if len(history) < 2 or history[-1].get("kind") != "final":
        raise BenchFailure(f"the loop reported {len(history)} record(s), no final one")
    return {
        "cell": cell, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "rehearsal": args.rehearse,
        "t_command": T_COMMAND, "out_dir": out_dir,
        "setup": history[0], "final": history[-1],
        "steps": history[-1]["steps"],
        "reported_steps": sum(1 for h in history if h.get("kind") == "step"),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--keep", metavar="DIR", default=None,
                        help="copy the run record and the raw trace here")
    args = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmarks.lib import cells, result

    try:
        check_environment(args.rehearse)
        cell = cells.load_cell(args.workload)
        if args.rehearse:
            cell = cells.rehearsed(cell)
        with tempfile.TemporaryDirectory(prefix="bench_") as out_dir:
            run = run_cell(cell, args, out_dir)
            line, notes = result.result_line(run)
            if args.keep:
                result.keep(run, notes, args.keep)
    except BenchFailure as e:
        print(f"benchmarks/run.py: FAILED: {e}", file=sys.stderr)
        return 1

    from jax._src import xla_bridge  # no public probe that does not initialise

    if xla_bridge.backends_are_initialized():
        print("benchmarks/run.py: FAILED: this process initialised a JAX "
              "backend; the chip belongs to the worker", file=sys.stderr)
        return 1
    # Worker output reaches sys.stdout from a client thread: a line still in
    # flight after shutdown goes to stderr, not after the result.
    out, sys.stdout = sys.stdout, sys.stderr
    for note in notes:
        print(f"benchmarks/run.py: {note}", file=out)
    print(json.dumps(line), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
