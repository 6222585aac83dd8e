"""Keep a run with the trainer's own record of it, and read kept runs back.

    python3 benchmarks/tools/stall_hunt.py run --workload <cell> --seed <n> \
        --seconds 20 --trace <0|1> --keep DIR [--rehearse]
    python3 benchmarks/tools/stall_hunt.py read DIR [DIR ...] [--over-ms 50]

``run`` is ``benchmarks/run.py --keep DIR`` (the same functions, in the same
order, one result line last) and besides keeps
``DIR/results/train_events_rank0.jsonl``, which ``run.py`` leaves in a
temporary directory, and says the lines of ``lib/train_events.py`` in an
untraced run too (the per-layer readers, which say them, run only with
``--trace 1``). ``read`` goes through kept runs without a chip: a line a run
(turns, both median turns, stall on and off CPU against the benchmark's own,
a turn's median CPU and switches), and every turn whose excess is over
``--over-ms`` with what the record holds of it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def run_and_keep(args) -> int:
    import cloudpickle

    from benchmarks import run as bench
    from benchmarks.lib import cells, result, train_events

    # run.py's loop entry reaches the worker pickled by value, as it does
    # out of run.py's own __main__: the worker cannot import this checkout yet.
    cloudpickle.register_pickle_by_value(bench)
    try:
        bench.check_environment(args.rehearse)
        cell = cells.load_cell(args.workload)
        if args.rehearse:
            cell = cells.rehearsed(cell)
        with tempfile.TemporaryDirectory(prefix="bench_") as out_dir:
            run = bench.run_cell(cell, args, out_dir)
            line, notes = result.result_line(run)
            train_events.metrics(run)  # said already in a traced run
            result.keep(run, notes, args.keep)
            kept = os.path.join(args.keep, train_events.FILE)
            if os.path.exists(os.path.join(out_dir, train_events.FILE)):
                os.makedirs(os.path.dirname(kept), exist_ok=True)
                shutil.copy(os.path.join(out_dir, train_events.FILE), kept)
    except bench.BenchFailure as e:
        print(f"benchmarks/tools/stall_hunt.py: FAILED: {e}", file=sys.stderr)
        return 1
    out, sys.stdout = sys.stdout, sys.stderr
    for text in notes:
        print(f"benchmarks/run.py: {text}", file=out)
    print(json.dumps(line), file=out, flush=True)
    return 0


def read_kept(args) -> int:
    from benchmarks.lib import train_events
    from benchmarks.lib.spans import percentile, turn_times

    for directory in args.dirs:
        with open(os.path.join(directory, "run.json")) as f:
            run = json.load(f)
        run.update(out_dir=directory, notes=[], trace_data=None)
        for key in ("train_events", "train_metrics"):
            run.pop(key, None)
        values = train_events.metrics(run)
        if values is None:
            print(f"{directory}: no record of the window", run["notes"])
            continue
        theirs = turn_times(run)
        print(json.dumps({
            "run": directory, "seed": run["seed"], "trace": run["trace"],
            "steps": len(theirs), "window_s": round(sum(theirs), 4),
            "median_turn_ms": round(1e3 * percentile(theirs, 50), 4),
            "stall_share_x_window_ms": round(
                1e3 * (sum(theirs) - len(theirs) * percentile(theirs, 50)), 3),
            **{k: round(v, 4) for k, v in values.items()},
        }))
        for text in run["notes"]:
            if "stalled turn" in text:
                turn = json.loads(text.split("stalled turn ", 1)[1])
                if turn["excess_ms"] > args.over_ms:
                    print("   ", json.dumps(turn))
            elif args.notes:
                print("   ", text)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = parser.add_subparsers(dest="command", required=True)
    run = sub.add_parser("run")
    run.add_argument("--workload", required=True)
    run.add_argument("--seed", type=int, default=0)
    run.add_argument("--seconds", type=float, default=10.0)
    run.add_argument("--trace", type=int, choices=(0, 1), default=0)
    run.add_argument("--rehearse", action="store_true")
    run.add_argument("--keep", metavar="DIR", required=True)
    read = sub.add_parser("read")
    read.add_argument("dirs", nargs="+")
    read.add_argument("--over-ms", type=float, default=50.0)
    read.add_argument("--notes", action="store_true",
                      help="the run's other lines too")
    args = parser.parse_args(argv)
    return run_and_keep(args) if args.command == "run" else read_kept(args)


if __name__ == "__main__":
    sys.exit(main())
