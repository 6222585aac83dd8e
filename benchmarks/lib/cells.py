"""Finding a cell's files by the names in ``BENCHMARK.json``.

Whatever belongs to one configuration, one traffic mix or one metric sits
in a file of its own; this module only joins them. A later PR adds files
and entries and edits none of these.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(dotted: str):
    """``package.module:name`` -> the object."""
    module, _, name = dotted.partition(":")
    if not name:
        raise ValueError(f"{dotted!r} is not of the form module:name")
    return getattr(importlib.import_module(module), name)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry of ``BENCHMARK.json`` joined with its configuration
    file and its traffic file, and the metrics that exist in it."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json (known: {sorted(cells)})"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    if "kernels" not in config:
        raise KeyError(
            f"{configs[cell['config']]['file']} names no \"kernels\" function: "
            "which Pallas kernels its step holds is the configuration's to "
            "state, and there is no default"
        )
    traffic = load_json(
        os.path.join(root, "benchmarks", "traffic", cell["traffic"] + ".json")
    )

    def in_cell(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload,
        "chips": cell["chips"],
        "config_name": cell["config"],
        "traffic_name": cell["traffic"],
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in bench["per_layer"] if in_cell(m)],
    }


def rehearsed(cell: dict) -> dict:
    """The cell at its files' own ``rehearsal`` sizes: tiny shapes for a
    CPU run of the control flow. Widths live in the configuration, batch
    and sequence in the traffic."""
    out = dict(cell)
    for key in ("config", "traffic"):
        out[key] = {**cell[key], **cell[key].get("rehearsal", {})}
    return out


def stated_kernels(cell: dict) -> dict:
    """``{kernel name: {"least": n, "call": (FLOPs, HBM bytes)}}`` as the
    configuration's ``kernels`` function states it for this cell's traffic
    and mesh (``benchmarks/lib/kernels_flash.py`` says what each means)."""
    return resolve(cell["config"]["kernels"])(cell["config"], cell["traffic"])


def program_config(config: dict):
    """The program's config object, built from the configuration file:
    ``program.fields`` maps the program's field to the source's key and
    ``program.set`` gives what only the program has (dtypes by name)."""
    import jax.numpy as jnp

    program = config["program"]
    kwargs = {
        field: config[key] for field, key in program["fields"].items()
        if config.get(key) is not None
    }
    for field, value in program.get("set", {}).items():
        kwargs[field] = getattr(jnp, value) if field.endswith("dtype") else value
    return resolve(program["config"])(**kwargs)


def load_reader(directory: str, name: str):
    """The metric's reader, ``<directory>/<metric name>.py``: a module with
    ``read(run)``, which returns a number, or None where there is nothing
    to read. Unit, source, layer and the cells a metric exists in are
    ``BENCHMARK.json``'s to say, and are said nowhere else."""
    path = os.path.join(directory, name + ".py")
    if not os.path.exists(path):
        raise KeyError(
            f"BENCHMARK.json declares {name!r} and {directory} holds no "
            "reader of that name"
        )
    spec = importlib.util.spec_from_file_location(
        "benchmarks_reader_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_metrics(declared: list, directory: str, run: dict) -> dict:
    """``{name: {"value", "unit"}}`` for the cell's declared metrics whose
    reader finds something to read."""
    out = {}
    for metric in declared:
        value = load_reader(directory, metric["name"]).read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
