"""The harness is driven by data: a configuration, a traffic mix and a
per-layer metric added as new files are found with no edit to a file that
is there; and the result line holds exactly the contract's keys."""
import json
import os
import re
import shutil

import pytest

from benchmarks.lib import cells, result

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def benchmark():
    return cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


def made_up_run(cell, trace=False):
    """A run record as benchmarks/run.py assembles it, with made-up values."""
    n = 2 * cell["traffic"]["batches"]
    steps = [{"t_start": i * 0.1, "t_dispatch": i * 0.1 + 0.01,
              "t_done": i * 0.1 + 0.1, "loss": 9.0 - 0.01 * i}
             for i in range(n)]
    layers = cell["config"]["num_hidden_layers"]
    setup = {
        "kind": "setup", "rehearsal": False, "platform": "tpu",
        "device_kind": "TPU v5 lite", "device_count": cell["chips"],
        "mesh": {a: s for a, s in cell["traffic"]["mesh"].items() if s > 1},
        "moe_dispatch": cell["traffic"].get("expect", {}).get("moe_dispatch"),
        "pallas_kernels": {"_fwd_kernel": 2 * layers, "_bwd_dkv_kernel": layers,
                           "_bwd_dq_kernel": layers},
        "collectives": None, "step_bytes": 12 * 2**30,
        "reference": {"ok": True}, "phases": {"compile_s": 7.0},
        "t_loop": 115.0, "t_ready": 140.0, "cache_dir": "x",
        "compiles": {"requests": 13, "hits": 2, "backend_compiles": 11},
    }
    snapshot = {"requests": 13, "hits": 2, "backend_compiles": 11}
    final = {"kind": "final", "steps": steps, "traced_steps": 6 if trace else 0,
             "failed": 0, "error": None,
             "compiles_before": snapshot, "compiles_after": snapshot,
             "param_devices": list(range(cell["chips"])), "params_split": True,
             "peak_bytes_in_use": [9 * 2**30] * cell["chips"],
             "bytes_in_use": [7 * 2**30] * cell["chips"]}
    return {"cell": cell, "seed": 0, "seconds": 10, "trace": trace,
            "rehearsal": False, "t_command": 100.0, "out_dir": "/nonexistent",
            "setup": setup, "final": final, "steps": steps,
            "reported_steps": n}


@pytest.mark.parametrize("workload", [w["name"] for w in benchmark()["workloads"]])
def test_result_line_has_exactly_the_contracts_keys(workload):
    cell = cells.load_cell(workload)
    line, notes = result.result_line(made_up_run(cell))
    assert tuple(line) == result.KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 2 * cell["traffic"]["batches"]
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    declared = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    for name, metric in line["metrics"].items():
        assert set(metric) == {"value", "unit"}
        assert metric["unit"] == declared[name] and metric["value"] > 0
    assert line["metrics"]["setup_s"]["value"] == 40.0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["memory_peak_bytes"] == 12 * 2**30
    json.dumps(line)
    # a traced run with no trace to read still reports the span metrics
    line, _ = result.result_line(made_up_run(cell, trace=True))
    assert "trainer.step_gap_ms" in line["metrics"]
    assert "tokens_per_s_per_chip" not in line["metrics"]
    assert set(line["metrics"]) <= {m["name"] for m in cell["per_layer"]}


def test_a_stalled_step_moves_the_throughput_and_not_the_median_step_rate():
    cell = cells.load_cell("mistral-7b-l4.sft512")
    tokens = cell["traffic"]["batch"] * cell["traffic"]["seq"]
    steady, _ = result.result_line(made_up_run(cell))
    # every step counts: attempted x tokens a step over the seconds they took
    assert steady["metrics"]["tokens_per_s_per_chip"]["value"] == pytest.approx(
        steady["attempted"] * tokens / 3.2)
    run = made_up_run(cell, trace=True)
    for step in run["steps"][10:]:  # the eleventh step takes 1.5 s longer
        for key in ("t_start", "t_dispatch", "t_done"):
            step[key] += 1.5
    run["steps"][10]["t_start"] -= 1.5
    traced, _ = result.result_line(run)
    assert traced["metrics"]["trainer.stall_share"]["value"] == pytest.approx(
        100 * 1.5 / (3.2 + 1.5))
    assert traced["metrics"]["trainer.median_step_tokens_per_s"]["value"] == (
        pytest.approx(tokens / 0.1))
    run["trace"] = False
    stalled, _ = result.result_line(run)
    for name in ("tokens_per_s_per_chip", "mfu_required"):
        assert stalled["metrics"][name]["value"] == pytest.approx(
            steady["metrics"][name]["value"] * 3.2 / (3.2 + 1.5))


def test_a_wrong_run_is_not_correct():
    cell = cells.load_cell("mistral-7b-l4.sft512")
    run = made_up_run(cell)
    run["final"]["compiles_after"] = {"requests": 14, "hits": 2, "backend_compiles": 12}
    assert result.result_line(run)[0]["correct"] is False
    run = made_up_run(cell)
    run["steps"][3]["loss"] = float("nan")
    line, _ = result.result_line(run)
    assert (line["correct"], line["failed"]) == (False, 1)
    run = made_up_run(cell)
    run["setup"]["pallas_kernels"]["_bwd_dq_kernel"] = 0
    assert result.result_line(run)[0]["correct"] is False
    run = made_up_run(cell)
    for i, step in enumerate(run["steps"]):
        step["loss"] = 9.0 + 0.01 * i
    assert result.result_line(run)[0]["correct"] is False


def test_new_files_are_found_without_an_edit(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(cells.BENCH_DIR, root / "benchmarks")
    bench = benchmark()
    # a configuration, a traffic mix and a per-layer metric, each a new file
    config = cells.load_json(f"{cells.BENCH_DIR}/configs/mistral-7b-l4.json")
    config["name"], config["num_hidden_layers"] = "mistral-7b-l3", 3
    (root / "benchmarks/configs/mistral-7b-l3.json").write_text(json.dumps(config))
    traffic = cells.load_json(f"{cells.BENCH_DIR}/traffic/sft512.json")
    traffic["name"], traffic["seq"] = "sft1k", 1024
    (root / "benchmarks/traffic/sft1k.json").write_text(json.dumps(traffic))
    (root / "benchmarks/layer_metrics/trainer.steps.py").write_text(
        'def read(run):\n    return len(run["steps"])\n'
    )
    # and the entries that name them
    bench["configs"].append({
        "name": "mistral-7b-l3", "source": config["source"],
        "file": "benchmarks/configs/mistral-7b-l3.json",
        "reduced": ["num_hidden_layers"], "why": "a test"})
    bench["workloads"].append({
        "name": "mistral-7b-l3.sft1k", "config": "mistral-7b-l3",
        "traffic": "sft1k", "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "trainer.steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "tokens_per_s_per_chip", "workloads": ["mistral-7b-l3.sft1k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell("mistral-7b-l3.sft1k", root=str(root))
    assert cell["config"]["num_hidden_layers"] == 3
    assert cell["traffic"]["seq"] == 1024
    assert "trainer.steps" in {m["name"] for m in cell["per_layer"]}
    run = made_up_run(cell, trace=True)
    run["trace_data"], run["notes"] = None, []
    metrics = cells.read_metrics(
        cell["per_layer"], str(root / "benchmarks/layer_metrics"), run
    )
    assert metrics["trainer.steps"] == {"value": 32.0, "unit": "steps"}
    # the new metric exists only in the cell that lists it
    old = cells.load_cell("mistral-7b-l4.sft512", root=str(root))
    assert "trainer.steps" not in {m["name"] for m in old["per_layer"]}
    # and the new cell builds its program's config from the new file
    assert cells.program_config(cell["config"]).num_layers == 3
    # required FLOPs follow the new files too
    flops = cells.resolve(cell["config"]["required_flops"])
    assert flops(cell["config"], 1024) < flops(old["config"], 1024)


def test_benchmark_json_meets_the_contract():
    bench = benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        held = cells.load_json(os.path.join(cells.ROOT, c["file"]))
        assert held["source"] == c["source"]
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(f"{cells.BENCH_DIR}/traffic/{w['traffic']}.json")
    assert len(pairs) == len(bench["workloads"]) >= 2
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    names = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
    every = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= names


def test_every_declared_metric_has_a_reader_and_every_reader_is_declared():
    bench = benchmark()
    for key, directory in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        directory = os.path.join(cells.BENCH_DIR, directory)
        files = {f[:-3] for f in os.listdir(directory) if f.endswith(".py")}
        assert files == {m["name"] for m in bench[key]}
        for m in bench[key]:
            assert callable(cells.load_reader(directory, m["name"]).read)
    with pytest.raises(KeyError):
        cells.load_reader(directory, "no.such_metric")
