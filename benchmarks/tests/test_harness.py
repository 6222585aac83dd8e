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
    setup = {
        "kind": "setup", "rehearsal": False, "platform": "tpu",
        "device_kind": "TPU v5 lite", "device_count": cell["chips"],
        "mesh": {a: s for a, s in cell["traffic"]["mesh"].items() if s > 1},
        "moe_dispatch": cell["traffic"].get("expect", {}).get("moe_dispatch"),
        # what the configuration's function states, and a kept remat replay
        "pallas_kernels": {k: (1 + (k == "_fwd_kernel")) * stated["least"]
                           for k, stated in cells.stated_kernels(cell).items()},
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
    # an OLMoE step whose expert layer says "gmm" and holds no grouped matmul
    cell = cells.load_cell("olmoe-1b-7b-1chip.dropless-4k")
    run = made_up_run(cell)
    assert run["setup"]["pallas_kernels"]["_gmm_kernel"] == 18
    assert result.result_line(run)[0]["correct"] is True
    del run["setup"]["pallas_kernels"]["_gmm_kernel"]
    assert result.result_line(run)[0]["correct"] is False
    run = made_up_run(cell)
    run["setup"]["pallas_kernels"]["_tgmm_kernel"] = 8  # one of nine missing
    assert result.result_line(run)[0]["correct"] is False


def checkout(tmp_path):
    """A copy of the benchmark's files for a test to add to."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(cells.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_new_files_are_found_without_an_edit(tmp_path):
    root = checkout(tmp_path)
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


HYBRID_KERNELS = '''
from benchmarks.lib.flops import FLASH_MATMULS, flash_call
from benchmarks.lib.flops_gmm import gmm_call


def required(config, seq):
    return 1.5e9  # made up


def hybrid(config, traffic):
    full = config["full_attention_layers"]
    bh = traffic["batch"] * config["num_attention_heads"]
    stated = {
        kernel: {"least": full, "call": flash_call(
            kernel, bh, traffic["seq"], traffic["seq"], config["qk_head_dim"],
            causal=True, d_v=config["v_head_dim"])}
        for kernel in FLASH_MATMULS
    }
    # forward and backward of the other mixer, at a made-up cost
    stated["_delta_chunk_kernel"] = {
        "least": 2 * (config["num_hidden_layers"] - full), "call": (4e12, 6e8)}
    held = config["num_experts_held"]
    pairs = (traffic["batch"] * traffic["seq"] * config["num_experts_per_token"]
             * held // config["num_routed_experts"])
    for kernel, calls in (("_gmm_kernel", 6), ("_tgmm_kernel", 3)):
        stated[kernel] = {
            "least": calls * config["num_hidden_layers"],
            "call": gmm_call(kernel, pairs, config["hidden_size"],
                             config["moe_intermediate_size"], held)}
    return stated
'''


def test_a_hybrid_is_taken_judged_and_measured_by_new_files_alone(tmp_path, monkeypatch):
    """4 layers of which 1 calls the flash kernels at q/k 192, v 128 and 3
    call a kernel of another name; 256 routed experts of which 16 are held.
    Its configuration, traffic and ``kernels`` function are new files."""
    import sys

    import benchmarks.lib
    from benchmarks.lib.trace import Event, Trace

    root = checkout(tmp_path)
    (root / "benchmarks/configs/hybrid-l4.json").write_text(json.dumps({
        "name": "hybrid-l4", "source": "https://example.org/hybrid",
        "hidden_size": 2304, "num_hidden_layers": 4, "full_attention_layers": 1,
        "num_attention_heads": 32, "qk_head_dim": 192, "v_head_dim": 128,
        "num_routed_experts": 256, "num_experts_held": 16,
        "num_experts_per_token": 8, "moe_intermediate_size": 1024,
        "reduced": {"num_hidden_layers": {}},
        "required_flops": "benchmarks.lib.kernels_hybrid:required",
        "kernels": "benchmarks.lib.kernels_hybrid:hybrid"}))
    (root / "benchmarks/traffic/b2s8k.json").write_text(json.dumps({
        "name": "b2s8k", "mesh": {}, "batch": 2, "seq": 8192, "batches": 16}))
    (root / "benchmarks/lib/kernels_hybrid.py").write_text(HYBRID_KERNELS)
    bench = benchmark()
    bench["configs"].append({
        "name": "hybrid-l4", "source": "https://example.org/hybrid",
        "file": "benchmarks/configs/hybrid-l4.json",
        "reduced": ["num_hidden_layers"], "why": "a test"})
    bench["workloads"].append({
        "name": "hybrid-l4.b2s8k", "config": "hybrid-l4", "traffic": "b2s8k",
        "chips": 1, "why": "a test"})
    for metric in bench["per_layer"]:
        if metric["name"].startswith("kernel.gmm_"):
            metric["workloads"] = metric["workloads"] + ["hybrid-l4.b2s8k"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # no file that is there is edited
    for directory, _, files in os.walk(cells.BENCH_DIR):
        for name in files:
            if "__pycache__" in directory:
                continue
            there = os.path.join(directory, name)
            copy = root / "benchmarks" / os.path.relpath(there, cells.BENCH_DIR)
            assert copy.read_bytes() == open(there, "rb").read(), there
    # the copy's lib directory stands in for the repository's
    monkeypatch.setattr(benchmarks.lib, "__path__",
                        [*benchmarks.lib.__path__, str(root / "benchmarks/lib")])
    monkeypatch.delitem(sys.modules, "benchmarks.lib.kernels_hybrid", raising=False)

    cell = cells.load_cell("hybrid-l4.b2s8k", root=str(root))
    stated = cells.stated_kernels(cell)
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 1, "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1,
        "_delta_chunk_kernel": 6, "_gmm_kernel": 24, "_tgmm_kernel": 12}
    # judged: correct with what its function states in the step (flash in one
    # layer of four), and not with one stated kernel short
    run = made_up_run(cell)
    assert run["setup"]["pallas_kernels"]["_bwd_dq_kernel"] == 1
    assert result.result_line(run)[0]["correct"] is True
    for kernel in stated:
        run = made_up_run(cell)
        run["setup"]["pallas_kernels"][kernel] = stated[kernel]["least"] - 1
        assert result.result_line(run)[0]["correct"] is False, kernel
    # measured: two steps of a made-up trace in which every call takes twice
    # its floor as counted by hand. 64 (batch x head) blocks of 8192 x 8192,
    # causal half: the forward's two matmuls contract 192 and produce 128;
    # q and k move at 192, v and o at 128, the lse in float32.
    pairs = 64 * 8192 * 8192 // 2
    assert stated["_fwd_kernel"]["call"] == (
        2 * pairs * (192 + 128),
        64 * 2 * (2 * 8192 * 192 + 2 * 8192 * 128) + 64 * 8192 * 4)
    assert stated["_bwd_dkv_kernel"]["call"][0] == 2 * pairs * (2 * 192 + 2 * 128)
    assert stated["_bwd_dq_kernel"]["call"][0] == 2 * pairs * (2 * 192 + 128)
    # every call bound by compute on a v5e: FLOPs over 197e12
    flash_floor = 2 * pairs * (320 + 640 + 512) / 197e12
    assert flash_floor == pytest.approx(32.094e-3, rel=1e-4)
    # 2 x 8192 tokens x top-8 = 131,072 pairs, a sixteenth of them here
    assert stated["_gmm_kernel"]["call"] == (
        2 * 8192 * 2304 * 1024, 2 * (8192 * (2304 + 1024) + 16 * 2304 * 1024))
    gmm_floor = 2 * 8192 * 2304 * 1024 / 197e12
    device, host, at = [], [], 0.0
    for step in range(2):
        start = at
        for kernel, calls in (("_fwd_kernel", 1), ("_delta_chunk_kernel", 6),
                              ("_gmm_kernel", 24), ("_tgmm_kernel", 12),
                              ("_bwd_dkv_kernel", 1), ("_bwd_dq_kernel", 1)):
            flops, nbytes = stated[kernel]["call"]
            dur = 2 * max(flops / 197e12, nbytes / 819e9)
            for i in range(calls):
                device.append(Event(f"{kernel}.{step}.{i}", at, dur,
                                    f"jit(train_step)/x kernel_name={kernel}"))
                at += dur
        host.append(Event("bench.step", start, at - start))
    run = made_up_run(cell, trace=True)
    run["trace_data"], run["notes"] = Trace({0: device}, {0: []}, host), []
    kernel_metrics = [m for m in cell["per_layer"] if m["name"].startswith("kernel.")]
    assert len(kernel_metrics) == 4
    metrics = cells.read_metrics(
        kernel_metrics, str(root / "benchmarks/layer_metrics"), run)
    assert metrics["kernel.flash_roofline"]["value"] == pytest.approx(50.0)
    assert f"6 calls, 6 of them bound by compute, the rest by bytes; floor " \
           f"{2 * flash_floor:.4f} s" in run["notes"][0]
    assert metrics["kernel.gmm_roofline"]["value"] == pytest.approx(50.0)
    assert f"72 calls, 72 of them bound by compute, the rest by bytes; floor " \
           f"{72 * gmm_floor:.4f} s" in run["notes"][1]
    # counted as if every pair of the 256 experts were computed here, the
    # same trace would read 16 times the work: an impossible share
    assert 16 * metrics["kernel.gmm_roofline"]["value"] > 105
    share = 100 * (72 * 2 * gmm_floor + 2 * 2 * flash_floor) / at
    assert metrics["kernel.gmm_share"]["value"] + metrics[
        "kernel.flash_share"]["value"] == pytest.approx(share)


def test_a_configuration_without_a_kernels_function_is_an_error(tmp_path):
    root = checkout(tmp_path)
    path = root / "benchmarks/configs/mistral-7b-l4.json"
    config = json.loads(path.read_text())
    del config["kernels"]
    path.write_text(json.dumps(config))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), root)
    with pytest.raises(KeyError, match="kernels"):
        cells.load_cell("mistral-7b-l4.sft512", root=str(root))


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
