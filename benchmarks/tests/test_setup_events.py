"""The five readers of set-up (``benchmarks/lib/setup_events.py``) on files a
CPU ``fit`` wrote: a loop shaped as the benchmark's (``jit(init)``,
``shard_params``, two lambdas, ``make_train_step`` lowered and compiled, one
warm-up step, set-up's report), run over an empty persistent cache and again
over the full one, with one step of the window that recompiles."""
from __future__ import annotations

import json
import os
import sys
import time

import cloudpickle
import pytest

from benchmarks.lib import cells, setup_events, train_events

ROOT = cells.ROOT
# The worker cannot import this module: the loop goes to it by value.
cloudpickle.register_pickle_by_value(sys.modules[__name__])
WINDOW, RECOMPILES = 4, 3  # steps after set-up; the one that takes a new shape


def loop(config):
    t_loop = time.time()
    import jax
    import jax.numpy as jnp
    import optax

    if config["root"] not in sys.path:
        sys.path.insert(0, config["root"])
    from benchmarks.lib.checks import CompileCounter
    from ray_tpu import train
    from ray_tpu.parallel import MeshSpec, shard_params

    jax.config.update("jax_compilation_cache_dir", config["cache"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter().install()
    mesh = MeshSpec().build()

    def dense(x):  # a jit inside the jits, traced once a shape
        time.sleep(0.02)
        return jnp.tanh(x)

    dense = jax.jit(dense)

    def init(key):
        time.sleep(0.15)
        return {"w": jax.random.normal(key, (16, 16)), "b": jnp.zeros((16,))}

    def forward(p, x):
        time.sleep(0.15)
        return dense(x @ p["w"] + p["b"])

    params = jax.jit(init)(jax.random.PRNGKey(0))
    params = shard_params(params, mesh)
    x = jnp.ones((4, 16))
    jax.jit(lambda p, x: forward(p, x)[0])(params, x)
    jax.jit(lambda p, x: forward(p, x)[-1] * 2)(params, x)
    tx = optax.sgd(0.1)
    opt_state = tx.init(params)
    step = train.make_train_step(lambda p, x: (forward(p, x) ** 2).mean(), tx)
    compiled = step.lower(params, opt_state, x).compile()
    params, opt_state, loss = compiled(params, opt_state, x)
    float(loss)
    train.report({"kind": "setup", "t_loop": t_loop, "t_ready": time.time(),
                  "compiles": compiles.snapshot()})
    for i in range(1, WINDOW + 1):
        if i == RECOMPILES:
            params, opt_state, loss = step(params, opt_state, jnp.ones((8, 16)))
        else:
            params, opt_state, loss = compiled(params, opt_state, x)
        train.report({"kind": "step", "loss": float(loss)})
    train.report({"kind": "final", "compiles": compiles.snapshot()})


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """{"cold", "warm"}: the run record the readers take, a fit each."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cache = str(tmp_path_factory.mktemp("jax_cache"))
    out = {}
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        for name in ("cold", "warm"):
            out_dir = str(tmp_path_factory.mktemp(name))
            t_command = time.time()
            result = JaxTrainer(
                loop, train_loop_config={"cache": cache, "root": ROOT},
                scaling_config=ScalingConfig(num_workers=1),
                run_config=RunConfig(name=name,
                                     storage_path=os.path.join(out_dir, "results")),
            ).fit()
            assert result.error is None
            history = result.metrics_history
            out[name] = {"out_dir": out_dir, "t_command": t_command, "notes": [],
                         "setup": history[0], "final": history[-1], "steps": []}
    finally:
        ray_tpu.shutdown()
    return out


def read(name, run):
    directory = os.path.join(cells.BENCH_DIR, "layer_metrics")
    return cells.load_reader(directory, name).read(run)


def note(run):
    said = [n for n in run["notes"] if n.startswith("set-up by function: ")]
    assert len(said) == 1  # once, whatever the number of readers
    return json.loads(said[0].split(": ", 1)[1])


def test_the_five_read_a_cold_and_a_warm_set_up(runs):
    cold = {name: read(name, runs["cold"]) for name in setup_events.NAMES}
    warm = {name: read(name, runs["warm"]) for name in setup_events.NAMES}
    for values, run in ((cold, runs["cold"]), (warm, runs["warm"])):
        setup_s = run["setup"]["t_ready"] - run["t_command"]
        worker_ready_s = run["setup"]["t_loop"] - run["t_command"]
        # init, two lambdas and train_step sleep 0.15 s each while traced.
        assert 0.6 <= values["step.trace_s"] < setup_s
        assert 0 < values["step.lower_s"] < setup_s
        assert 0 < values["step.executables_s"] < setup_s
        assert 0 < values["mesh.shard_params_s"] < 5
        assert worker_ready_s + sum(v for k, v in values.items()
                                    if k != "step.cache_misses") <= setup_s
    # The cold one compiled and wrote every executable; the warm one none.
    assert cold["step.cache_misses"] == runs["cold"]["setup"]["compiles"]["requests"] >= 4
    assert warm["step.cache_misses"] == 0
    assert runs["warm"]["setup"]["compiles"]["hits"] \
        == runs["warm"]["setup"]["compiles"]["requests"]
    assert warm["step.executables_s"] < cold["step.executables_s"]


def test_set_up_is_said_by_function_once(runs):
    for name, cache in (("cold", "miss"), ("warm", "hit")):
        run = runs[name]
        for metric in setup_events.NAMES:
            read(metric, run)
        said = note(run)
        # The loop's four, in the order made (the process's first eager
        # operation, whose lowering starts MLIR, may be said before them).
        rows = [r for r in said["executables"] if r[1] >= 0.15]
        assert [r[0] for r in rows] == ["init", "<lambda>", "<lambda>", "train_step"]
        for function, trace_s, lower_s, backend_s, held in said["executables"]:
            assert lower_s > 0 and backend_s > 0 and held == cache
        small = said["small"]
        assert small["count"] >= 1 and small["hits" if cache == "hit" else "misses"] >= 1
        assert small["misses" if cache == "hit" else "hits"] == 0
        # dense is traced inside the first caller and kept for the others.
        inner = {r[0]: r for r in said["inner_traces"]}
        assert inner["dense"][1] == 1 and inner["dense"][2] >= 0.02
        # (A cache read under the program's floor of a millisecond leaves
        # no event of its own: it is in the short tally.)
        assert 4 <= said["events"]["backend"] <= run["setup"]["compiles"]["backend_compiles"]
        assert said["events"]["ray_tpu.parallel.shard_params"] == 1
        assert said["events"].get("cache_" + cache) == run["setup"]["compiles"]["requests"]
        assert said["dropped"] == 0 and said["short"]["trace"][0] >= 1
        assert said["covered_s"] <= sum(said["stages_s"].values()) + 5e-3
        assert said["no_span_s"] == pytest.approx(
            said["setup_s"] - said["worker_ready_s"] - said["covered_s"], abs=2e-3)
        assert said["no_span_s"] >= 0
        # The step that recompiled: the window's third turn took a new shape.
        late = said["compiled_after_setup"]
        assert late["count"] == len(late["first"]) >= 3
        steps = [row for row in late["first"] if row[1] in ("train_step", "jit(train_step)")]
        assert [row[0] for row in steps] == ["trace", "lower", "backend"]
        assert {row[3] for row in late["first"]} == {RECOMPILES}
        after = run["final"]["compiles"]["backend_compiles"] \
            - run["setup"]["compiles"]["backend_compiles"]
        assert sum(1 for row in late["first"] if row[0] == "backend") == after >= 1


def test_the_five_are_declared_for_every_cell_and_move_setup_s():
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in setup_events.NAMES}
    for name in setup_events.NAMES:
        m = declared[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] == "setup_s" and m["better"] == "lower" and m["layer"] in layers
        assert m["layer"] == ("mesh and sharding" if name.startswith("mesh.") else "train step")
        assert (m["unit"], m["source"]) == (
            ("count", "program_counter") if name == "step.cache_misses"
            else ("s", "program_span"))
    assert declared["step.compile_s"]["moves"] == "setup_s"  # it stays


def test_a_parents_file_or_none_gives_nothing_and_says_nothing(runs, tmp_path):
    """A parent commit under these files: its record holds no set-up span."""
    path = os.path.join(str(tmp_path), train_events.FILE)
    os.makedirs(os.path.dirname(path))
    with open(os.path.join(runs["warm"]["out_dir"], train_events.FILE)) as f:
        kept = [text for text in f if "ray_tpu.compile." not in text
                and "ray_tpu.parallel." not in text]
    with open(path, "w") as f:
        f.writelines(kept)
    for out_dir in (str(tmp_path), str(tmp_path / "absent")):
        run = {**runs["warm"], "out_dir": out_dir, "notes": []}
        for stale in ("setup_events", "setup_metrics", "train_events"):
            run.pop(stale, None)
        assert [read(name, run) for name in setup_events.NAMES] == [None] * 5
        assert run["notes"] == []


def test_the_window_readers_read_the_same_file_unchanged(runs):
    """``train_events._load`` takes the file whole: every set-up span is one
    more of its ``spans``, an interval under its name."""
    path = os.path.join(runs["warm"]["out_dir"], train_events.FILE)
    record = train_events._load(path)
    mine = setup_events.load({"out_dir": runs["warm"]["out_dir"]})
    theirs = [s for s in record["spans"]
              if s[0].startswith(("ray_tpu.compile.", "ray_tpu.parallel."))]
    assert sorted((s[0], s[1], s[2], s[3]) for s in theirs) \
        == sorted((s[0], s[1], s[2], s[3]) for s in mine)
    assert [r["ordinal"] for r in record["reports"]] == list(range(WINDOW + 2))
    json.dumps(mine)  # --keep dumps the run record
