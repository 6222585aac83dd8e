"""What keeps the chip path honest without a chip: chip_smoke.py refuses
the CPU, the compile cache has one placed directory, interpret mode is
refused on a TPU backend, and a TPU worker sees exactly the chips it was
granted — on the head-local and the raylet spawn path — and is retired
after its one task."""
import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu.cluster_utils import DaemonCluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_script(name, tmp_path, **env):
    full = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    full.update(env, TMPDIR=str(tmp_path))
    return subprocess.run(
        [sys.executable, os.path.join(REPO, name)],
        env=full, capture_output=True, text=True, timeout=120, cwd=REPO,
    )


@pytest.mark.parametrize(
    "env",
    [{"JAX_PLATFORMS": "cpu"}, {"JAX_PLATFORMS": "cpu,tpu"},
     {"JAX_PLATFORMS": "tpu,cpu", "RAY_TPU_PALLAS_INTERPRET": "1"}],
    ids=["cpu", "cpu-first", "interpret"],
)
def test_chip_smoke_refuses_before_spawning_anything(tmp_path, env):
    out = _run_script("chip_smoke.py", tmp_path, **env)
    assert out.returncode != 0
    assert out.stdout == ""  # no result line
    assert "chip" in out.stderr
    # The session directory lives under TMPDIR: none means no ray_tpu.init,
    # so no worker was started.
    assert not (tmp_path / "ray_tpu").exists()


def test_chip_smoke_last_line_holds_ok_and_device_only():
    """main() with the device phases stubbed out: whatever the summary
    carries, the last line of stdout is {"ok", "device"} and no more."""
    import json

    code = (
        "import sys, chip_smoke\n"
        "chip_smoke.run = lambda ray_tpu: {\n"
        "    'ok': True,\n"
        "    'device': {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1},\n"
        "    'phases': {'one_chip': {}}, 'claim': None}\n"
        "sys.exit(chip_smoke.main())\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, text=True, timeout=120,
        capture_output=True, env=env,
    )
    assert out.returncode == 0, out.stderr
    summary, last = out.stdout.splitlines()[-2:]
    assert summary.startswith("chip_smoke: summary: ")
    assert summary.endswith('"claim": null}')
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_compile_cache_is_placed_once():
    from ray_tpu._private.accelerators.tpu import place_compile_cache

    env = {"JAX_COMPILATION_CACHE_DIR": "/placed/from/outside"}
    assert place_compile_cache(env) == "/placed/from/outside"
    assert env == {"JAX_COMPILATION_CACHE_DIR": "/placed/from/outside"}

    env = {}
    assert place_compile_cache(env) == os.path.join(REPO, ".jax_cache")
    assert env == {"JAX_COMPILATION_CACHE_DIR": os.path.join(REPO, ".jax_cache")}
    # The same directory from any process and any working directory.
    code = (
        "from ray_tpu._private.accelerators.tpu import place_compile_cache;"
        "print(place_compile_cache({}))"
    )
    seen = {
        subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, text=True, timeout=60,
            capture_output=True, check=True,
            env={**os.environ, "PYTHONPATH": REPO},
        ).stdout.strip()
        for cwd in (REPO, "/")
    }
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_interpret_mode_refused_on_tpu_backend(monkeypatch):
    from ray_tpu.ops import attention, gmm

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    assert attention._interpret() is True  # CPU backend: the test path
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="interpret mode"):
        attention._interpret()
    with pytest.raises(RuntimeError, match="interpret mode"):
        gmm._interpret()  # one switch for both kernel modules


def test_visible_chips_env():
    from ray_tpu._private.accelerators import TPUAcceleratorManager as M

    env = {}
    M.set_visible_accelerator_ids(env, ["2"], host_chips=4)
    assert env == {
        "TPU_VISIBLE_CHIPS": "2", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
    }
    # The whole host: the machine's own TPU environment stands.
    env = {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}
    M.set_visible_accelerator_ids(env, ["0", "1", "2", "3"], host_chips=4)
    assert env == {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}
    with pytest.raises(ValueError):
        M.set_visible_accelerator_ids({}, ["0", "1", "2"], host_chips=4)


def test_chip_table_hands_over_after_exit():
    from ray_tpu._private.accelerators.tpu import ChipTable

    table = ChipTable(2)
    holder = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(600)"])
    try:
        chips = table.reserve(2)
        assert chips == [0, 1]
        table.bind(chips, holder)
        assert table.reserve(1) is None  # held by a live process
        holder.kill()
        holder.wait(timeout=30)
        assert table.reserve(2) == [0, 1]  # free once it has exited
        assert table.reserve(1) is None  # reserved for a starting process
        table.release([0])
        assert table.reserve(1) == [0]
    finally:
        holder.kill()


def _probes():
    """(function, actor class) reporting a worker's pid and TPU env;
    local definitions, so they pickle by value into any worker."""

    def tpu_env():
        import os

        return (
            os.getpid(),
            {
                k: os.environ.get(k)
                for k in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS",
                          "JAX_COMPILATION_CACHE_DIR")
            },
        )

    class ChipHolder:
        def env(self):
            return tpu_env()

    return tpu_env, ChipHolder


def test_head_local_tpu_worker_sees_its_granted_chips(monkeypatch):
    _tpu_env, ChipHolder = _probes()
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "4")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ray_tpu.init(num_cpus=2)
    try:
        assert ray_tpu.cluster_resources()["TPU"] == 4.0
        one = ray_tpu.remote(num_tpus=1)(_tpu_env)
        pid_a, env = ray_tpu.get(one.remote(), timeout=120)
        assert env == {
            "TPU_VISIBLE_CHIPS": "0", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
            "JAX_COMPILATION_CACHE_DIR": os.path.join(REPO, ".jax_cache"),
        }
        # Retired after its one task: the next task gets a new process.
        pid_b, _ = ray_tpu.get(one.remote(), timeout=120)
        assert pid_b != pid_a

        # Two one-chip actors alive together hold distinct chips.
        holder = ray_tpu.remote(num_tpus=1)(ChipHolder)
        a, b = holder.remote(), holder.remote()
        (_, env_a), (_, env_b) = ray_tpu.get(
            [a.env.remote(), b.env.remote()], timeout=120
        )
        assert {env_a["TPU_VISIBLE_CHIPS"], env_b["TPU_VISIBLE_CHIPS"]} <= set("0123")
        assert env_a["TPU_VISIBLE_CHIPS"] != env_b["TPU_VISIBLE_CHIPS"]

        # A grant of the whole host waits for both holders' processes to
        # be gone, then sees every chip: no restriction in its env.
        ray_tpu.kill(a)
        ray_tpu.kill(b)
        _, env = ray_tpu.get(
            ray_tpu.remote(num_tpus=4)(_tpu_env).remote(), timeout=120
        )
        assert env["TPU_VISIBLE_CHIPS"] is None
        assert env["TPU_CHIPS_PER_HOST_BOUNDS"] is None
    finally:
        ray_tpu.shutdown()


def test_train_worker_holds_the_chips_of_its_bundle(monkeypatch):
    """The TrainWorker asks for what its bundle reserved, so it is spawned
    TPU-visible with those chips (it used to ask for nothing and came up
    pinned to the CPU inside a TPU bundle)."""
    from ray_tpu import train

    def loop():
        import os

        from ray_tpu import train

        train.report({
            k: os.environ.get(k)
            for k in ("TPU_VISIBLE_CHIPS", "JAX_COMPILATION_CACHE_DIR")
        })

    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "4")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ray_tpu.init(num_cpus=2)
    try:
        result = train.JaxTrainer(
            loop,
            scaling_config=train.ScalingConfig(num_workers=1, use_tpu=True),
        ).fit()
        assert result.error is None
        assert result.metrics == {
            "TPU_VISIBLE_CHIPS": "0",
            "JAX_COMPILATION_CACHE_DIR": os.path.join(REPO, ".jax_cache"),
        }
        # The worker is gone with its chip: all four are free again.
        assert ray_tpu.available_resources().get("TPU") == 4.0
    finally:
        ray_tpu.shutdown()


def test_raylet_tpu_worker_sees_its_granted_chips():
    _tpu_env, _ = _probes()
    cluster = DaemonCluster(head_node_args={"num_cpus": 1, "tcp_port": 0})
    try:
        cluster.add_node(num_cpus=2, resources={"TPU": 4.0})
        # From the driver a TPU task is head-routed: the head asks the
        # node's daemon for a worker and the daemon grants the chips.
        _, env = ray_tpu.get(
            ray_tpu.remote(num_tpus=2)(_tpu_env).remote(), timeout=120
        )
        assert env["TPU_VISIBLE_CHIPS"] == "0,1"
        assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
        _, env = ray_tpu.get(
            ray_tpu.remote(num_tpus=4)(_tpu_env).remote(), timeout=120
        )
        assert env["TPU_VISIBLE_CHIPS"] is None
    finally:
        cluster.shutdown()
