"""Runtime environments + accelerator manager.

Models the reference's python/ray/tests/test_runtime_env*.py and
accelerator manager unit tests.
"""
import os

import pytest

import ray_tpu


@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_env_vars_task(cluster):
    @ray_tpu.remote(runtime_env={"env_vars": {"MY_RE_FLAG": "hello"}})
    def read_env():
        return os.environ.get("MY_RE_FLAG")

    assert ray_tpu.get(read_env.remote()) == "hello"

    @ray_tpu.remote
    def read_plain():
        return os.environ.get("MY_RE_FLAG")

    # Restored after the task: pooled workers don't leak the env.
    assert ray_tpu.get(read_plain.remote()) is None


def test_env_vars_actor_lifetime(cluster):
    @ray_tpu.remote(runtime_env={"env_vars": {"ACTOR_FLAG": "on"}})
    class EnvActor:
        def read(self):
            return os.environ.get("ACTOR_FLAG")

    a = EnvActor.remote()
    assert ray_tpu.get(a.read.remote()) == "on"
    assert ray_tpu.get(a.read.remote()) == "on"  # persists across calls


def test_working_dir_ships_code(cluster, tmp_path):
    pkg = tmp_path / "mypkg"
    pkg.mkdir()
    (pkg / "helper_mod.py").write_text("def value():\n    return 'shipped'\n")
    (pkg / "data.txt").write_text("file-content")

    @ray_tpu.remote(runtime_env={"working_dir": str(pkg)})
    def use_pkg():
        import helper_mod  # importable from the shipped dir

        with open("data.txt") as f:  # cwd is the shipped dir
            data = f.read()
        return helper_mod.value(), data

    assert ray_tpu.get(use_pkg.remote()) == ("shipped", "file-content")


def test_py_modules(cluster, tmp_path):
    mod = tmp_path / "extra_mod_dir"
    mod.mkdir()
    (mod / "extra_util.py").write_text("X = 41\n")

    @ray_tpu.remote(runtime_env={"py_modules": [str(mod)]})
    def use_module():
        import extra_util

        return extra_util.X + 1

    assert ray_tpu.get(use_module.remote()) == 42


def test_invalid_runtime_env_key(cluster):
    with pytest.raises(ValueError, match="Unsupported runtime_env"):

        @ray_tpu.remote(runtime_env={"no_such_plugin": ["x"]})
        def f():
            return 1

        f.remote()


# ------------------------------------------------------------ accelerators
def test_tpu_manager_detection_env_override(monkeypatch):
    from ray_tpu._private.accelerators import TPUAcceleratorManager as M

    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "4")
    assert M.get_current_node_num_accelerators() == 4


def test_tpu_manager_type_and_head_resources(monkeypatch):
    from ray_tpu._private.accelerators import TPUAcceleratorManager as M

    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-16")
    monkeypatch.setenv("TPU_NAME", "mypod")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    assert M.get_current_node_accelerator_type() == "v5e"
    extra = M.get_current_node_additional_resources()
    assert extra == {"TPU-pod-mypod": 1.0, "TPU-v5e-head": 1.0}
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    assert "TPU-v5e-head" not in M.get_current_node_additional_resources()


def test_tpu_visible_chips_bounds():
    from ray_tpu._private.accelerators import TPUAcceleratorManager as M

    env = {}
    M.set_visible_accelerator_ids(env, ["0", "1"], host_chips=4)
    assert env["TPU_VISIBLE_CHIPS"] == "0,1"
    assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"


# ----------------------------------------------------- plugins (pip etc.)
def _make_wheel(tmp_path, name="tinypkg", version="1.0", body="VALUE = 42\n"):
    """Hand-rolled wheel (a zip with dist-info) — installable offline."""
    import base64
    import hashlib
    import zipfile

    whl = tmp_path / f"{name}-{version}-py3-none-any.whl"
    dist = f"{name}-{version}.dist-info"
    files = {
        f"{name}/__init__.py": body,
        f"{dist}/METADATA": (
            f"Metadata-Version: 2.1\nName: {name}\nVersion: {version}\n"
        ),
        f"{dist}/WHEEL": (
            "Wheel-Version: 1.0\nGenerator: test\nRoot-Is-Purelib: true\n"
            "Tag: py3-none-any\n"
        ),
    }
    records = []
    with zipfile.ZipFile(whl, "w") as zf:
        for path, content in files.items():
            data = content.encode()
            zf.writestr(path, data)
            digest = base64.urlsafe_b64encode(
                hashlib.sha256(data).digest()
            ).rstrip(b"=").decode()
            records.append(f"{path},sha256={digest},{len(data)}")
        records.append(f"{dist}/RECORD,,")
        zf.writestr(f"{dist}/RECORD", "\n".join(records) + "\n")
    return str(whl)


def test_pip_plugin_venv_isolation(cluster, tmp_path):
    """A pip runtime_env installs into a cached venv whose packages are
    importable ONLY inside tasks carrying that env (reference:
    _private/runtime_env/pip.py)."""
    wheel = _make_wheel(tmp_path)

    @ray_tpu.remote
    def with_pkg():
        import tinypkg

        return tinypkg.VALUE

    @ray_tpu.remote
    def without_pkg():
        try:
            import tinypkg  # noqa: F401

            return "leaked"
        except ImportError:
            return "isolated"

    env = {"pip": [wheel]}
    assert ray_tpu.get(
        with_pkg.options(runtime_env=env).remote(), timeout=120
    ) == 42
    assert ray_tpu.get(without_pkg.remote(), timeout=60) == "isolated"


def test_pip_plugin_bad_requirement_fails_loudly(cluster):
    @ray_tpu.remote
    def f():
        return 1

    with pytest.raises(ray_tpu.exceptions.RayTaskError) as ei:
        ray_tpu.get(
            f.options(
                runtime_env={"pip": ["/nonexistent/nowhere-9.9.whl"]}
            ).remote(),
            timeout=120,
        )
    assert "pip" in str(ei.value)


def test_container_plugin_gated(cluster):
    @ray_tpu.remote
    def f():
        return 1

    # No docker/podman on this host: rejected at validation time.
    with pytest.raises(ValueError):
        f.options(runtime_env={"container": {"image": "x"}}).remote()
