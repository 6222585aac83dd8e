"""Serve: deployments, handles, routing, autoscaling, HTTP, batching.

Models the reference's serve test coverage (python/ray/serve/tests/).
"""
import asyncio
import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_session():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    serve.start(proxy=False)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def serve_session_http():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    serve.start(serve.HTTPOptions(host="127.0.0.1", port=18099))
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_function_deployment(serve_session):
    @serve.deployment
    def double(x):
        return x * 2

    handle = serve.run(double.bind(), name="fn_app", route_prefix=None)
    assert handle.remote(21).result(timeout_s=10) == 42
    serve.delete("fn_app")


def test_class_deployment_and_methods(serve_session):
    @serve.deployment
    class Greeter:
        def __init__(self, greeting):
            self.greeting = greeting

        def __call__(self, name):
            return f"{self.greeting}, {name}!"

        def shout(self, name):
            return f"{self.greeting.upper()} {name.upper()}"

    handle = serve.run(Greeter.bind("hello"), name="greet", route_prefix=None)
    assert handle.remote("world").result(timeout_s=10) == "hello, world!"
    assert handle.shout.remote("world").result(timeout_s=10) == "HELLO WORLD"
    serve.delete("greet")


def test_composition(serve_session):
    @serve.deployment
    class Adder:
        def __init__(self, amount):
            self.amount = amount

        def __call__(self, x):
            return x + self.amount

    @serve.deployment
    class Combiner:
        def __init__(self, a, b):
            self.a = a
            self.b = b

        async def __call__(self, x):
            return await self.a.remote(x) + await self.b.remote(x)

    app = Combiner.bind(Adder.options(name="A1").bind(1), Adder.options(name="A2").bind(2))
    handle = serve.run(app, name="comp", route_prefix=None)
    # (x+1) + (x+2) = 2x+3
    assert handle.remote(10).result(timeout_s=10) == 23
    serve.delete("comp")


def test_multiple_replicas_spread(serve_session):
    @serve.deployment(num_replicas=3)
    class WhoAmI:
        def __call__(self, _):
            return serve.get_replica_context().replica_id

    handle = serve.run(WhoAmI.bind(), name="spread", route_prefix=None)
    ids = {handle.remote(i).result(timeout_s=10) for i in range(30)}
    assert len(ids) >= 2, f"expected requests on >=2 replicas, saw {ids}"
    serve.delete("spread")


def test_status_and_redeploy_reconfigure(serve_session):
    @serve.deployment(user_config={"factor": 2})
    class Scaler:
        def __init__(self):
            self.factor = 1

        def reconfigure(self, cfg):
            self.factor = cfg["factor"]

        def __call__(self, x):
            return x * self.factor

    handle = serve.run(Scaler.bind(), name="cfg", route_prefix=None)
    assert handle.remote(10).result(timeout_s=10) == 20
    statuses = serve.status()
    assert statuses["cfg"].status.value == "RUNNING"
    assert statuses["cfg"].deployments["Scaler"].num_replicas == 1

    # Redeploy with a new user_config: reconfigured in place.
    handle = serve.run(
        Scaler.options(user_config={"factor": 5}).bind(), name="cfg",
        route_prefix=None,
    )
    deadline = time.time() + 15
    while time.time() < deadline:
        if handle.remote(10).result(timeout_s=10) == 50:
            break
        time.sleep(0.1)
    assert handle.remote(10).result(timeout_s=10) == 50
    serve.delete("cfg")


def test_autoscaling_up_and_down(serve_session):
    @serve.deployment(
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=1,
            max_replicas=3,
            target_ongoing_requests=1,
            upscale_delay_s=0.2,
            downscale_delay_s=1.0,
            metrics_interval_s=0.1,
            look_back_period_s=1.0,
        ),
        max_ongoing_requests=2,
    )
    class Slow:
        async def __call__(self, _):
            await asyncio.sleep(0.4)
            return serve.get_replica_context().replica_id

    handle = serve.run(Slow.bind(), name="auto", route_prefix=None)
    # Flood with concurrent requests to force upscale.
    responses = [handle.remote(i) for i in range(40)]
    ids = {r.result(timeout_s=60) for r in responses}
    assert len(ids) >= 2, f"expected autoscale to >=2 replicas, saw {len(ids)}"
    # Idle: scale back down to min_replicas.
    deadline = time.time() + 30
    while time.time() < deadline:
        info = serve.status()["auto"].deployments["Slow"]
        if info.num_replicas == 1:
            break
        time.sleep(0.25)
    assert serve.status()["auto"].deployments["Slow"].num_replicas == 1
    serve.delete("auto")


def test_http_proxy(serve_session_http):
    @serve.deployment
    class Echo:
        def __call__(self, request):
            if request.path.endswith("/sum"):
                data = request.json()
                return {"sum": sum(data["values"])}
            return "hello http"

    serve.run(Echo.bind(), name="web", route_prefix="/")
    base = "http://127.0.0.1:18099"
    with urllib.request.urlopen(f"{base}/") as resp:
        assert resp.read().decode() == "hello http"
    req = urllib.request.Request(
        f"{base}/sum", data=json.dumps({"values": [1, 2, 3]}).encode(),
        method="POST",
    )
    with urllib.request.urlopen(req) as resp:
        assert json.loads(resp.read()) == {"sum": 6}
    with urllib.request.urlopen(f"{base}/-/routes") as resp:
        assert json.loads(resp.read()) == {"/": "web"}
    serve.delete("web")


def test_batching(serve_session):
    @serve.deployment
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
        async def handle_batch(self, xs):
            self.batch_sizes.append(len(xs))
            return [x * 10 for x in xs]

        async def __call__(self, x):
            return await self.handle_batch(x)

        def seen_batches(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind(), name="batch", route_prefix=None)
    responses = [handle.remote(i) for i in range(16)]
    assert [r.result(timeout_s=20) for r in responses] == [i * 10 for i in range(16)]
    sizes = handle.seen_batches.remote().result(timeout_s=10)
    assert max(sizes) > 1, f"batching never coalesced: {sizes}"
    serve.delete("batch")


def test_multiplexed_models(serve_session):
    @serve.deployment
    class MuxModel:
        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            return {"id": model_id, "loaded_at": time.time()}

        async def __call__(self, _):
            model_id = serve.get_multiplexed_model_id()
            model = await self.get_model(model_id)
            return model["id"]

    handle = serve.run(MuxModel.bind(), name="mux", route_prefix=None)
    assert (
        handle.options(multiplexed_model_id="m1").remote(None).result(timeout_s=10)
        == "m1"
    )
    assert (
        handle.options(multiplexed_model_id="m2").remote(None).result(timeout_s=10)
        == "m2"
    )
    serve.delete("mux")


def test_failing_deployment_reports_deploy_failed(serve_session):
    """A crash-looping constructor surfaces DEPLOY_FAILED instead of
    hanging serve.run until timeout."""

    @serve.deployment
    class Broken:
        def __init__(self):
            raise RuntimeError("boom at init")

        def __call__(self, x):
            return x

    with pytest.raises(RuntimeError, match="Deploy failed"):
        serve.run(Broken.bind(), name="broken", route_prefix=None,
                  timeout_s=60)
    serve.delete("broken")


def test_replica_recovery_after_kill(serve_session):
    @serve.deployment(health_check_period_s=0.2)
    class Sturdy:
        def __call__(self, x):
            return x + 1

        def pid(self):
            import os

            return os.getpid()

    handle = serve.run(Sturdy.bind(), name="sturdy", route_prefix=None)
    assert handle.remote(1).result(timeout_s=10) == 2
    pid = handle.pid.remote().result(timeout_s=10)
    # Kill the replica's worker process out from under Serve.
    import signal
    import os

    os.kill(pid, signal.SIGKILL)
    # The controller's health checks replace the replica; requests keep
    # succeeding (routed around the dead replica, retried).
    deadline = time.time() + 40
    ok = False
    while time.time() < deadline:
        try:
            if handle.remote(5).result(timeout_s=10) == 6:
                new_pid = handle.pid.remote().result(timeout_s=10)
                if new_pid != pid:
                    ok = True
                    break
        except Exception:
            time.sleep(0.2)
    assert ok, "replica was not replaced after SIGKILL"
    serve.delete("sturdy")


@pytest.mark.parametrize("shut_down", ["serve_after_its_controller", "the_session_alone"])
def test_a_handles_router_ends_with_its_session(shut_down):
    """A handle's router polls and pushes to its controller from daemon
    threads. They end with ``serve.shutdown()``, also where the
    controller is already gone (the dashboard's REST DELETE shuts Serve
    down from another process), and with the session: in the next one
    they would submit to a controller its head never had, four tasks a
    second, for as long as the process lives."""
    from ray_tpu._private.worker import _global
    from ray_tpu.serve._private.common import CONTROLLER_NAME

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start(proxy=False)

    @serve.deployment
    def double(x):
        return x * 2

    handle = serve.run(double.bind(), name="fn_app", route_prefix=None)
    assert handle.remote(21).result(timeout_s=10) == 42
    if shut_down == "serve_after_its_controller":
        ray_tpu.kill(ray_tpu.get_actor(CONTROLLER_NAME))
        serve.shutdown()
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1)
    try:
        time.sleep(2.0)
        assert _global.node.gcs._table_versions["pending"] == 0
    finally:
        ray_tpu.shutdown()
