"""Dashboard HTTP API (the profiler spans of util/tracing.py are in
test_program_spans.py and test_program_paths_*.py)."""
import json
import urllib.error
import urllib.request

import pytest

import ray_tpu


@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_dashboard_serves_state(cluster):
    from ray_tpu.dashboard import start_dashboard

    url = start_dashboard(port=18266)

    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get([f.remote() for _ in range(3)])

    with urllib.request.urlopen(f"{url}/") as r:
        shell = r.read()
        assert b"ray_tpu dashboard" in shell
        assert b"/static/app.js" in shell  # SPA shell loads the app
    with urllib.request.urlopen(f"{url}/static/app.js") as r:
        js = r.read()
        assert r.headers.get_content_type() == "application/javascript"
        # every nav page has a renderer
        for page in (b"overview", b"nodes", b"jobs", b"serve", b"profile"):
            assert b"PAGES." + page in js
    with urllib.request.urlopen(f"{url}/static/style.css") as r:
        assert r.headers.get_content_type() == "text/css"
    try:
        urllib.request.urlopen(f"{url}/static/../__init__.py")
        assert False, "traversal must 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404
    with urllib.request.urlopen(f"{url}/api/jobs") as r:
        assert json.loads(r.read()) == []  # no jobs submitted yet
    with urllib.request.urlopen(f"{url}/api/cluster") as r:
        cluster_info = json.loads(r.read())
        assert cluster_info["total"]["CPU"] == 4.0
    with urllib.request.urlopen(f"{url}/api/tasks") as r:
        tasks = json.loads(r.read())
        assert any(t["name"] == "f" for t in tasks)
    with urllib.request.urlopen(f"{url}/api/nodes") as r:
        assert len(json.loads(r.read())) >= 1
