"""Task retries + actor restarts + chaos.

Reference semantics: max_retries re-submits on system failure
(task_manager.h:468); retry_exceptions opts app errors into retries;
max_restarts drives the GCS actor restart state machine
(gcs_actor_manager.h:278, actor_states.rst). Chaos model:
python/ray/tests/test_chaos.py.
"""
import os
import time

import pytest

import ray_tpu
from ray_tpu.exceptions import RayActorError, WorkerCrashedError


def test_task_retry_on_crash(ray_start):
    marker = f"/tmp/ray_tpu_retry_{os.getpid()}"
    if os.path.exists(marker):
        os.unlink(marker)

    @ray_tpu.remote(max_retries=2)
    def flaky(path):
        if not os.path.exists(path):
            open(path, "w").close()
            os._exit(1)  # crash on first attempt
        return "recovered"

    assert ray_tpu.get(flaky.remote(marker), timeout=60) == "recovered"
    os.unlink(marker)


def test_task_no_retry_by_default(ray_start):
    @ray_tpu.remote
    def die():
        os._exit(1)

    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(die.remote(), timeout=30)


def test_app_error_retry_with_retry_exceptions(ray_start):
    marker = f"/tmp/ray_tpu_appretry_{os.getpid()}"
    if os.path.exists(marker):
        os.unlink(marker)

    @ray_tpu.remote(max_retries=3, retry_exceptions=True)
    def flaky(path):
        if not os.path.exists(path):
            open(path, "w").close()
            raise RuntimeError("transient")
        return "ok"

    assert ray_tpu.get(flaky.remote(marker), timeout=60) == "ok"
    os.unlink(marker)


def test_app_error_no_retry_without_flag(ray_start):
    @ray_tpu.remote(max_retries=3)
    def always_raises():
        raise RuntimeError("app error")

    with pytest.raises(RuntimeError):
        ray_tpu.get(always_raises.remote(), timeout=30)


def test_actor_restart(ray_start):
    @ray_tpu.remote(max_restarts=1)
    class Phoenix:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

        def die(self):
            os._exit(1)

    p = Phoenix.remote()
    assert ray_tpu.get(p.incr.remote()) == 1
    p.die.remote()
    # After restart, state resets (fresh __init__) but the handle works.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            assert ray_tpu.get(p.incr.remote(), timeout=10) == 1
            break
        except RayActorError:
            time.sleep(0.2)
    else:
        pytest.fail("actor did not come back after restart")


def test_actor_dies_after_restart_budget(ray_start):
    @ray_tpu.remote(max_restarts=1)
    class Fragile:
        def die(self):
            os._exit(1)

        def ping(self):
            return "pong"

    f = Fragile.remote()
    assert ray_tpu.get(f.ping.remote()) == "pong"
    f.die.remote()  # restart 1
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            ray_tpu.get(f.ping.remote(), timeout=10)
            break
        except RayActorError:
            time.sleep(0.2)
    f.die.remote()  # exceeds budget
    time.sleep(0.5)
    with pytest.raises(RayActorError):
        ray_tpu.get(f.ping.remote(), timeout=10)


def test_actors_survive_live_head_failover(tmp_path):
    """ISSUE 9 satellite: a detached actor and a max_restarts>0 actor
    both remain callable through a LIVE head failover — the driver
    stays connected (reconnect + replay), the raylet and its workers
    outlive the head, and the actors are either re-claimed by their
    surviving workers during the recovery window or recreated from the
    durable actor table; the named handle re-resolves afterwards."""
    from ray_tpu.cluster_utils import DaemonCluster, SupervisedHead

    head = SupervisedHead(
        session_dir=str(tmp_path / "sess"),
        # Generous window: the claim path (worker reconnect) is the
        # interesting one; a too-short window degrades to recreation.
        env={"RAY_TPU_head_recovery_grace_s": "5.0"},
    )
    cluster = None
    try:
        ray_tpu.init(address=head.address)
        cluster = DaemonCluster.attach(head.tcp_address, head.authkey)
        cluster.add_node(num_cpus=2)

        @ray_tpu.remote(max_restarts=2)
        class Phoenix:
            def __init__(self):
                self.n = 0

            def incr(self):
                self.n += 1
                return self.n

        det = Phoenix.options(
            name="det_survivor", lifetime="detached"
        ).remote()
        reg = Phoenix.remote()
        assert ray_tpu.get(det.incr.remote(), timeout=60) == 1
        assert ray_tpu.get(reg.incr.remote(), timeout=60) == 1

        head.kill()
        assert head.wait_restarted(1, timeout=60), "head never came back"

        # Both handles stay callable through the failover (the call may
        # need a few retries while the recovery window re-binds them).
        deadline = time.monotonic() + 90
        vals = {}
        while time.monotonic() < deadline and len(vals) < 2:
            for key, h in (("det", det), ("reg", reg)):
                if key in vals:
                    continue
                try:
                    vals[key] = ray_tpu.get(h.incr.remote(), timeout=20)
                except Exception:  # noqa: BLE001 - mid-recovery
                    time.sleep(0.5)
        assert vals.get("det", 0) >= 1, "detached actor lost in failover"
        assert vals.get("reg", 0) >= 1, "restartable actor lost in failover"

        # Handle re-resolution: the durable name table still resolves,
        # and the resolved handle reaches the same live actor.
        h = ray_tpu.get_actor("det_survivor")
        assert ray_tpu.get(h.incr.remote(), timeout=30) > vals["det"]
    finally:
        if cluster is not None:
            for p in list(cluster._daemons):
                try:
                    cluster.kill_node(p)
                except Exception:  # noqa: BLE001
                    pass
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        head.stop()


def test_a_refused_claim_drops_the_instance_that_was_claimed_and_no_other():
    """A head restored from a table older than an actor queues its
    creation again, refuses the surviving worker's claim, and may hand
    that creation to the same worker before the worker acts on the
    refusal: the instance made since then must stay (dropped by id, the
    head held the actor ALIVE on a worker that answered "actor is gone"
    for ever: test_actors_survive_live_head_failover, 3 of 20 under
    load)."""
    import types

    from ray_tpu._private.worker_main import WorkerRuntime

    old, kept, new = object(), object(), object()
    rt = types.SimpleNamespace(
        actors={b"again": old, b"kept": kept, b"gone": old},
        _actor_locks={b"again": 1, b"kept": 2, b"gone": 3},
    )
    assert sorted(WorkerRuntime.claim_actors(rt)) == [b"again", b"gone", b"kept"]
    rt.actors[b"again"], rt._actor_locks[b"again"] = new, 4  # the creation ran
    WorkerRuntime.drop_refused_actors(rt, [b"again", b"gone", b"never"])
    assert rt.actors == {b"again": new, b"kept": kept}
    assert rt._actor_locks == {b"again": 4, b"kept": 2}


# ---------------------------------------- membership fencing (ISSUE 18)


class _FencePeer:
    """Captures what a GCS handler sends/replies to a raylet conn."""

    def __init__(self):
        self.sent = []
        self.replies = []
        self.peer_role = None

    def send(self, msg):
        self.sent.append(msg)

    def reply(self, req_msg, **fields):
        self.replies.append(fields)


def test_monotonic_liveness_survives_wall_clock_jump():
    """Satellite: the death sweeper diffs time.monotonic() readings —
    never the wall clock — so an NTP step / VM resume between two
    sweeps declares nothing dead."""
    from types import SimpleNamespace

    from ray_tpu._private.gcs import stale_node_ids
    from ray_tpu._private.ids import NodeID

    def node(last_hb, alive=True, conn=object()):
        return SimpleNamespace(
            node_id=NodeID.from_random(), alive=alive, conn=conn,
            last_heartbeat=last_hb,
        )

    now_mono = 1000.0
    fresh = node(now_mono - 1.0)
    quiet = node(now_mono - 60.0)
    # A +2h wall-clock jump happens between heartbeat and sweep. The
    # sweep never sees it: its inputs are monotonic readings only, so
    # the freshly-heartbeating node stays alive.
    assert stale_node_ids([fresh], now_mono, 1.0, 5) == []
    # The genuinely silent node IS declared dead by monotonic delta.
    assert stale_node_ids([quiet], now_mono, 1.0, 5) == [
        quiet.node_id.binary()
    ]
    # Dead / in-process (conn=None) / never-heartbeated nodes are out
    # of scope for the sweeper.
    assert stale_node_ids([node(now_mono - 60, alive=False)],
                          now_mono, 1.0, 5) == []
    assert stale_node_ids([node(now_mono - 60, conn=None)],
                          now_mono, 1.0, 5) == []
    assert stale_node_ids([node(0.0)], now_mono, 1.0, 5) == []


def test_stale_incarnation_heartbeat_fenced(ray_start):
    """A heartbeat carrying the wrong incarnation (or an unknown
    node_id) must not refresh liveness — the head answers with ONE
    fenced push per connection and ignores the beat."""
    from ray_tpu._private.worker import _global

    gcs = _global.node.gcs
    peer = _FencePeer()
    state = {"peer": peer}
    gcs._h_register_node(
        state, {"resources": {"CPU": 1.0}, "label": "fence-unit"}
    )
    reply = peer.replies[-1]
    assert reply["ok"] and reply["incarnation"] >= 1
    nid, inc = reply["node_id"], reply["incarnation"]
    try:
        # Correct incarnation: liveness refreshes, no fence.
        gcs._h_node_heartbeat(state, {"node_id": nid, "incarnation": inc})
        assert peer.sent == []
        hb0 = gcs.nodes[nid].last_heartbeat
        # Stale incarnation: fenced push, liveness NOT refreshed.
        gcs._h_node_heartbeat(
            state, {"node_id": nid, "incarnation": inc + 1}
        )
        assert [m["type"] for m in peer.sent] == ["fenced"]
        assert gcs.nodes[nid].last_heartbeat == hb0
        # Repeat offender on the same conn: no push spam.
        gcs._h_node_heartbeat(
            state, {"node_id": nid, "incarnation": inc + 1}
        )
        assert len(peer.sent) == 1
        # Unknown node_id on a fresh conn: fenced too.
        p2 = _FencePeer()
        gcs._h_node_heartbeat({"peer": p2}, {"node_id": b"\x99" * 16})
        assert p2.sent and p2.sent[0]["type"] == "fenced"
    finally:
        gcs._handle_node_death(nid, "fence-unit cleanup")


def test_fenced_node_id_cannot_reregister(ray_start):
    """Declare-dead arms the fence: the dead node_id is rejected at
    re-registration (the zombie must rejoin as a fresh identity), and
    the fresh join is granted a strictly higher incarnation."""
    from ray_tpu._private.worker import _global

    gcs = _global.node.gcs
    peer = _FencePeer()
    gcs._h_register_node(
        {"peer": peer}, {"resources": {"CPU": 1.0}, "label": "zombie"}
    )
    nid, inc = peer.replies[-1]["node_id"], peer.replies[-1]["incarnation"]
    gcs._handle_node_death(nid, "declared dead by test")
    # The zombie replays its registration with the fenced node_id.
    p2 = _FencePeer()
    gcs._h_register_node(
        {"peer": p2}, {"node_id": nid, "resources": {"CPU": 1.0}}
    )
    assert p2.replies[-1] == {"ok": False, "fenced": True}
    assert nid not in gcs.nodes or not gcs.nodes[nid].alive
    # The normal join path (no node_id) succeeds — new identity, higher
    # incarnation than anything the dead node ever held.
    p3 = _FencePeer()
    gcs._h_register_node({"peer": p3}, {"resources": {"CPU": 1.0}})
    fresh = p3.replies[-1]
    try:
        assert fresh["ok"] and fresh["node_id"] != nid
        assert fresh["incarnation"] > inc
    finally:
        gcs._handle_node_death(fresh["node_id"], "fence-unit cleanup")


def test_stale_object_advert_rejected_after_free(ray_start):
    """A zombie's put_object advert landing AFTER its death was
    processed (objects freed) must not resurrect the freed id as a
    ghost READY entry."""
    from ray_tpu._private.gcs import W_DEAD, WorkerHandle
    from ray_tpu._private.ids import WorkerID
    from ray_tpu._private.worker import _global

    gcs = _global.node.gcs
    wid = WorkerID.from_random().binary()
    with gcs._lock:
        gcs.workers[wid] = WorkerHandle(
            worker_id=WorkerID(wid),
            node_id=gcs.head_node.node_id,
            state=W_DEAD,
        )
    oid = b"\xa5" * 16
    peer = _FencePeer()
    msg = {"type": "put_object", "object_id": oid, "inline": b"zombie",
           "size": 6, "req_id": 1}
    try:
        gcs._h_put_object({"peer": peer, "client_id": wid}, msg)
        assert peer.replies == [{"ok": False, "fenced": True}]
        assert oid not in gcs.objects, "freed id resurrected by zombie"
        # Same advert from a live (ownerless) path still lands.
        gcs._h_put_object({"peer": peer, "client_id": None}, dict(msg))
        assert peer.replies[-1] == {"ok": True}
        assert gcs.objects[oid].inline == b"zombie"
    finally:
        with gcs._lock:
            gcs.objects.pop(oid, None)
            gcs.workers.pop(wid, None)


def test_zombie_node_rejoins_with_new_incarnation(tmp_path):
    """Tentpole e2e: a raylet partitioned from the head past the death
    threshold — TCP stays ESTABLISHED, frames blackhole — is declared
    dead (incarnation bumped, node_id fenced). On heal its first
    heartbeat draws a fenced push; it self-fences and rejoins through
    the normal join path as a NEW node_id with a HIGHER incarnation,
    and a restartable actor that lived there answers exactly one
    incarnation's calls (fresh boot token, counter restarted at 1)."""
    import secrets

    from ray_tpu.cluster_utils import DaemonCluster

    ray_tpu.init(
        num_cpus=0,
        tcp_port=0,
        _system_config={
            "health_check_period_ms": 250,
            "health_check_failure_threshold": 4,
        },
    )
    cluster = DaemonCluster.attach()
    try:
        epoch = time.time()
        # Cut both directions of the raylet<->head link from t=+10s,
        # heal 6s later. Installed ONLY in the victim daemon's env: the
        # driver and the head never see the spec (gray failure).
        cluster.add_node(
            num_cpus=2,
            label="victim",
            env={
                "RAY_TPU_chaos_spec": "partition:raylet<->head=10:6",
                "RAY_TPU_chaos_seed": "7",
                "RAY_TPU_chaos_epoch": str(epoch),
                # Beat at the head's sweep cadence: the default 1s
                # period would read as missed beats under the head's
                # tightened 250ms*4 threshold.
                "RAY_TPU_health_check_period_ms": "250",
            },
        )
        victim = next(
            n for n in ray_tpu.nodes() if n["label"] == "victim"
        )
        nid0, inc0 = victim["node_id"], victim["incarnation"]

        # num_cpus=1 pins the actor to the victim — the head node has
        # zero CPUs, so nothing else can host it (or its restart).
        @ray_tpu.remote(max_restarts=4, num_cpus=1)
        class Tokened:
            def __init__(self):
                self.token = secrets.token_hex(4)
                self.n = 0

            def bump(self):
                self.n += 1
                return self.token, self.n

        a = Tokened.remote()
        tok_a, n1 = ray_tpu.get(a.bump.remote(), timeout=60)
        assert n1 == 1

        # Phase 1: the partition outlasts the death threshold — the
        # victim disappears from the live membership view.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            alive = [n for n in ray_tpu.nodes()
                     if n["alive"] and n["node_id"] == nid0]
            if not alive:
                break
            time.sleep(0.25)
        else:
            pytest.fail("partitioned node never declared dead")

        # Phase 2: heal -> fenced heartbeat -> self-fence -> rejoin as
        # a fresh identity with a strictly higher incarnation.
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            back = [
                n for n in ray_tpu.nodes()
                if n["alive"] and n["label"] == "victim"
                and n["node_id"] != nid0
                and n["incarnation"] > inc0
            ]
            if back:
                break
            time.sleep(0.25)
        else:
            pytest.fail("zombie never rejoined as a new incarnation")

        # Phase 3: the actor answers exactly one incarnation's calls —
        # a fresh boot token, counter restarted, strictly increasing,
        # never interleaved with the old token.
        deadline = time.monotonic() + 90
        tok_b = None
        while time.monotonic() < deadline:
            try:
                tok_b, m1 = ray_tpu.get(a.bump.remote(), timeout=15)
                break
            except Exception:  # noqa: BLE001 - mid-restart
                time.sleep(0.5)
        assert tok_b is not None, "actor never answered after rejoin"
        assert tok_b != tok_a, "old incarnation answered after fencing"
        assert m1 == 1, "restarted actor kept stale state"
        for expect in (2, 3):
            tok, m = ray_tpu.get(a.bump.remote(), timeout=30)
            assert (tok, m) == (tok_b, expect)
    finally:
        for p in list(cluster._daemons):
            try:
                cluster.kill_node(p)
            except Exception:  # noqa: BLE001
                pass
        ray_tpu.shutdown()


def test_rpc_delay_injection():
    # Reference: RAY_testing_asio_delay_us (ray_config_def.h:832).
    # Pool disabled: a same-host put through the shm segment advertises
    # asynchronously and never blocks on put_object, so the delay rule
    # is only observable on the legacy synchronous path.
    os.environ["RAY_TPU_NATIVE_STORE"] = "0"
    ray_tpu.init(
        num_cpus=2,
        _system_config={"testing_rpc_delay_us": "put_object=30000:30000"},
    )
    try:
        start = time.monotonic()
        ray_tpu.get(ray_tpu.put(1))
        assert time.monotonic() - start >= 0.03
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_NATIVE_STORE", None)
