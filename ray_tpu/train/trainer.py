"""JaxTrainer: gang-scheduled SPMD training over actor worker groups.

Reference call stack (SURVEY.md §3.3): TorchTrainer.fit →
BackendExecutor + WorkerGroup actors + per-worker _TrainSession with a
report queue → TrainingIterator drains epoch results. This trainer
keeps that architecture — N worker actors gang-placed via a placement
group, session report contract, checkpoint persistence, group restart
on failure (FailureConfig) — with the torch/NCCL backend replaced by
the JAX model: each worker is one TPU host of a slice; worker 0's
address seeds `jax.distributed.initialize` (coordinator brokered
through the control plane KV, replacing the reference's
NCCLUniqueIDStore actor — util/collective/util.py:9); the mesh from
ScalingConfig spans all hosts' devices and XLA compiles the
collectives.

Single-worker mode (num_workers=1) drives the whole local mesh in one
process — the bench path on one host.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from .._private import events as _events
from ..exceptions import RayActorError
from ..util.placement_group import placement_group, remove_placement_group
from ..util import tracing
from ..util.scheduling_strategies import PlacementGroupSchedulingStrategy
from .checkpoint import Checkpoint
from .config import Result, RunConfig, ScalingConfig
from .session import TrainContext, get_session, init_session

logger = logging.getLogger(__name__)

#: Under ``Result.path``, one a rank: what the flight recorder holds of the
#: run when ``fit`` ends (``write_train_events``).
EVENTS_FILE = "train_events_rank{rank}.jsonl"


def write_train_events(storage: str, worker_ids: List[str], t_fit: float,
                       experiment: str) -> None:
    """One JSON-lines file a rank under ``storage``, read back from the head's
    flight recorder before the workers are killed. First a ``header`` line
    (``dropped``: events of this worker that a ring or the head's retention
    lost), then, by wall time, events as ``ray_tpu events --json`` prints
    them: the rank's ``train`` category (REPORT, USAGE, GC_PAUSE, OVERDUE,
    host spans); every task it executed as one more such span,
    ``ray_tpu.worker.exec``, from the task's ``EXEC_SPAN``; the worker's own
    lifecycle (SPAWN_REQUESTED ... REGISTERED); and the transitions, with the
    task's name, of the tasks that ran before the loop's first report (the
    actor's creation, ``run``)."""
    from .._private import state
    from .._private.config import RayConfig
    from ..util.state import summarize_events

    if not worker_ids:
        return  # the group never came up
    cap = int(RayConfig.event_retention_per_job)
    names = {e["task_id"]: e["name"] for e in state.task_events()}
    ranks = []
    for rank, wid in enumerate(worker_ids):
        source = _events.worker_source(wid)
        # An EXEC_SPAN reads back as four transitions.
        mine = state.list_cluster_events(job=source, limit=4 * cap)
        first_report = next(
            (e["timestamp"] for e in mine if e["event"] == "REPORT"), None
        )
        if first_report is None:
            continue  # the loop never reported: there is no turn to read
        lines, early = [], set()
        for e in mine:
            if e["category"] == _events.TRAIN:
                lines.append(e)
            elif e["category"] == _events.TASK:
                if e["timestamp"] < first_report:
                    early.add(e["entity"])
                attrs = e.get("attrs") or {}
                if e["event"] == "EXEC_END" and attrs.get("m_end") is not None:
                    lines.append({
                        "category": _events.TRAIN,
                        "event": tracing.WORKER_EXEC,
                        "entity": str(attrs["thread"]),
                        "timestamp": e["timestamp"],
                        "monotonic": attrs["m_end"],
                        "attrs": {"m_start": attrs["m_start"]},
                        "task": e["entity"], "source": source,
                    })
        lines += state.list_cluster_events(
            category=_events.WORKER, entity=wid, limit=cap
        )
        for tid in early:
            for e in state.list_cluster_events(
                category=_events.TASK, entity=tid, limit=cap
            ):
                lines.append({**e, "name": names.get(tid, "")})
        lines.sort(key=lambda e: e["timestamp"])
        ranks.append((rank, wid, source, lines))
    # After the reads: their barrier shipped the rings' last events and,
    # with them, the count of what the rings evicted.
    drops = summarize_events()["drops"]
    for rank, wid, source, lines in ranks:
        header = {
            "rank": rank, "experiment": experiment, "worker_id": wid,
            "source": source, "t_fit": t_fit, "t_written": time.time(),
            "dropped": drops.get(source, 0),
        }
        path = os.path.join(storage, EVENTS_FILE.format(rank=rank))
        with open(path, "w") as f:
            f.write(json.dumps({"header": header}) + "\n")
            for e in lines:
                e.pop("job", None)
                f.write(json.dumps(e, default=str) + "\n")


class TrainWorker:
    """Actor wrapping one training process (reference:
    RayTrainWorker — train/_internal/worker_group.py)."""

    def __init__(self, rank: int, world_size: int, experiment_name: str,
                 storage_path: Optional[str], use_jax_distributed: bool = False,
                 num_processes: Optional[int] = None,
                 rendezvous_token: str = ""):
        self.rank = rank
        self.world_size = world_size
        self.session = init_session(
            TrainContext(
                world_rank=rank,
                world_size=world_size,
                local_rank=rank,
                node_rank=rank,
                experiment_name=experiment_name,
                storage_path=storage_path,
            )
        )
        self._thread: Optional[threading.Thread] = None
        if use_jax_distributed and world_size > 1:
            # Multi-host: join the jax.distributed cluster so all hosts
            # see the global device set. Rank 0 binds the coordinator and
            # publishes its address through the GCS KV; other ranks poll
            # for it (reference: coordinator rendezvous via the named
            # NCCLUniqueIDStore actor, util/collective/util.py:9).
            coordinator = self._rendezvous(
                f"{experiment_name}/{rendezvous_token}"
            )
            import jax

            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes or world_size,
                process_id=rank,
            )

    def _rendezvous(self, rendezvous_id: str) -> str:
        """rendezvous_id is unique per fit attempt (the driver mints a
        fresh token for every _fit_once) so a group restart can never
        read the previous attempt's dead coordinator address."""
        from .._private import transport
        from .._private.worker import global_client

        client = global_client()
        key = f"train_coordinator/{rendezvous_id}".encode()
        if self.rank == 0:
            import socket

            s = socket.socket()
            s.bind(("", 0))
            port = s.getsockname()[1]
            s.close()
            addr = f"{transport.node_ip()}:{port}"
            client.kv_put(key, addr.encode())
            return addr
        deadline = time.time() + 60
        while time.time() < deadline:
            val = client.kv_get(key)
            if val:
                return val.decode()
            time.sleep(0.1)
        raise TimeoutError("jax.distributed coordinator address never published")

    def run(self, train_loop: Callable, config: Dict[str, Any],
            latest_checkpoint: Optional[str] = None) -> bool:
        """Start the user loop in a background thread; results stream
        through next_result()."""
        self.session.context.latest_checkpoint = (
            Checkpoint(latest_checkpoint) if latest_checkpoint else None
        )

        def runner():
            try:
                # The user loop may take (config) or no args (reference:
                # train_loop_per_worker signature detection).
                import inspect

                if len(inspect.signature(train_loop).parameters) >= 1:
                    train_loop(config or {})
                else:
                    train_loop()
                self.session.finish()
            except BaseException as e:  # noqa: BLE001
                traceback.print_exc()
                self.session.finish(e)

        self._thread = threading.Thread(target=runner, daemon=True)
        self._thread.start()
        return True

    def next_result(self):
        with tracing.span(tracing.TRAIN_NEXT_RESULT):
            kind, metrics, checkpoint = self.session.next_result()
            if kind == "done":
                err = self.session.error
                if err is not None:
                    raise err if isinstance(err, Exception) else RuntimeError(str(err))
                return ("done", None, None)
            # Checkpoints are directories on shared storage; ship the path.
            ckpt_path = checkpoint.path if isinstance(checkpoint, Checkpoint) else checkpoint
            return (kind, metrics, ckpt_path)

    def worker_id(self) -> str:
        """Which worker process holds this rank (``fit`` waits for it as the
        sign that the actor is up): its flight-recorder events reach the
        head under that name."""
        from .._private.worker import global_client

        return global_client().worker_id.hex()


class JaxTrainer:
    """Reference: train/data_parallel_trainer.py:25 DataParallelTrainer;
    fit() contract from base_trainer.py:567."""

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
    ):
        self._train_loop = train_loop_per_worker
        self._config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}

    # ------------------------------------------------------------------ fit

    def fit(self) -> Result:
        if not ray_tpu.is_initialized():
            ray_tpu.init()
        name = self.run_config.name or f"JaxTrainer_{int(time.time())}"
        storage = self.run_config.storage_path or os.path.join(
            "/tmp/ray_tpu_results", name
        )
        os.makedirs(storage, exist_ok=True)
        max_failures = self.run_config.failure_config.max_failures
        attempt = 0
        latest_ckpt: Optional[str] = None
        while True:
            try:
                return self._fit_once(name, storage, latest_ckpt)
            except RayActorError as e:
                attempt += 1
                if max_failures >= 0 and attempt > max_failures:
                    return Result(
                        metrics=None, checkpoint=None, error=e, path=storage
                    )
                latest_ckpt = self._latest_checkpoint_path(storage)

    def _latest_checkpoint_path(self, storage: str) -> Optional[str]:
        cands = sorted(
            (d for d in os.listdir(storage) if d.startswith("checkpoint_")),
            key=lambda d: int(d.split("_")[-1]),
        )
        return os.path.join(storage, cands[-1]) if cands else None

    def _fit_once(self, name: str, storage: str, latest_ckpt: Optional[str]) -> Result:
        t_fit = time.time()
        sc = self.scaling_config
        n = sc.num_workers
        worker_ids: List[str] = []
        pg = placement_group(
            [sc.worker_resources() for _ in range(n)],
            strategy=sc.placement_strategy,
        )
        workers = []
        try:
            import secrets

            rdv_token = secrets.token_hex(4)
            # Each worker takes what its bundle reserved: a worker that
            # asks for no TPU is spawned pinned to the CPU, whatever
            # bundle it is placed in.
            res = sc.worker_resources()
            worker_cls = ray_tpu.remote(TrainWorker).options(
                num_cpus=res.pop("CPU", 0), resources=res, max_concurrency=2
            )
            for rank in range(n):
                workers.append(
                    worker_cls.options(
                        scheduling_strategy=PlacementGroupSchedulingStrategy(
                            placement_group=pg,
                            placement_group_bundle_index=rank,
                        ),
                    ).remote(
                        rank, n, name, storage, sc.use_jax_distributed,
                        None, rdv_token,
                    )
                )
            worker_ids = ray_tpu.get(
                [w.worker_id.remote() for w in workers], timeout=120
            )
            cfg = self._config
            if self.datasets:
                cfg = dict(cfg or {})
                cfg["__datasets__"] = self.datasets
            ray_tpu.get(
                [w.run.remote(self._train_loop, cfg, latest_ckpt) for w in workers],
                timeout=120,
            )
            history = []
            final_metrics = None
            checkpoint = None
            iteration = 0
            while True:
                results = ray_tpu.get(
                    [w.next_result.remote() for w in workers]
                )
                kinds = {r[0] for r in results}
                if "done" in kinds:
                    break
                iteration += 1
                rank0_kind, metrics, ckpt_path = results[0]
                final_metrics = metrics
                history.append(metrics)
                if ckpt_path:
                    persisted = os.path.join(storage, f"checkpoint_{iteration:06d}")
                    if os.path.abspath(ckpt_path) != persisted:
                        import shutil

                        shutil.copytree(ckpt_path, persisted, dirs_exist_ok=True)
                    checkpoint = Checkpoint(persisted)
                    self._prune_checkpoints(storage)
            return Result(
                metrics=final_metrics,
                checkpoint=checkpoint,
                error=None,
                path=storage,
                metrics_history=history,
            )
        finally:
            try:
                write_train_events(storage, worker_ids, t_fit, name)
            except Exception:  # noqa: BLE001 - the record must not cost a run its result
                logger.warning("train events were not written", exc_info=True)
            for w in workers:
                try:
                    ray_tpu.kill(w)
                except Exception:
                    pass
            try:
                remove_placement_group(pg)
            except Exception:
                pass

    def _prune_checkpoints(self, storage: str):
        keep = self.run_config.checkpoint_config.num_to_keep
        if not keep:
            return
        cands = sorted(
            (d for d in os.listdir(storage) if d.startswith("checkpoint_")),
            key=lambda d: int(d.split("_")[-1]),
        )
        import shutil

        for d in cands[:-keep]:
            shutil.rmtree(os.path.join(storage, d), ignore_errors=True)


def get_checkpoint() -> Optional[Checkpoint]:
    """Resume checkpoint for the current session (reference:
    train.get_checkpoint)."""
    s = get_session()
    if s is None:
        return None
    return getattr(s.context, "latest_checkpoint", None)
