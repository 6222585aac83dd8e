"""Flight-recorder event-name registry: the checked taxonomy.

Every name passed to ``events.record(category, entity, name, attrs)``
and every name the timeline stitcher in ``state.py`` matches against
MUST appear here. raylint's ``event-taxonomy`` rule enforces both
directions statically, so a renamed or fat-fingered event cannot
silently vanish from ``ray_tpu timeline`` / the state API — the lint
fails instead of the timeline quietly missing rows.

Standalone by design: no imports, constants only. raylint execs this
file without the ray_tpu package on the path (linting must not require
jax), and ``events.py``/tests import it normally. A cross-check test
asserts ``events.TASK_TRANSITIONS``/span names stay registered.

To add an event: append the name to the right block below, emit it,
and (if the timeline should render it) teach ``state.py`` — the lint
keeps all three in sync from then on.
"""
from __future__ import annotations

#: Recorder categories (mirrors events.py's constants; the string
#: values are the wire/category names, the const names are what call
#: sites reference as ``_events.TASK`` etc.).
CATEGORIES = frozenset(
    {
        "task", "worker", "lease", "object", "transfer", "sched",
        "refs", "chaos", "head", "train",
    }
)
CATEGORY_CONSTS = frozenset(
    {
        "TASK", "WORKER", "LEASE", "OBJECT", "TRANSFER", "SCHED",
        "REFS", "CHAOS", "HEAD", "TRAIN",
    }
)

#: category name -> registered event names emitted under it.
EVENTS_BY_CATEGORY = {
    "task": frozenset(
        {
            # Canonical lifecycle transitions + the two span events
            # that carry them (events._SPAN_KEYS).
            "SUBMITTED", "QUEUED", "LEASED", "FORKED", "EXEC_START",
            "EXEC_END", "SEALED", "SUBMIT_SPAN", "EXEC_SPAN",
        }
    ),
    "worker": frozenset(
        {
            "BOOT", "REGISTERED", "SPAWN_REQUESTED", "FORK_REQUESTED",
            "FORKED", "FORK_FAILED",
        }
    ),
    "lease": frozenset({"GRANTED", "RETURNED"}),
    "object": frozenset(
        {
            "SEALED", "SPILLED", "FREED_BATCH", "PUT_BACKPRESSURE",
            # Shared-memory object plane (PR 12): fire-and-forget put
            # advertisement, get served from the node segment with zero
            # RPCs, and the raylet's dead-client refcount sweep.
            "SHM_PUT_ADVERT", "SHM_GET_LOCAL", "SHM_SWEEP",
        }
    ),
    "transfer": frozenset(
        {
            "PULL", "PULL_RETRY", "PUSH",
            # Same-host pull served by mapping the provider's node
            # segment: one memcpy, zero data bytes over the socket.
            "SHM_PULL",
        }
    ),
    "sched": frozenset({"BLOCKED"}),
    "refs": frozenset(
        {
            "REF_FLUSH", "REF_REFLUSH", "SHARD_ENQUEUE", "SHARD_APPLY",
            "OWNER_FALLBACK", "SPILL_FAIL",
            "PULL_QUEUED", "PULL_ACTIVATE", "PULL_DONE", "PULL_CANCEL",
            # Hedged pulls (straggler layer): an active pull whose
            # throughput fell below the floor re-led onto another
            # holder (the in-flight byte budget is charged once).
            "PULL_RELEAD",
        }
    ),
    "chaos": frozenset(
        {
            # Injected faults + the lock-order witness's finding.
            "FAULT", "KILLED", "NODE_KILL", "LOCK_ORDER",
            # Partition primitive: link-cut window edges (begin on the
            # first blocked frame, heal on the first frame after).
            "PARTITION_BEGIN", "PARTITION_HEAL",
            # Sustained-degradation primitives: token-bucket link
            # throttle window edges and the first stretched execution.
            "THROTTLE_BEGIN", "THROTTLE_HEAL", "SLOWEXEC",
        }
    ),
    "head": frozenset(
        {
            "HEAD_DOWN", "HEAD_RECONNECT", "RECONCILE_BEGIN",
            "RECONCILE_CLAIM", "RECONCILE_END", "GHOSTS_LOST",
            "RESUBMITS_DROPPED",
            # Membership fencing (incarnation/epoch protocol): a stale
            # node/client message rejected, a stale actor-epoch result
            # rejected, and a zombie raylet draining itself after
            # learning it was declared dead.
            "NODE_FENCED", "ACTOR_EPOCH_FENCED", "ZOMBIE_SELF_FENCE",
            # Gray-failure tolerance (straggler layer): per-sweep node
            # score, suspect/quarantine/readmit transitions, and the
            # speculative-execution hedge lifecycle.
            "HEALTH_SCORE", "NODE_SUSPECT", "NODE_QUARANTINE",
            "NODE_READMIT", "HEDGE_LAUNCH", "HEDGE_WIN", "HEDGE_CANCEL",
        }
    ),
    "train": frozenset(
        {
            # The trainer's own record of every turn of a user's loop
            # (train/session.py): one REPORT a train.report call; one
            # USAGE a report taken off the queue, with the loop
            # thread's CPU time and the process's CPU and fault
            # counters (cumulative); a collector pause; and the loop
            # thread's stack when a report is overdue. The entity is
            # the thread the event is about.
            "REPORT", "USAGE", "GC_PAUSE", "OVERDUE",
            # Host spans (util/tracing.py HOST_SPANS) that also record
            # here while the process holds a train session: the event
            # is the span's name, attrs its monotonic start.
            # ray_tpu.worker.exec is not among them: the task
            # category's EXEC_SPAN already holds that interval.
            "ray_tpu.train.report", "ray_tpu.train.next_result",
            "ray_tpu.train.result_wait", "ray_tpu.worker.reply",
            "ray_tpu.worker.recv",
            # Set-up (util/tracing.py SETUP_SPANS), laid out as the host
            # spans are, attrs a dict {"m_start", "fun_name"}: what JAX
            # reports of each trace, lowering, backend compile and
            # persistent-cache read of the process (watch_compiles), the
            # cache's hits and misses as spans of no length, one span
            # of no length as the record ends whose attrs also hold
            # {stage: [count, seconds]} of the durations too short for an
            # event, another there whose attrs hold {entry: [calls,
            # traces]} of the kernels' inlined jitted entries
            # (ops/attention.py kernel_entry), and parallel/mesh.py
            # shard_params.
            "ray_tpu.compile.trace", "ray_tpu.compile.lower",
            "ray_tpu.compile.backend", "ray_tpu.compile.cache_read",
            "ray_tpu.compile.cache_hit", "ray_tpu.compile.cache_miss",
            "ray_tpu.compile.short", "ray_tpu.compile.entries",
            "ray_tpu.parallel.shard_params",
        }
    ),
}

#: Flat set: every registered recorder event name.
EVENT_NAMES = frozenset().union(*EVENTS_BY_CATEGORY.values())

#: GCS task-table states (gcs.py's task_events store — a separate
#: namespace from the flight recorder, but state.py's timeline matches
#: these literals too, so they are registered alongside).
TASK_TABLE_EVENTS = frozenset(
    {"PENDING", "RUNNING", "FINISHED", "FAILED"}
)


def is_registered(name: str) -> bool:
    return name in EVENT_NAMES

