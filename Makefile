# CI entry points (reference: the Bazel/Buildkite pipelines in
# .buildkite/ + ci/ — here one deterministic make surface: native
# build, bytecode lint, stress binaries, full suite).

.PHONY: ci native lint raylint raylint-baseline race-smoke test tier1-times \
	obs-smoke envelope-smoke chaos-smoke failover-smoke \
	pressure-smoke shm-smoke partition-smoke straggler-smoke \
	stress clean

ci: native lint test obs-smoke envelope-smoke chaos-smoke failover-smoke \
	pressure-smoke race-smoke shm-smoke partition-smoke straggler-smoke

native:
	$(MAKE) -C native

# Three lint layers: compileall catches syntax errors in every module
# (including ones the suite never imports), the import line smoke-
# checks the public surface, and raylint enforces the runtime's
# concurrency/reliability invariants (thread domains, one retry
# policy, at-least-once GCS traffic, counted-never-silent faults, the
# event-name registry) against tools/raylint/baseline.json —
# pre-existing debt is tracked, NEW violations fail CI. See README
# "Static analysis & concurrency invariants".
lint:
	python -m compileall -q ray_tpu tests tools
	python -m tools.raylint
	JAX_PLATFORMS=cpu python -c "import ray_tpu, ray_tpu.data, \
	ray_tpu.train, ray_tpu.tune, ray_tpu.serve, ray_tpu.rllib, \
	ray_tpu.workflow, ray_tpu.dag, ray_tpu.autoscaler.gce, \
	ray_tpu.util.multiprocessing, ray_tpu.experimental.tqdm_ray"

# raylint alone (fast; no jax import needed).
raylint:
	python -m tools.raylint

# Re-snapshot the accepted debt after deliberately fixing or accepting
# violations. Review the diff of tools/raylint/baseline.json!
raylint-baseline:
	python -m tools.raylint --write-baseline

# Lock-order witness soak (Python TSan-lite): the full witness unit
# suite (inverted pair caught, clean ordering clean, reentrant RLock
# no-false-positive) plus the object-plane, chaos, lifetime, and
# actors suites with every threading.Lock/RLock wrapped and the
# held-before graph checked for cycles. A witnessed inversion FAILS the run (pytest exit 3 from the
# sessionfinish hook) even when every test passed — the inversion is a
# deadlock waiting for production traffic to align. Subprocesses
# (heads/raylets/workers) inherit RAY_TPU_lock_witness and
# self-install; their findings append to the shared sidecar file the
# sessionfinish gate scans (plus stderr and CHAOS LOCK_ORDER
# flight-recorder events), so a daemon-side inversion fails the run
# too. Skips are counted by pytest, never silent.
race-smoke:
	RAY_TPU_lock_witness=1 JAX_PLATFORMS=cpu python -m pytest \
		tests/test_lock_witness.py tests/test_object_plane.py \
		tests/test_chaos.py tests/test_object_lifetime.py \
		tests/test_actors.py -q -p no:cacheprovider

test:
	python -m pytest tests/ -q

# Seconds by test file of the last tier-1 run (the XML its --junitxml wrote):
# the sum, the sum over six workers, the longest cases; non-zero where a file
# is over 150 s (README "Tests").
tier1-times:
	python -m tools.tier1_times /tmp/_t1.xml

# Observability surface: flight-recorder event pipeline + tracing +
# dashboard tests, including the recorder overhead-budget perf check
# (test_flight_recorder_overhead_budget asserts ≤5% on the
# single_client_tasks_async shape vs recording disabled).
obs-smoke:
	python -m pytest tests/test_observability.py \
		tests/test_dashboard_tracing.py tests/test_logging.py -q

# Object-plane envelope, scaled down (64 MiB broadcast to 4 real
# daemon nodes, 1k args, 300 returns, 1k gets, spill-backed get) held
# concurrently with a 20k-task/100-node scheduling stress. The full
# reference-scale rows (1 GiB / 32 nodes / 10k / 3k / 200k-task
# stress) run via:
#   python -m ray_tpu._private.ray_perf --only object_envelope
# A host that can't fit even the smoke payload records an explicit
# object_envelope_skipped row — counted, never silent.
envelope-smoke:
	JAX_PLATFORMS=cpu python -m ray_tpu._private.ray_perf \
		--only object_envelope --envelope-smoke \
		--out /tmp/ray_tpu_envelope_smoke.json

# Chaos soak, short + seeded (2 real daemon nodes, ~25s of task/actor/
# object traffic under message drop/delay/dup/reorder on ref_flush /
# borrow / pull paths, worker kill points, and node SIGKILLs). The run
# prints its seed up front; any red run reproduces with
#   python -m ray_tpu._private.ray_perf --only chaos_soak --chaos-smoke \
#       --chaos-seed <printed seed>
# A host without the TCP control plane records chaos_soak_skipped —
# counted, never silent. The full multi-minute soak:
#   python -m ray_tpu._private.ray_perf --only chaos_soak
chaos-smoke:
	JAX_PLATFORMS=cpu python -m ray_tpu._private.ray_perf \
		--only chaos_soak --chaos-smoke \
		--out /tmp/ray_tpu_chaos_smoke.json

# Head-failover smoke, short + seeded (1 supervised-head SIGKILL under
# task/actor/object traffic on a 2-daemon cluster, bounded wall time).
# Asserts zero wedged gets, actor + kv continuity across the restart,
# no leaked directory entries, and visible HEAD/RECONCILE events. A
# red run reproduces with
#   python -m ray_tpu._private.ray_perf --only head_failover \
#       --failover-smoke --chaos-seed <printed seed>
# A host that cannot launch the external head records an explicit
# head_failover_skipped row — counted, never silent. The full
# multi-kill soak:
#   python -m ray_tpu._private.ray_perf --only head_failover
failover-smoke:
	JAX_PLATFORMS=cpu python -m ray_tpu._private.ray_perf \
		--only head_failover --failover-smoke \
		--out /tmp/ray_tpu_failover_smoke.json

# Partition soak, short + seeded (1 victim daemon fully partitioned
# from the head past the death threshold while holding a restartable
# actor, leased tasks and owned objects; scheduled heal; then one
# supervised-head SIGKILL to prove fencing composes with failover).
# Asserts zero wedged gets, at-most-once actor side effects across the
# false death (per-incarnation boot tokens never interleave, counters
# stay monotonic), no resurrected freed objects, NODE_FENCED +
# ZOMBIE_SELF_FENCE visible, and the victim back as a NEW node id with
# a HIGHER incarnation. A red run reproduces with
#   python -m ray_tpu._private.ray_perf --only partition_soak \
#       --partition-smoke --chaos-seed <printed seed>
# A host that cannot launch the external head records an explicit
# partition_soak_skipped row — counted, never silent. The full
# two-node soak:
#   python -m ray_tpu._private.ray_perf --only partition_soak
partition-smoke:
	JAX_PLATFORMS=cpu python -m ray_tpu._private.ray_perf \
		--only partition_soak --partition-smoke \
		--out /tmp/ray_tpu_partition_smoke.json

# Straggler soak, short + seeded (2 healthy daemons + 1 gray victim:
# alive and heartbeating but with task execution stretched 50x and its
# transfer plane later throttled to 1 MiB/s). Asserts the health
# scorer suspects then quarantines the victim (drain, not fence),
# hedged twins keep task p99 within 3x the all-healthy baseline,
# every hedged pair resolves to exactly one accepted done (the
# resource ledger never over-credits), throttled multi-chunk pulls
# re-lead (PULL_RELEAD) instead of wedging and deliver correct bytes,
# hedging stays <= 1% launch rate while healthy, the victim is
# readmitted after heal, and the sequence composes with one
# supervised-head SIGKILL. A red run reproduces with
#   python -m ray_tpu._private.ray_perf --only straggler_soak \
#       --straggler-smoke --chaos-seed <printed seed>
# A host that cannot launch the external head records an explicit
# straggler_soak_skipped row — counted, never silent. The full
# >=100-pair soak:
#   python -m ray_tpu._private.ray_perf --only straggler_soak
straggler-smoke:
	JAX_PLATFORMS=cpu python -m ray_tpu._private.ray_perf \
		--only straggler_soak --straggler-smoke \
		--out /tmp/ray_tpu_straggler_smoke.json

# Memory-pressure soak, scaled down (a 32 MiB broadcast chunk train to
# 8 real daemon nodes concurrent with hundreds of small gets, under a
# 48 MiB pool and a 12 MiB in-flight pull budget, then seeded storage
# chaos: spill IO errors, disk-full, truncated spill files). Asserts
# bounded small-get p99 (no starvation), in-flight pull bytes <= budget
# (from PULL_ACTIVATE flight-recorder events), zero wedged gets, no
# leaked pool bytes, and that every injected storage fault ends in
# backpressure / OutOfMemoryError / lineage reconstruction. A host
# without the TCP control plane records pressure_soak_skipped —
# counted, never silent. The full 1 GiB / 8-node soak:
#   python -m ray_tpu._private.ray_perf --only pressure_soak
pressure-smoke:
	JAX_PLATFORMS=cpu python -m ray_tpu._private.ray_perf \
		--only pressure_soak --pressure-smoke \
		--out /tmp/ray_tpu_pressure_smoke.json

# Shared-memory object plane smoke: the node-pool crash-safety suite
# (multi-process bit-exactness, SIGKILL ledger sweep, mid-put partial
# reclamation, cross-process eviction pinning, pool-full -> segment
# ladder) plus the allocator/refcount unit tests. On a host without
# /dev/shm or the C++ toolchain the suite SKIPS each test with a
# counted reason (pytest's skip column) — never silently green.
shm-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_shm_plane.py \
		tests/test_native_store.py -q -p no:cacheprovider -rs

stress:
	$(MAKE) -C native stress-asan
	./ray_tpu/_private/_native/store_stress_asan 30

clean:
	$(MAKE) -C native clean
