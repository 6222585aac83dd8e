# Repo-root conftest: makes `ray_tpu` importable and pins JAX to a virtual
# 8-device CPU mesh for tests (multi-chip sharding is validated on CPU; the
# real chip is exercised by chip_smoke.py and benchmarks/run.py). The pin
# goes through the environment, which worker processes inherit, and
# through jax.config before any backend initializes.
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

# Lock-order witness (RAY_TPU_lock_witness=1, `make race-smoke`):
# install before the runtime modules under test construct their locks
# so every threading.Lock/RLock they create in this process is
# witnessed. One shared predicate (lock_witness.enabled) gates every
# process — subprocesses (heads, raylets, workers) self-install via
# the same maybe_install() off the inherited env var, so the driver
# can never diverge from the daemons on what counts as "enabled".
from ray_tpu._private import lock_witness as _lock_witness

_lock_witness.maybe_install()

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
