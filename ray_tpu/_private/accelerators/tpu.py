"""TPU accelerator manager.

Reference: _private/accelerators/tpu.py (TPUAcceleratorManager:71) —
chip detection via /dev/accel*|/dev/vfio (:98-117), GCE-metadata / GKE
env probing for accelerator type and pod topology (:48-68),
TPU_VISIBLE_CHIPS + TPU_CHIPS_PER_HOST_BOUNDS for sub-host slicing
(:155+), and synthetic `TPU-{version}-head` / pod-name resources for
gang placement (:334). Detection here is env/device-file based only
(no metadata-server calls under zero egress; GKE sets the env vars).
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, MutableMapping, Optional

from .accelerator import AcceleratorManager

# GKE-injected env vars (reference consts :14-45).
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5litepod-16"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
TPU_NAME_ENV = "TPU_NAME"
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
# Sub-host bounds for 1/2/4-chip slices of a 4-chip host (:40-45).
TPU_CHIPS_PER_HOST_BOUNDS_1_CHIP = "1,1,1"
TPU_CHIPS_PER_HOST_BOUNDS_2_CHIP = "1,2,1"
TPU_CHIPS_PER_HOST_BOUNDS_4_CHIP = "2,2,1"


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        env = os.environ.get("RAY_TPU_NUM_CHIPS")
        if env is not None:
            return int(env)
        chips = glob.glob("/dev/accel*")
        if chips:
            return len(chips)
        try:
            vfio = [
                p
                for p in glob.glob("/dev/vfio/*")
                if os.path.basename(p).isdigit()
            ]
            if vfio:
                return len(vfio)
        except OSError:
            pass
        return 0

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        """'v5e', 'v4', ... parsed from the GKE accelerator-type env
        ('v5litepod-16' → 'v5e')."""
        acc = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
        if not acc:
            return None
        gen = acc.split("-")[0].lower()
        return {"v5litepod": "v5e", "v5p": "v5p", "v6e": "v6e"}.get(gen, gen)

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> Optional[str]:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def set_visible_accelerator_ids(env: Dict[str, str], ids: List[str],
                                    host_chips: int) -> None:
        """Make a worker see exactly the chips it was granted
        (reference :155+). A grant of the whole host changes nothing:
        the machine's own TPU environment (its bounds, topology and
        accelerator type) already describes it. A sub-host grant names
        the chips and the bounds by which libtpu carves the host; with
        libtpu 0.0.34 on a four-chip v5e host these two variables give
        1- and 2-chip workers that run side by side (chip probe, PR 21).
        """
        if len(ids) >= host_chips:
            return
        bounds = {
            1: TPU_CHIPS_PER_HOST_BOUNDS_1_CHIP,
            2: TPU_CHIPS_PER_HOST_BOUNDS_2_CHIP,
            4: TPU_CHIPS_PER_HOST_BOUNDS_4_CHIP,
        }.get(len(ids))
        if bounds is None:
            raise ValueError(
                f"no TPU_CHIPS_PER_HOST_BOUNDS for {len(ids)} of "
                f"{host_chips} chips; grants are 1, 2, 4 or the whole host"
            )
        env[TPU_VISIBLE_CHIPS_ENV] = ",".join(ids)
        env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Synthetic gang-placement resources: the pod's worker 0
        carries `TPU-{type}-head` so exactly one actor per slice can
        claim slice leadership, plus a pod-name resource every host
        shares (reference :334)."""
        out: Dict[str, float] = {}
        acc_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        pod_name = os.environ.get(TPU_NAME_ENV)
        worker_id = os.environ.get(TPU_WORKER_ID_ENV)
        if pod_name:
            out[f"TPU-pod-{pod_name}"] = 1.0
        if acc_type and worker_id == "0":
            out[f"TPU-{acc_type}-head"] = 1.0
        return out


class ChipTable:
    """Which worker process holds which chip of one host.

    libtpu gives a chip to one process at a time, so a chip passes on
    only when its holder's process has exited — not when the scheduler
    stops counting the worker: a killed worker keeps its chips until
    the kernel has torn the process down. Not thread-safe: the owning
    control plane (GCS, node daemon) calls it under its own lock."""

    def __init__(self, num_chips: int):
        self.num_chips = num_chips
        # chip -> Popen-shaped holder; None while its process starts.
        self._holder: Dict[int, Any] = {}

    def reserve(self, n: int) -> Optional[List[int]]:
        """Take n free chips for a process about to start, or None
        while fewer than n are free."""
        free = [
            c for c in range(self.num_chips)
            if c not in self._holder
            or (self._holder[c] is not None
                and self._holder[c].poll() is not None)
        ]
        if len(free) < n:
            return None
        for c in free[:n]:
            self._holder[c] = None
        return free[:n]

    def bind(self, chips: List[int], proc) -> None:
        for c in chips:
            self._holder[c] = proc

    def release(self, chips: List[int]) -> None:
        """Give back a reservation whose process never started."""
        for c in chips:
            self._holder.pop(c, None)


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def place_compile_cache(env: MutableMapping[str, str]) -> str:
    """Decide where a process about to compile for the chip keeps JAX's
    persistent compile cache, and return the directory. A directory
    placed from outside (JAX_COMPILATION_CACHE_DIR) is left alone — JAX
    reads the variable itself; otherwise it is a fixed path inside the
    checkout, so that every process and every run finds the same one."""
    return env.setdefault(
        COMPILE_CACHE_ENV, os.path.join(_CHECKOUT, ".jax_cache")
    )
