"""The comparison that decides ``correct``, and the counters it rests on."""
from __future__ import annotations

import math

from .cells import stated_kernels

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


class CompileCounter:
    """Counts JAX's compile events, as ``chip_smoke.py`` does: requests that
    went to the persistent cache, the hits among them, and backend compiles.
    ``snapshot()`` before and after a window shows what compiled inside."""

    def __init__(self):
        self.requests = self.hits = self.backend_compiles = 0

    def install(self):
        import jax

        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "backend_compiles": self.backend_compiles}


def count_pallas_kernels(lowered_text: str, names) -> dict:
    return {k: lowered_text.count(f'kernel_name = "{k}"') for k in names}


def holds_stated_kernels(counts: dict, stated: dict) -> bool:
    """Every kernel the configuration states (``cells.stated_kernels``), at
    least its stated count among the lowered step's ``counts``."""
    return all(counts.get(kernel, 0) >= s["least"] for kernel, s in stated.items())


def count_collectives(compiled_text: str) -> dict:
    return {op: compiled_text.count(f" {op}(") + compiled_text.count(f" {op}-start(")
            for op in COLLECTIVES}


def logits_agreement(system_logits, reference_logits, tolerance: dict) -> dict:
    """Per-position relative error of [T, V] logits against the reference,
    held to the tolerance the reference's file states."""
    import jax.numpy as jnp

    sys32 = system_logits.astype(jnp.float32)
    err = jnp.linalg.norm(sys32 - reference_logits, axis=-1)
    rel = err / jnp.linalg.norm(reference_logits, axis=-1)
    within = float(jnp.mean(rel <= tolerance["per_position_rel_err"]))
    out = {
        "positions": int(rel.shape[0]),
        "rel_err_median": float(jnp.median(rel)),
        "rel_err_max": float(jnp.max(rel)),
        "share_within": within,
        "tolerance": tolerance,
    }
    out["ok"] = bool(
        math.isfinite(out["rel_err_max"])
        and within >= tolerance["min_share_within"]
    )
    return out


def decide(setup: dict, steps: list, final: dict, cell: dict) -> dict:
    """Every condition of ``correct`` by name; the run is correct when all
    hold. ``steps`` are the window's steps in order."""
    traffic = cell["traffic"]
    losses = [s["loss"] for s in steps]
    # A pass over the corpus, or half the window where it holds fewer than two.
    n = min(traffic["batches"], len(losses) // 2)
    checks = {
        "reference_agrees": setup["reference"]["ok"],
        "losses_finite": bool(losses) and all(math.isfinite(l) for l in losses),
        # The corpus is cycled, so the window's last pass sees again what
        # its first pass saw.
        "loss_fell": n > 0 and sum(losses[-n:]) / n < sum(losses[:n]) / n,
        "nothing_compiled_in_window":
            final["compiles_after"] == final["compiles_before"],
        "device_count": setup["device_count"] == cell["chips"],
        "mesh": setup["mesh"] == {
            axis: size for axis, size in traffic["mesh"].items() if size > 1
        },
    }
    expected = traffic.get("expect", {})
    if "moe_dispatch" in expected:
        checks["moe_dispatch"] = setup["moe_dispatch"] == expected["moe_dispatch"]
    if not setup["rehearsal"]:
        checks["pallas_kernels"] = holds_stated_kernels(
            setup["pallas_kernels"], stated_kernels(cell)
        )
        checks["on_tpu"] = setup["platform"] == "tpu"
        if cell["chips"] > 1:
            checks["params_split"] = bool(final["params_split"]) and len(
                final["param_devices"]
            ) == cell["chips"]
            if setup["collectives"] is not None:  # counted in a traced run
                checks["collectives"] = sum(setup["collectives"].values()) > 0
    return checks
