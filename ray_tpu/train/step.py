"""The jitted optimizer step shared by train loops, the benchmark and
chip_smoke.py."""
from __future__ import annotations

from functools import partial
from typing import Any, Callable


def make_train_step(loss_fn: Callable[..., Any], tx) -> Callable[..., Any]:
    """``step(params, opt_state, *batch) -> (params, opt_state, loss)``
    for ``loss_fn(params, *batch)`` under the optax transform ``tx``.

    params and opt_state are donated: the step consumes the old buffers
    in place, so old and new copies never coexist in device memory.
    Callers rebind both from the result.
    """
    import jax
    import optax

    from ..util import tracing

    tracing.watch_compiles()

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, *batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        with tracing.scope(tracing.OPTIMIZER):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step
