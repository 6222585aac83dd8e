"""In-program pipeline parallelism: GPipe-style microbatch rotation.

Reference parity: the reference drives pipeline stages from the host —
compiled-DAG actors shuttling activations through mutable channels
(python/ray/dag/compiled_dag_node.py) or third-party integrations; it
has no native in-graph PP training path (SURVEY.md §2.3 flags this as a
rebuild goal). On TPU the idiomatic design is the opposite of
host-driven: put the *whole* pipeline schedule inside one jitted SPMD
program over a `pipe` mesh axis and let collective permutes move
activations over ICI.

Design (the scaling-book recipe):
  - Each device along the `pipe` axis holds ONE stage's parameters
    (a pytree stacked on a leading axis of size S = n_stages).
  - The schedule runs T = M + S - 1 ticks (M = n_microbatches). At tick
    t, stage 0 ingests microbatch t while stage s processes the
    activation that entered at tick t - s; between ticks every stage
    hands its output to its right neighbor with one `lax.ppermute`
    (nearest-neighbor ICI hop — the cheapest collective on a torus).
  - Bubble fraction is (S-1)/(M+S-1), exactly the GPipe figure; the
    transform is differentiable (the transpose of ppermute is the
    reverse ppermute), so `jax.grad` of a pipelined loss yields the
    backward pipeline automatically — no hand-written 1F1B schedule,
    XLA overlaps the permutes with stage compute.

Constraints: every stage must map activations of one shape to the same
shape (true for stacked transformer blocks); the microbatched input is
visible to all pipe devices (stage 0 reads it, others ignore it — for
very long inputs shard it on `data`/`seq` axes orthogonal to `pipe`).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(per_stage: Sequence[Any]):
    """Stack S per-stage parameter pytrees on a new leading axis so the
    stack shards one-stage-per-device over the `pipe` axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)


def pipeline_spec(mesh: Mesh, axis: str = "pipe"):
    """(params_spec, replicated_spec) for placing stacked stage params
    and everything else."""
    return NamedSharding(mesh, P(axis)), NamedSharding(mesh, P())


def pipelined(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    *,
    mesh: Mesh,
    axis: str = "pipe",
    n_microbatches: int,
    remat: bool = False,
) -> Callable[[Any, jax.Array], jax.Array]:
    """Lift ``stage_fn(stage_params, x) -> y`` (one pipeline stage) into
    a full S-stage pipelined apply over the mesh's ``axis``.

    Returns ``apply(stacked_params, x)`` where ``stacked_params`` has
    leading axis S (see :func:`stack_stage_params`) and ``x`` is
    ``[M, microbatch, ...]`` (M = ``n_microbatches``). The result is the
    composition stage_{S-1}(...stage_0(x)) per microbatch, replicated
    across the pipe axis. Differentiable; wrap in ``jax.jit`` (or call
    under an outer pjit) for real use.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
    n_stages = mesh.shape[axis]
    M = n_microbatches
    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    def _spmd(stacked_params, x):
        # Inside shard_map: params carry a leading axis of size 1 (this
        # device's stage); x is replicated along `axis`.
        my_params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
        stage_idx = lax.axis_index(axis)
        S = n_stages
        perm = [(i, (i + 1) % S) for i in range(S)]
        out0 = jnp.zeros((M,) + x.shape[1:], x.dtype)
        state0 = jnp.zeros(x.shape[1:], x.dtype)

        def tick(carry, t):
            state, outbuf = carry
            # Stage 0 ingests microbatch t (clamped: ticks >= M are
            # drain-only); downstream stages consume the rotated state.
            x_t = lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
            )
            inp = jnp.where(stage_idx == 0, x_t, state)
            y = stage_fn(my_params, inp)
            # The last stage commits finished microbatch t-(S-1).
            out_t = t - (S - 1)
            valid = jnp.logical_and(
                stage_idx == S - 1,
                jnp.logical_and(out_t >= 0, out_t < M),
            )
            committed = lax.dynamic_update_index_in_dim(
                outbuf,
                jnp.where(valid, y, lax.dynamic_index_in_dim(
                    outbuf, jnp.clip(out_t, 0, M - 1), axis=0, keepdims=False
                )),
                jnp.clip(out_t, 0, M - 1),
                axis=0,
            )
            state = lax.ppermute(y, axis, perm)
            return (state, committed), None

        (_, outbuf), _ = lax.scan(
            tick, (state0, out0), jnp.arange(M + S - 1)
        )
        # Only the last stage holds real outputs; psum over the pipe
        # axis replicates them (everyone else contributes zeros).
        mask = (stage_idx == S - 1).astype(outbuf.dtype)
        return lax.psum(outbuf * mask, axis)

    # A single PartitionSpec acts as a pytree prefix: every param leaf
    # shards its stage axis over `axis`; x and the output replicate.
    apply = shard_map(
        _spmd,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
        axis_names={axis},
    )

    @functools.wraps(stage_fn)
    def wrapped(stacked_params, x):
        if x.shape[0] != M:
            raise ValueError(
                f"expected leading microbatch axis {M}, got {x.shape[0]}"
            )
        return apply(stacked_params, x)

    return wrapped


def sequential_reference(stage_fn, per_stage_params, x):
    """Unpipelined oracle: fold the stages over each microbatch. Used by
    tests to pin pipelined numerics."""
    def one(mb):
        for p in per_stage_params:
            mb = stage_fn(p, mb)
        return mb

    return jax.vmap(one)(x)
