"""Device meshes and sharding rules: the TPU parallelism substrate.

Where the reference scales out with NCCL process groups wired by Train
backends (reference: train/torch/config.py:35 init_process_group,
util/collective nccl groups), a TPU framework declares a
`jax.sharding.Mesh` with named axes and lets XLA compile collectives
over ICI into the program (GSPMD). Five axes cover the strategies in
SURVEY.md §2.3:

  data    -- pure data parallelism (gradient allreduce)
  fsdp    -- data parallelism with sharded params/optimizer (ZeRO-3:
             params all-gathered per layer, grads reduce-scattered)
  seq     -- sequence/context parallelism (ring attention over ICI)
  tensor  -- megatron-style tensor parallelism within a layer
  expert  -- expert parallelism for MoE layers

Logical axis names on arrays map to mesh axes through LOGICAL_RULES
(flax logical-partitioning convention), so models annotate *meaning*
("embed", "heads") and deployment picks the mesh.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..util import tracing

AXIS_ORDER = ("data", "fsdp", "seq", "tensor", "expert")

# logical axis -> mesh axis (or tuple of mesh axes). First matching rule
# wins; None means replicate.
LOGICAL_RULES: List[Tuple[str, Any]] = [
    ("batch", ("data", "fsdp")),
    ("seq", "seq"),
    ("embed", "fsdp"),
    ("heads", "tensor"),
    ("kv_heads", "tensor"),
    ("qkv", None),
    ("mlp", "tensor"),
    ("vocab", "tensor"),
    ("expert", "expert"),
    # Batch rows of an MoE capacity buffer [E, B, C, D]. A slot is
    # per-token work with no sequence axis left in it, so the chips of a
    # "seq" group, which hold the same experts, share out the rows.
    ("expert_batch", ("data", "fsdp", "seq")),
    ("norm", None),
    ("head_dim", None),
]


@dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape; ScalingConfig carries one of these
    (reference equivalent: ScalingConfig num_workers/use_gpu —
    air/config.py — reimagined as axis sizes over a TPU slice)."""

    data: int = 1
    fsdp: int = 1
    seq: int = 1
    tensor: int = 1
    expert: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {
            "data": self.data,
            "fsdp": self.fsdp,
            "seq": self.seq,
            "tensor": self.tensor,
            "expert": self.expert,
        }

    @property
    def num_devices(self) -> int:
        return self.data * self.fsdp * self.seq * self.tensor * self.expert

    @classmethod
    def for_devices(cls, n: int, *, strategy: str = "fsdp") -> "MeshSpec":
        """Fill one axis with all devices (simple presets)."""
        if strategy not in AXIS_ORDER:
            raise ValueError(f"strategy must be one of {AXIS_ORDER}")
        return cls(**{strategy: n})

    def build(self, devices: Optional[Sequence[Any]] = None) -> Mesh:
        devices = list(devices if devices is not None else jax.devices())
        if len(devices) < self.num_devices:
            raise ValueError(
                f"MeshSpec needs {self.num_devices} devices, have {len(devices)}"
            )
        devices = devices[: self.num_devices]
        shape = tuple(self.axis_sizes()[a] for a in AXIS_ORDER)
        arr = np.array(devices, dtype=object).reshape(shape)
        return Mesh(arr, AXIS_ORDER)


def mesh_axes_for_logical(logical: str) -> Any:
    for name, axes in LOGICAL_RULES:
        if name == logical:
            return axes
    return None


def logical_to_spec(logical_axes: Sequence[Optional[str]]) -> P:
    """("batch", "seq", "embed") -> PartitionSpec(("data","fsdp"), "seq", "fsdp")."""
    out = []
    used: set = set()
    for ax in logical_axes:
        mesh_axes = mesh_axes_for_logical(ax) if ax is not None else None
        # A mesh axis may appear at most once in a PartitionSpec.
        if mesh_axes is not None:
            flat = mesh_axes if isinstance(mesh_axes, tuple) else (mesh_axes,)
            if any(a in used for a in flat):
                mesh_axes = None
            else:
                used.update(flat)
        out.append(mesh_axes)
    return P(*out)


def logical_sharding(mesh: Mesh, logical_axes: Sequence[Optional[str]]) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes))


def ambient_spec(logical_axes: Sequence[Optional[str]]) -> Optional[P]:
    """``logical_to_spec`` over the axes the ambient mesh has
    (`jax.set_mesh`); None when there is no ambient mesh."""
    axis_names = jax.sharding.get_abstract_mesh().axis_names
    if not axis_names:
        return None
    cleaned = []
    for entry in logical_to_spec(logical_axes):
        if entry is None:
            cleaned.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in axis_names)
            cleaned.append(kept if kept else None)
        else:
            cleaned.append(entry if entry in axis_names else None)
    return P(*cleaned)


def with_logical_constraint(x, logical_axes: Sequence[Optional[str]]):
    """In-jit sharding constraint by logical axis names. No-op when there
    is no ambient mesh (single-device runs, unit tests) so model code can
    annotate unconditionally."""
    spec = ambient_spec(logical_axes)
    return x if spec is None else jax.lax.with_sharding_constraint(x, spec)


def ambient_axes(logical: str) -> Tuple[str, ...]:
    """The ambient mesh's axes that split a logical axis (none with no
    ambient mesh)."""
    spec = ambient_spec((logical,))
    entry = spec[0] if spec is not None else None
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def logical_axis_shards(logical: str) -> int:
    """How many ways the ambient mesh splits a logical axis (1 with no
    ambient mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    return math.prod(mesh.shape[a] for a in ambient_axes(logical))


def spec_for_param(path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
    """Heuristic PartitionSpec for a parameter by name, used when a model
    doesn't carry explicit logical axes. Matmul weights shard (in=fsdp,
    out=tensor); embeddings shard (vocab=tensor, embed=fsdp); 1-D scales
    replicate."""
    if len(shape) <= 1:
        return P()
    name = "/".join(str(p) for p in path).lower()
    if "embed" in name and len(shape) == 2:
        return P("tensor", "fsdp")
    if len(shape) == 2:
        return P("fsdp", "tensor")
    if len(shape) == 3 and ("expert" in name or "w_gate" in name
                            or "w_up" in name or "w_down" in name):
        # Stacked MoE expert weights [E, in, out]: expert-parallel first
        # axis, then the usual (fsdp, tensor) matmul split.
        if "w_down" in name:
            return P("expert", "tensor", "fsdp")
        return P("expert", "fsdp", "tensor")
    if len(shape) == 3:  # e.g. (heads, head_dim, embed) attention proj
        return P("tensor", None, "fsdp")
    return P(*([None] * len(shape)))


def shard_params(params, mesh: Mesh, rules=None):
    """Place a parameter pytree on the mesh: explicit flax
    ``nn.with_partitioning`` metadata wins; otherwise spec_for_param."""
    def place(path, leaf):
        spec = spec_for_param(
            tuple(getattr(p, "key", getattr(p, "idx", "")) for p in path),
            getattr(leaf, "shape", ()),
        )
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    # The span is the host placing the leaves: device_put returns before a
    # copy ends, and nothing here waits for one.
    with tracing.span(tracing.SHARD_PARAMS):
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        leaves = [place(path, leaf) for path, leaf in flat]
        treedef = jax.tree_util.tree_structure(params)
        return jax.tree_util.tree_unflatten(treedef, leaves)


def pad_to_multiple(n: int, k: int) -> int:
    return int(math.ceil(n / k) * k)
