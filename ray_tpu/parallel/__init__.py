from ..util import tracing as _tracing
from .mesh import (  # noqa: F401
    MeshSpec,
    LOGICAL_RULES,
    ambient_axes,
    ambient_spec,
    logical_axis_shards,
    logical_sharding,
    shard_params,
    with_logical_constraint,
)

# Every compile from here on is on the train session's record, where one is
# held: a loop imports this package before it places or compiles anything.
_tracing.watch_compiles()
