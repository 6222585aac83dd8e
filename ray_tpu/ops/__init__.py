"""TPU ops: pallas kernels for the paths XLA doesn't already fuse well.

Policy (SURVEY.md §7): let XLA fuse elementwise and norm passes into
matmuls; hand-write kernels only where blockwise algorithms beat
materialization — attention (flash), which rings where the ambient mesh
splits the sequence — or where a traced step shows XLA's own passes far from
their bytes' floor: the rotation of q and k (``rotary.rotate``: XLA's split
of a head's lanes at half a vreg ran at a seventh of it).
"""
from .attention import flash_attention, attention_reference  # noqa: F401
