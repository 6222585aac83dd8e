"""TPU ops: pallas kernels for the paths XLA doesn't already fuse well.

Policy (SURVEY.md §7): let XLA fuse elementwise/norm/rope into matmuls;
hand-write kernels only where blockwise algorithms beat materialization
— attention (flash), which rings where the ambient mesh splits the
sequence.
"""
from .attention import flash_attention, attention_reference  # noqa: F401
