"""ray_tpu.train: distributed training on TPU meshes.

Reference: python/ray/train — trainers, session contract,
checkpointing. See trainer.py for the architecture mapping.
"""
from .checkpoint import AsyncCheckpointer, Checkpoint, load_pytree, save_pytree  # noqa: F401
from .config import (  # noqa: F401
    CheckpointConfig,
    FailureConfig,
    Result,
    RunConfig,
    ScalingConfig,
)
from .session import get_context, report  # noqa: F401
from .step import make_train_step  # noqa: F401
from .trainer import JaxTrainer, get_checkpoint  # noqa: F401

from ray_tpu._private import usage_stats as _usage

_usage.record_library_usage("train")
