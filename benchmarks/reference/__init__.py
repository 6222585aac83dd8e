"""Plain references: each architecture's forward pass in straightforward
``jax.numpy`` and float32, with no kernel, cache or batching trick. They
import nothing from ``ray_tpu.models`` or ``ray_tpu.ops``."""
