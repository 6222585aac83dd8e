"""The two-body causal flash kernels at the benchmark's cells' shapes compile
ahead of time for a v5e chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import jax.numpy as jnp
import pytest

from aot_v5e import (  # noqa: F401 - fixtures
    _causal_bwd, _causal_fwd, _compile_for, topo, v5e,
)


# The two-body causal kernels (PR 46) at the shapes of the benchmark's cells
# that the cases above and ``test_flash_at_192_and_128`` leave out, and where
# the diagonal is moved or a key block padded: (bh, tq, tk, d, d_v, block).
TWO_BODY_SHAPES = [
    (32, 16384, 16384, 128, 128, 1024),  # long16k: 120 interior, 16 edge, 120 dead
    (48, 16384, 16384, 128, 128, 1024),  # Laguna's full layers
    (64, 512, 512, 128, 128, 1024),      # sft512: one tile, an edge one
    (32, 2048, 4096, 128, 128, 1024),    # tq < tk: a prefill chunk behind a cache
    (32, 2048, 3000, 128, 128, 1024),    # tq < tk and a padded last key block
    (64, 3000, 3000, 192, 128, 1024),    # padded rows and keys at 192/128
    (64, 4096, 4096, 128, 128, 1024),    # Solar-Open2's GQA layer: K/V repeated 8 -> 64
]


@pytest.mark.parametrize("bh,tq,tk,d,d_v,block", TWO_BODY_SHAPES)
def test_two_body_flash_kernels_compile_for_v5e(v5e, bh, tq, tk, d, d_v, block):
    q, k = ((bh, tq, d), jnp.bfloat16), ((bh, tk, d), jnp.bfloat16)
    v, o = ((bh, tk, d_v), jnp.bfloat16), ((bh, tq, d_v), jnp.bfloat16)
    _compile_for(v5e, _causal_fwd(block), q, k, v)
    _compile_for(v5e, _causal_bwd(block), q, k, v, o, ((bh, tq), jnp.float32), o)
