"""The grouped matmuls (``gmm``, ``tgmm``) and ``pairs_summed`` compile ahead of
time for a v5e chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.gmm import _tgmm_pallas, gmm, pairs_summed

from aot_v5e import _compile_for, topo, v5e  # noqa: F401 - fixtures


# mixtral-small: b2 x s2048 tokens x top-2 pairs padded to 128-row tiles
# per expert, hidden 1024 <-> expert width 3584 (w_gate/w_up and w_down).
# OLMoE-1B-7B (the benchmark's dropless-4k cell): b2 x s4096 x top-8 pairs
# over 64 experts, hidden 2048 <-> expert width 1024.
@pytest.mark.parametrize("m,experts,k,n", [
    (2 * 2048 * 2 + 8 * 128, 8, 1024, 3584),
    (2 * 2048 * 2 + 8 * 128, 8, 3584, 1024),
    (2 * 4096 * 8 + 64 * 128, 64, 2048, 1024),
    (2 * 4096 * 8 + 64 * 128, 64, 1024, 2048),
])
def test_gmm_and_its_gradient_compile_for_v5e(v5e, m, experts, k, n):
    operands = (
        ((m, k), jnp.bfloat16), ((experts, k, n), jnp.bfloat16),
        ((m // 128,), jnp.int32),
    )
    _compile_for(v5e, gmm, *operands)
    # dlhs (the same kernel on transposed weights) and drhs (_tgmm).
    _compile_for(
        v5e,
        lambda lhs, rhs, tg: jax.grad(
            lambda lhs, rhs: gmm(lhs, rhs, tg).astype(jnp.float32).sum(),
            argnums=(0, 1),
        )(lhs, rhs),
        *operands,
    )


# The same cell's expert layer: 16 held experts of 2304 x 1024 over a
# layout bounded at every pair of 16,384 tokens x top-8 (+ 17 tiles), told
# how many tiles hold rows. And pretrain-4k's: 8 held experts of 4096 x 2048
# over every pair of 4,096 tokens x top-8 (+ 9 tiles).
@pytest.mark.parametrize("tokens,experts,k,n", [
    (16384, 16, 2304, 1024), (16384, 16, 1024, 2304),
    (4096, 8, 4096, 2048), (4096, 8, 2048, 4096),
])
def test_bounded_gmm_and_its_gradient_compile_for_v5e(v5e, tokens, experts, k, n):
    m = tokens * 8 + (experts + 1) * 128
    text = _compile_for(
        v5e,
        lambda lhs, rhs, tg, used: jax.grad(
            lambda a, b: gmm(a, b, tg, 128, used).astype(jnp.float32).sum(), (0, 1)
        )(lhs, rhs),
        ((m, k), jnp.bfloat16), ((experts, k, n), jnp.bfloat16),
        ((m // 128,), jnp.int32), ((1,), jnp.int32),
    )
    assert text.count("tpu_custom_call") >= 2  # dlhs and drhs


# A held share's rows back to tokens (PR 70), at the four cells that gather:
# (tokens, top-k, hidden, experts held). The layout is bounded at every pair,
# in whole windows of 16 tiles; a present pair's DMA slices the 8-row tile its
# row lies in, which Mosaic takes where it refuses a slice of one row.
@pytest.mark.parametrize("tokens,k,d,held", [
    (8192, 8, 5120, 8), (16384, 8, 2048, 32), (4096, 8, 4096, 8), (4096, 4, 3584, 16),
], ids=["dots3", "laguna", "solar", "xing4"])
def test_pairs_summed_compiles_for_v5e(v5e, tokens, k, d, held):
    m_pad = -(-(tokens * k + (held + 1) * 128) // 2048) * 2048
    rows, pairs = ((m_pad, d), jnp.bfloat16), ((tokens, k), jnp.int32)
    _compile_for(v5e, pairs_summed, rows, pairs, ((tokens, k), jnp.bfloat16))
    text = _compile_for(v5e, pairs_summed, rows, pairs)
    # Nothing of [tokens, k, d] is made beside the kernel.
    assert f"bf16[{tokens},{k},{d}]" not in text and f"f32[{tokens},{k},{d}]" not in text


# The Mixtral cell's capacity FFN (ep2seq2-4k): a chip's four experts of
# 4096 x 14336, the weights' gradients over 19 stacked trips of 512 rows, a
# trip a tile of one group: (2048, 2048) blocks with their float32
# accumulator, 40 MiB of VMEM by the rule's own count.
@pytest.mark.parametrize("k,n", [(4096, 14336), (14336, 4096)])
def test_tgmm_over_the_capacity_ffns_trips_compiles_for_v5e(v5e, k, n):
    m = 19 * 512
    _compile_for(
        v5e, lambda lhs, dout, tg: _tgmm_pallas(lhs, dout, tg, 4, 512),
        ((m, k), jnp.bfloat16), ((m, n), jnp.bfloat16), ((19,), jnp.int32),
    )
