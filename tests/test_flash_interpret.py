"""Pallas flash kernels vs the XLA oracle, on CPU via interpret mode.

The kernels normally run only on real TPU; interpret mode executes the
same kernel code (including the causal block-skip control flow added
for long-context perf) bit-accurately on CPU, so CI covers fwd+bwd
numerics without a chip.

This file holds the kernels against the reference. Each tile's position
(``tests/test_flash_tile_classes.py``), the three products over every tiling
at one pair of head widths a file (``tests/test_flash_tiles_*.py``,
``tests/test_flash_tile_gradients_*.py``) and the traced texts
(``tests/test_flash_kernel_texts.py``) are beside it, over
``tests/flash_cases.py``.
"""
import jax
import numpy as np
import pytest

from ray_tpu.ops.attention import attention_reference, flash_attention

from flash_cases import _interpret_mode, _rand  # noqa: F401 - fixtures


@pytest.mark.parametrize(
    "tq,tk,bq,bk,causal",
    [
        (512, 512, 128, 128, True),   # 4x4 grid: skip logic active
        (512, 512, 128, 256, True),   # uneven q/k blocks across diagonal
        (384, 512, 128, 128, True),   # tq != tk (kv-cache decode chunk)
        (512, 512, 128, 128, False),  # no skipping path
        (500, 500, 128, 128, True),   # padded tails
    ],
)
def test_flash_fwd_bwd_matches_reference(tq, tk, bq, bk, causal):
    B, H, D = 1, 2, 64
    q = _rand((B, H, tq, D), 0)
    k = _rand((B, H, tk, D), 1)
    v = _rand((B, H, tk, D), 2)

    def f_flash(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk
        ).sum()

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=causal).sum()

    o_flash = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    o_ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(o_flash), np.asarray(o_ref), atol=2e-3, rtol=2e-3
    )

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3
        )


def test_flash_gqa_heads():
    B, H, HKV, T, D = 1, 4, 2, 256, 64
    q = _rand((B, H, T, D), 3)
    k = _rand((B, HKV, T, D), 4)
    v = _rand((B, HKV, T, D), 5)
    o_flash = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    o_ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(o_flash), np.asarray(o_ref), atol=2e-3, rtol=2e-3
    )


def test_causal_rejects_more_queries_than_keys():
    q = _rand((1, 2, 256, 64), 6)
    k = _rand((1, 2, 128, 64), 7)
    v = _rand((1, 2, 128, 64), 8)
    with pytest.raises(ValueError, match="Tq <= Tk"):
        flash_attention(q, k, v, causal=True)
