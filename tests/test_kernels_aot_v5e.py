"""Every Pallas kernel compiles for a v5e chip ahead of time, with no chip:
libtpu describes the topology and runs the real Mosaic and XLA:TPU
compilers. Catches a kernel the chip's compiler rejects before a chip run
is spent on it. Shapes are the ones chip_smoke.py and the benchmark run.

This file holds the causal flash kernels. ``tests/aot_v5e.py`` has the
topology, the compile and a cell's lowered step; the other kernel families
(``tests/test_aot_v5e_flash_two_body.py``, ``_flash_masks``, ``_gmm``,
``_scans``, ``_convs``, ``_rotary``, ``_latent``, ``_hyper_connections``) and
each benchmark cell's whole train step (``tests/test_aot_v5e_step_*.py``,
``_steps_*.py``) have a file each, so that the real-size compiles run side by
side and a new cell adds a file."""
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.attention import flash_attention

from aot_v5e import (  # noqa: F401 - fixtures
    _causal_bwd, _causal_fwd, _compile_for, topo, v5e,
)


# (batch*heads, seq, head_dim, block): llama-1b b2 x s2048 as the model
# runs it; mixtral-small's head_dim; ring attention's 512 blocks.
FLASH_SHAPES = [(32, 2048, 128, 1024), (32, 2048, 64, 1024), (32, 2048, 128, 512)]


@pytest.mark.parametrize("bh,t,d,block", FLASH_SHAPES)
def test_flash_fwd_compiles_for_v5e(v5e, bh, t, d, block):
    qkv = ((bh, t, d), jnp.bfloat16)
    _compile_for(v5e, _causal_fwd(block), qkv, qkv, qkv)


@pytest.mark.parametrize("bh,t,d,block", FLASH_SHAPES)
def test_flash_bwd_compiles_for_v5e(v5e, bh, t, d, block):
    qkv = ((bh, t, d), jnp.bfloat16)
    _compile_for(
        v5e, _causal_bwd(block), qkv, qkv, qkv, qkv, ((bh, t), jnp.float32), qkv)


def test_grouped_query_attention_at_64_over_8_lowers_to_the_causal_kernels(
        v5e, monkeypatch):
    """Solar-Open2's GQA layer, 64 q heads over 8 K/V heads of 128 at 4,096
    tokens, through ``flash_attention`` under a gradient: K and V reach the
    causal kernels repeated to q's heads, each K/V head serving 8, and the
    step compiles for the chip."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((1, 64, 4096, 128), jnp.bfloat16, sharding=v5e)
    kv = jax.ShapeDtypeStruct((1, 8, 4096, 128), jnp.bfloat16, sharding=v5e)
    lowered = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )).lower(q, kv, kv)
    text = lowered.as_text()
    for name in ("_fwd", "_bwd_dkv", "_bwd_dq"):
        assert text.count(f'kernel_name = "{name}_kernel"') == 1
    assert "64x4096x128xbf16" in text and "window" not in text
    assert "tpu_custom_call" in lowered.compile().as_text()


# Kimi-Linear's MLA layer (the benchmark's longctx-16k cell): 32 heads over
# 16,384 tokens, q/k heads of 128 + 64 and v heads of 128; sarvam-105b's five
# (pretrain-4k): 64 heads over 4,096 tokens at the same head dims.
@pytest.mark.parametrize("bh,t", [(32, 16384), (64, 4096)])
def test_flash_at_192_and_128_compiles_for_v5e(v5e, bh, t):
    d, d_v, block = 192, 128, 1024
    qk, v = ((bh, t, d), jnp.bfloat16), ((bh, t, d_v), jnp.bfloat16)
    _compile_for(v5e, _causal_fwd(block), qk, qk, v)
    _compile_for(v5e, _causal_bwd(block), qk, qk, v, v, ((bh, t), jnp.float32), v)


def test_causal_flash_at_32_heads_of_64_compiles_for_v5e(v5e):
    """The Granite cell's one attention layer: K and V repeated from 8 to 32
    heads of 64 lanes, b1 x s8192, at the blocks ``flash_attention`` runs."""
    qkv = ((32, 8192, 64), jnp.bfloat16)
    _compile_for(v5e, _causal_fwd(1024), qkv, qkv, qkv)
    _compile_for(
        v5e, _causal_bwd(1024), qkv, qkv, qkv, qkv, ((32, 8192), jnp.float32), qkv)
