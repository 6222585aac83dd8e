"""The four scans of ``ops/kda.py`` (KDA, the scalar, the fixed and the step-
scaled decay) compile ahead of time for a v5e chip, with no chip
(``tests/aot_v5e.py`` has how; ``tests/test_kernels_aot_v5e.py`` the flash
kernels).
"""
import jax.numpy as jnp
import pytest

from ray_tpu.ops import kda

from aot_v5e import _compile_for, _kernels, topo, v5e  # noqa: F401 - fixtures


# The same cell's KDA layers: 32 heads of 128 over 16,384 tokens, q and k
# raw in float32, the output gate and the norm's weight with them: the
# forward kernel (with and without the states) and the backward kernel,
# which differentiates a chunk and its normalisations inside the kernel. And
# Solar-Open2's (pretrain-4k): 64 heads of 128 over 4,096 tokens, where every
# head's running sums are 8 MiB of VMEM, their cotangents as much, the states
# 4 and g's block of every head 2, under the kernels' 64 MiB. Under a
# gradient the forward also writes every chunk's inverse T, a pair's two [64,
# 64] blocks side by side on 128 lanes, and the backward reads it; at an odd
# head count (one head a step) the block is one head's [64, 64].
@pytest.mark.parametrize("t,h", [(16384, 32), (4096, 64), (1024, 3)])
def test_kda_kernels_compile_for_v5e(v5e, t, h):
    b, d = 1, 128
    raw, rows = ((b, t, h * d), jnp.float32), ((b, t, h * d), jnp.bfloat16)
    operands = (raw, raw, rows, raw, ((b, h, t, 1), jnp.float32), rows,
                ((1, d), jnp.float32))
    norm = (d ** -0.5, 1e-6, 1e-5)
    p = kda._heads_a_step(h)
    _compile_for(v5e, lambda *a: kda._forward_pallas(*a, h, norm, states=False), *operands)
    _compile_for(v5e, lambda *a: kda._forward_pallas(*a, h, norm, states=True), *operands)
    _compile_for(
        v5e, lambda *a: kda._backward_pallas(*a, h, norm), *operands,
        ((b, t // kda.CHUNK, d, h * d), jnp.float32),
        ((b, t // kda.CHUNK, h // p, kda.CHUNK, p * kda.CHUNK), jnp.bfloat16), rows,
    )


# Olmo-Hybrid's scalar-decay scan kernels (pretrain-8k): 30 heads whose key
# heads are 96 lanes and value heads 192, neither a whole number of vregs, over
# 8,192 tokens, every operand [B, H, T, d] with a block whole in its last
# extent (q and k the one array [B, 2, H, T, dk] their convolution writes, a
# block of it both), the decay one float a head and token; v, the gate, o and
# their cotangents [B, T, H * dv], two heads' 384 lanes a block; at an odd head
# count the block is one head's and those lie [B, H, T, dv]. No width is
# padded in what the caller hands over.
@pytest.mark.parametrize("t,h", [(8192, 30), (1024, 15)])
def test_gdn_kernels_compile_for_v5e(v5e, t, h):
    b, dk, dv = 1, 96, 192
    raw, scalar = ((b, 2, h, t, dk), jnp.float32), ((b, h, t, 1), jnp.float32)
    assert kda._values_lie_tokens_first(h, dv) == (h == 30)
    rows = ((b, t, h * dv) if h == 30 else (b, h, t, dv), jnp.bfloat16)
    operands = (raw, rows, scalar, scalar, rows, ((1, dv), jnp.float32))
    norm = (dk ** -0.5, 1e-6, 1e-6)
    p = kda._heads_a_step(h)
    _compile_for(v5e, lambda *a: kda._gdn_forward_pallas(*a, norm, states=False), *operands)
    text = _compile_for(
        v5e, lambda *a: kda._gdn_forward_pallas(*a, norm, states=True), *operands)
    assert f"f32[{b},{t // kda.CHUNK},{h},{dv},{dk}]" in text  # the states, as published
    _compile_for(
        v5e, lambda *a: kda._gdn_backward_pallas(*a, norm), *operands,
        ((b, t // kda.CHUNK, h, dv, dk), jnp.float32),
        ((b, t // kda.CHUNK, h // p, kda.CHUNK, p * kda.CHUNK), jnp.bfloat16), rows,
    )


# MiniCPM-SALA's kernels at the benchmark's real size (b1 x s16384): the
# fixed-decay scan at 32 heads of 128 in chunks of 256 rows (and at an odd
# head count), the three sparse kernels at 32 query heads over K and V at
# their own 2, the chosen blocks as [2, T, 128] words.
@pytest.mark.parametrize("t,h", [(16384, 32), (1024, 3)])
def test_lightning_kernels_compile_for_v5e(v5e, t, h):
    b, d = 1, 128
    rows = ((b, h, t, d), jnp.bfloat16)
    operands = (rows, rows, rows, rows, ((1, d), jnp.float32),
                ((h, 1, 128), jnp.float32))
    norm = (d ** -0.5, 1e-6)
    _compile_for(v5e, lambda *a: kda._lightning_forward_pallas(*a, norm, states=False), *operands)
    text = _compile_for(
        v5e, lambda *a: kda._lightning_forward_pallas(*a, norm, states=True), *operands)
    states = (b, h, t // kda.LIGHTNING_CHUNK, d, d)
    assert "f32[%s]" % ",".join(map(str, states)) in text  # float32, a chunk's first
    _compile_for(
        v5e, lambda *a: kda._lightning_backward_pallas(*a, norm), *operands,
        (states, jnp.float32), rows)


@pytest.mark.parametrize("t,h", [(8192, 64), (1024, 24)])
def test_ssd_kernels_compile_for_v5e(v5e, t, h):
    b, p, n, groups = 1, 64, 128, h // 8
    rows, shared = ((b, t, h * p), jnp.bfloat16), ((b, t, n), jnp.bfloat16)
    operands = (rows, ((b, groups, 8, t), jnp.float32), ((groups, 1, 128), jnp.float32),
                ((groups, 4, 128), jnp.float32), shared, shared)
    _compile_for(v5e, lambda *a: kda._ssd_forward_pallas(*a, states=False), *operands)
    forward = _compile_for(
        v5e, lambda *a: kda._ssd_forward_pallas(*a, states=True), *operands)
    states = (b, t // kda.SSD_CHUNK, groups, 4, n, 2 * p)
    assert "f32[%s]" % ",".join(map(str, states)) in forward  # float32, a chunk's first
    backward = _compile_for(
        v5e, kda._ssd_backward_pallas, *operands, (states, jnp.float32), rows)
    assert (_kernels(forward), _kernels(backward)) == (
        ["_ssd_fwd_kernel"], ["_ssd_bwd_kernel"])
