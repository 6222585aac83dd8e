"""The convolutions of ``ops/kda.py`` (tokens first, heads first, with a bias,
gated) compile ahead of time for a v5e chip, with no chip
(``tests/aot_v5e.py`` has how; ``tests/test_kernels_aot_v5e.py`` the flash
kernels).
"""
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import kda

from aot_v5e import _compile_for, _kernels, topo, v5e  # noqa: F401 - fixtures


# The KDA mixer's convolution, SiLU and rounding as one pass, over one
# projection of longctx-16k's (b1 x s16384, 32 heads of 128) and of
# Solar-Open2's (b1 x s4096, 64 heads of 128) at the blocks ``conv_silu``
# gives them: float32 out for q and k, bfloat16 for v, whose cotangent comes
# back in bfloat16 with a halo of 16 rows.
@pytest.mark.parametrize("t,channels", [(16384, 4096), (4096, 8192)])
def test_conv_kernels_compile_for_v5e(v5e, monkeypatch, t, channels):
    import base64
    import re

    from benchmarks.lib import trace

    monkeypatch.setattr(kda._attention, "_on_tpu", lambda: True)
    x, w = ((1, t, channels), jnp.float32), ((4, channels), jnp.float32)
    blocks = kda._conv_blocks(jax.ShapeDtypeStruct(*x), jax.ShapeDtypeStruct(*w))
    assert blocks == (512, 512, 64, False, 0)

    def kernels(text):
        """As a profile's reader names them: the backward's module holds no
        frame of the forward's, which is traced first."""
        return [trace.kernel_name(line) for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line]

    for dtype in (jnp.float32, jnp.bfloat16):
        forward = _compile_for(
            v5e, lambda x, w: kda._conv_forward(x, w, jnp.dtype(dtype), blocks), x, w)
        backward = _compile_for(
            v5e, lambda x, w, dy: kda._conv_backward(x, w, dy, blocks), x, w, (x[0], dtype))
        assert (kernels(forward), kernels(backward)) == (
            ["_conv_fwd_kernel"], ["_conv_bwd_kernel"])
        module = re.search(r'"body":"([^"]*)"', backward).group(1)
        assert b"_conv_fwd_kernel" not in base64.b64decode(module)


# Olmo-Hybrid's two convolution passes (b1 x s8192, 5,760 channels) writing
# heads first, [B, D / d, T, d], and reading their cotangents there: q with k
# at 60 heads of 96 lanes, float32, four heads to a block of 384 lanes (a
# head's lanes begin inside a vreg: the store is a lane rotation and a masked
# store, the cotangent's tile is put together in VMEM); v at 30 heads of 192,
# bfloat16 out and back, two heads to a block.
@pytest.mark.parametrize("d,dtype", [(96, jnp.float32), (192, jnp.bfloat16)])
def test_conv_kernels_compile_for_v5e_heads_first(v5e, monkeypatch, d, dtype):
    monkeypatch.setattr(kda._attention, "_on_tpu", lambda: True)
    t, channels = 8192, 5760
    x, w = ((1, t, channels), jnp.float32), ((4, channels), jnp.float32)
    blocks = kda._conv_blocks(jax.ShapeDtypeStruct(*x), jax.ShapeDtypeStruct(*w), d)
    assert blocks == (512, 384, 64, False, d)
    forward = _compile_for(
        v5e, lambda x, w: kda._conv_forward(x, w, jnp.dtype(dtype), blocks), x, w)
    assert f"[1,{channels // d},{t},{d}]" in forward
    _compile_for(v5e, lambda x, w, dy: kda._conv_backward(x, w, dy, blocks), x, w,
                 ((1, channels // d, t, d), dtype))


def test_the_biased_convolution_compiles_for_v5e_at_4352_channels(v5e, monkeypatch):
    """x, B and C of a Granite layer together: 34 vregs of lanes, which no
    512 and no 384 divide, in blocks of 256; the bias an operand of both
    kernels and its cotangent an output of the backward one."""
    monkeypatch.setattr(kda._attention, "_on_tpu", lambda: True)
    t, channels = 8192, 4352
    x, w, b = ((1, t, channels), jnp.float32), ((4, channels), jnp.float32), (
        (1, channels), jnp.float32)
    blocks = kda._conv_blocks(jax.ShapeDtypeStruct(*x), jax.ShapeDtypeStruct(*w))
    assert blocks == (512, 256, 64, False, 0)
    forward = _compile_for(
        v5e, lambda x, w, b: kda._conv_forward(x, w, jnp.dtype(jnp.bfloat16), blocks, b),
        x, w, b)
    backward = _compile_for(
        v5e, lambda x, w, dy, b: kda._conv_backward(x, w, dy, blocks, b),
        x, w, (x[0], jnp.bfloat16), b)
    assert (_kernels(forward), _kernels(backward)) == (
        ["_conv_fwd_kernel"], ["_conv_bwd_kernel"])
    assert f"f32[1,{channels}]" in backward  # the bias's cotangent


# LFM2's gated convolution over the cell's projection (b2 x s4096, three thirds
# of 2,048 channels, bfloat16 in and out): the thirds read where they lie under
# a halo of 16 rows, and the pass back over a grid with the thirds as its
# innermost axis, at the blocks ``gated_conv`` gives them.
def test_gated_conv_kernels_compile_for_v5e(v5e, monkeypatch):
    import base64
    import re

    from benchmarks.lib import trace

    monkeypatch.setattr(kda._attention, "_on_tpu", lambda: True)
    p, w = ((2, 4096, 6144), jnp.bfloat16), ((3, 2048), jnp.bfloat16)
    y = ((2, 4096, 2048), jnp.bfloat16)
    blocks = kda._gated_blocks(jax.ShapeDtypeStruct(*p), jax.ShapeDtypeStruct(*w))
    assert blocks == (512, 512, 64, False, 0)

    def kernels(text):
        return [trace.kernel_name(line) for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line]

    forward = _compile_for(
        v5e, lambda p, w: kda._gated_forward(p, w, jnp.dtype(jnp.bfloat16), blocks), p, w)
    backward = _compile_for(
        v5e, lambda p, w, dy: kda._gated_backward(p, w, dy, blocks), p, w, y)
    assert (kernels(forward), kernels(backward)) == (
        ["_gated_conv_fwd_kernel"], ["_gated_conv_bwd_kernel"])
    # the cotangent of the projection's output leaves whole, in its own dtype
    assert "bf16[2,4096,6144]" in backward and "f32[2,4096,6144]" not in backward
    module = re.search(r'"body":"([^"]*)"', backward).group(1)
    assert b"_conv_fwd_kernel" not in base64.b64decode(module).replace(
        b"_gated_conv_fwd_kernel", b"")
