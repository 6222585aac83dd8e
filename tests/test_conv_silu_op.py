"""The short convolution of ``ray_tpu/ops/kda.py`` on the CPU: ``short_conv``,
and the convolution with its SiLU and v's rounding as one Pallas pass forward
and one backward (``conv_silu``) against ``silu(short_conv)`` and its
gradients, across block and tile edges, at the sequence's start, over batch
rows, rounded to bfloat16, heads first, and where a shape does not tile; the
lanes a block takes; the text the kernels lowered to before they took heads.

One of the six kernel families of ``ray_tpu/ops/kda.py``, a test file each
(ROADMAP C15's seams: the module's split moves one test file with each
family).
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

from kda_cases import (
    CONV_BEFORE, CONV_CASES, conv_and_gradients, conv_calls_text, conv_inputs,
    conv_reference, pallas_calls,
)


def test_short_conv_is_causal_and_depthwise():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 10, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    y = np.asarray(kda.short_conv(x, w))
    xn, wn = np.asarray(x), np.asarray(w)
    for t in range(10):
        want = sum(wn[i] * xn[:, t - 3 + i] for i in range(4) if t - 3 + i >= 0)
        np.testing.assert_allclose(y[:, t], want, rtol=1e-5, atol=1e-6)
    # a later token changes no earlier output, a channel no other channel
    y2 = np.asarray(kda.short_conv(x.at[:, 7, 2].add(1.0), w))
    assert (y2[:, :7] == y[:, :7]).all()
    assert (np.delete(y2, 2, axis=2) == np.delete(y, 2, axis=2)).all()


def tokens_first(y):
    """[B, D / d, T, d], heads first, as [B, T, D]: channel h * d + c from [h, :, c]."""
    return y.transpose(0, 2, 1, 3).reshape(y.shape[0], y.shape[2], -1)


@pytest.mark.parametrize("case", sorted(CONV_CASES))
def test_the_fused_convolution_is_silu_of_short_conv_and_its_gradients(monkeypatch, case):
    """Under the interpreter ``conv_silu`` is the Pallas pass where the shape
    tiles and ``silu(short_conv(x, w))`` as XLA has it where it does not:
    the values and the gradients in x and in w, to float32's reassociation
    (the filter's gradient is a sum over every token, in another order).
    Told a head's lanes, the output and its cotangent lie [B, D / d, T, d]:
    ``silu(short_conv)`` transposed, either way."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    batch, t, channels, dtype, heads, blocks = CONV_CASES[case]
    x, w, dy = conv_inputs(batch, t, channels, dtype, heads=heads)
    assert kda._conv_blocks(x, w, heads) == blocks
    both = jax.make_jaxpr(
        lambda *a: conv_and_gradients(kda.conv_silu, *a, heads=heads))(x, w, dy)
    names = [eqn.params["jaxpr"].debug_info.func_name for eqn in pallas_calls(both.jaxpr, [])]
    assert names == (["_conv_fwd_kernel", "_conv_bwd_kernel"] if blocks else [])
    y, dx, dw = conv_and_gradients(kda.conv_silu, x, w, dy, heads=heads)
    y_ref, dx_ref, dw_ref = conv_and_gradients(conv_reference, x, w, dy, heads=heads)
    assert (y.dtype, dx.dtype, dw.dtype) == (dtype, jnp.float32, jnp.float32)
    assert y.shape == dy.shape == (
        (batch, channels // heads, t, heads) if heads else (batch, t, channels))
    if blocks is None:
        assert all(bool((a == b).all()) for a, b in ((y, y_ref), (dx, dx_ref), (dw, dw_ref)))
        return
    if dtype == jnp.bfloat16:  # one rounding, of float32 values an ulp apart at most
        assert float(jnp.mean(y != y_ref)) < 1e-3
    np.testing.assert_allclose(
        y.astype(jnp.float32), y_ref.astype(jnp.float32),
        rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-6)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dw, dw_ref, rtol=1e-5, atol=1e-5 * float(jnp.abs(dw_ref).max()))


@pytest.mark.parametrize("heads", [None, 64], ids=["tokens-first", "heads-of-64"])
def test_the_fused_convolution_is_causal_across_its_blocks_and_depthwise(monkeypatch, heads):
    """A bump at token 7 moves nothing before it and nothing after token 10,
    one at a block's last token moves the next block's first three (the
    halo), and neither moves another channel or batch row; the gradient in x
    reaches back as far and no further. Blocks of 32 rows in tiles of 16;
    heads first, two heads of 64 lanes to the block's 128, read back as they
    lie tokens first."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(kda, "_CONV_ROWS", 32)
    monkeypatch.setattr(kda, "_CONV_TILE", 16)
    x, w, _ = conv_inputs(2, 96, 128, jnp.float32, seed=1)
    assert kda._conv_blocks(x, w, heads) == (32, 128, 16, True, heads or 0)

    def conv(x):
        y = kda.conv_silu(x, w, heads=heads)
        return tokens_first(y) if heads else y

    y = np.asarray(conv(x))
    np.testing.assert_allclose(y, conv_reference(x, w, jnp.float32), rtol=1e-5, atol=1e-6)
    for token in (7, 15, 31, 95):
        moved = np.asarray(conv(x.at[1, token, 2].add(1.0))) != y
        assert moved[1, token:token + 4, 2].all()
        moved[1, token:token + 4, 2] = False
        assert not moved.any(), token
        # dy at tokens token .. token + 3 reaches x at token, and at no other
        reach = jax.grad(lambda x: conv(x)[1, token:token + 4, 2].sum())(x)
        reached = np.argwhere(np.asarray(reach) != 0)
        assert {tuple(at[[0, 2]]) for at in reached} == {(1, 2)}
        assert set(reached[:, 1]) == set(range(max(token - 3, 0), min(token + 4, 96)))


def test_the_convolutions_lanes_are_the_most_vregs_that_divide_the_channels(monkeypatch):
    """5,760 channels (Olmo-Hybrid's q with k, and its v) are 45 vregs, which
    no power of two above one divides: blocks of 384 lanes; 2,880 alone do not
    tile; 4,096 and 8,192 keep their 512."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    w = jax.ShapeDtypeStruct((4, 1), jnp.float32)
    lanes = lambda d: kda._conv_blocks(jax.ShapeDtypeStruct((1, 8192, d), jnp.float32), w)  # noqa: E731
    assert (lanes(5760).lanes, lanes(4096).lanes, lanes(8192).lanes, lanes(384).lanes) == (
        384, 512, 512, 384)
    assert lanes(2880) is None
    # whole heads too, where the output lies heads first: four of 96 or two of
    # 192 are the 384, 128 fill the 512, and 96 of 4,096 channels fit no block
    heads = lambda d, n: kda._conv_blocks(  # noqa: E731
        jax.ShapeDtypeStruct((1, 8192, d), jnp.float32), w, n)
    assert (heads(5760, 96).lanes, heads(5760, 192).lanes, heads(4096, 128).lanes) == (
        384, 384, 512)
    assert heads(4096, 96) is None
    x, wts, _ = conv_inputs(1, 64, 384, jnp.float32)
    np.testing.assert_allclose(
        kda.conv_silu(x, wts), conv_reference(x, wts, jnp.float32), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", sorted(CONV_BEFORE))
def test_without_heads_the_convolution_lowers_what_it_lowered(monkeypatch, name):
    """``heads=None`` changes no operand, block, index map or operation of
    either kernel: the forward and backward calls at the widths of the three
    cells that convolve tokens first are, as text, what they were before the
    output could lie heads first. And told a head's lanes the same shape
    traces to another text: the digest sees the layout."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    shape, before = CONV_BEFORE[name]
    text = conv_calls_text(*shape)
    assert hashlib.sha1(text.encode()).hexdigest()[:16] == before
    t, channels, dtype, biased = shape
    x = jax.ShapeDtypeStruct((1, t, channels), jnp.float32)
    w = jax.ShapeDtypeStruct((4, channels), jnp.float32)
    first = jax.make_jaxpr(lambda x, w: kda.conv_silu(x, w, dtype, heads=128))(x, w)
    (call,) = pallas_calls(first.jaxpr, [])
    assert str(call.params["jaxpr"]) not in text
    assert call.outvars[0].aval.shape == (1, channels // 128, t, 128)
