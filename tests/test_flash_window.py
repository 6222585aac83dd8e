"""The windowed flash kernels against the XLA oracle under the interpreter:
forward and all three gradients where the window is smaller than, equal to
and larger than a block, at a sequence that is no multiple of it, with K and
V at an eighth of q's heads; a window that covers the sequence is the causal
result; an off-by-one window is told apart; a mesh that splits the sequence
refuses it by the axis's name."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import attention_reference, flash_attention


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _qkv(t, heads=8, kv_heads=1, d=16, tk=None):
    rng = np.random.RandomState(t)
    tk = tk or t
    make = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa: E731
    return (make(1, heads, t, d), make(1, kv_heads, tk, d),
            make(1, kv_heads, tk, d), make(1, heads, t, d))


def _both(fn, q, k, v, do, window):
    o, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, causal=True, window=window), q, k, v)
    return (o, *vjp(do))


@pytest.mark.parametrize("t,window,bq,bk,tk", [
    (512, 64, 128, 128, None),    # smaller than a block
    (512, 128, 128, 128, None),   # a block
    (512, 200, 128, 128, None),   # larger than a block, no multiple of it
    (512, 128, 256, 128, None),   # uneven blocks, rows the larger
    (512, 128, 128, 256, None),   # uneven blocks, keys the larger
    (300, 128, 128, 128, None),   # a sequence that is no multiple of window or block
    (256, 96, 128, 128, 384),     # fewer rows than keys: the ends aligned
    (640, 1, 128, 128, None),     # the row itself and nothing else
])
def test_windowed_kernels_match_the_reference_forward_and_backward(
        monkeypatch, t, window, bq, bk, tk):
    """GQA 8 : 1: K and V stay at their one head, and dk and dv sum the
    group inside the kernel."""
    monkeypatch.setattr(attention, "WINDOW_BLOCK_Q", bq)
    monkeypatch.setattr(attention, "WINDOW_BLOCK_K", bk)
    args = _qkv(t, tk=tk)
    got = _both(flash_attention, *args, window)
    want = _both(attention_reference, *args, window)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5, err_msg=name)


def test_a_window_that_covers_the_sequence_is_the_causal_result():
    q, k, v, do = _qkv(384)
    for window in (384, 1000):
        got = _both(flash_attention, q, k, v, do, window)
        want = _both(attention_reference, q, k, v, do, None)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_a_window_off_by_one_and_a_window_dropped_are_told_apart():
    q, k, v, do = _qkv(256)
    right = _both(flash_attention, q, k, v, do, 64)
    for wrong in (63, 65, None):
        other = _both(attention_reference, q, k, v, do, wrong)
        assert float(jnp.abs(right[0] - other[0]).max()) > 1e-2, wrong
    # rows before the window's edge see the same keys either way
    np.testing.assert_allclose(
        right[0][:, :, :63], _both(attention_reference, q, k, v, do, None)[0][:, :, :63],
        atol=2e-5, rtol=2e-5)


def test_below_the_kernels_sizes_the_reference_masks_the_window():
    q, k, v, _ = _qkv(64)  # under 128 rows: the XLA road
    got = flash_attention(q, k, v, causal=True, window=16)
    scores = jnp.einsum("bhqd,bkd->bhqk", q, k[:, 0]) / 4.0
    i, j = jnp.arange(64)[:, None], jnp.arange(64)[None, :]
    scores = jnp.where((i >= j) & (i - j < 16), scores, -jnp.inf)
    want = jnp.einsum("bhqk,bkd->bhqd", jax.nn.softmax(scores, -1), v[:, 0])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_a_window_needs_causal_and_a_whole_sequence_on_each_device():
    q, k, v, _ = _qkv(256)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64)
    with pytest.raises(ValueError, match="at least the row"):
        flash_attention(q, k, v, causal=True, window=0)
    from ray_tpu.parallel import MeshSpec

    if jax.device_count() < 2:
        pytest.skip("one device: no mesh can split the sequence")
    with jax.set_mesh(MeshSpec(seq=2).build(jax.devices()[:2])):
        with pytest.raises(ValueError, match=r"window=64.*seq"):
            flash_attention(q, k, v, causal=True, window=64)
        flash_attention(q, k, v, causal=True)  # the ring still turns
