"""The flash kernels under a band, a block bitmap and a token-level selection,
and the indexer, compile ahead of time for a v5e chip, with no chip
(``tests/aot_v5e.py`` has how; ``tests/test_kernels_aot_v5e.py`` the flash
kernels).
"""
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.attention import (
    _backward_call, _bitmap_mask, _forward_call, _window_mask, flash_attention,
    index_keys,
)

from aot_v5e import _compile_for, topo, v5e  # noqa: F401 - fixtures


# Laguna's sliding layers: 64 q heads over 8 K/V heads of 128, b1 x s16384,
# a window of 512, at the blocks ops/attention.py runs and the others swept.
@pytest.mark.parametrize("bq,bk", [(512, 512), (256, 512), (256, 256)])
def test_windowed_flash_compiles_for_v5e(v5e, bq, bk):
    q, kv = ((64, 16384, 128), jnp.bfloat16), ((8, 16384, 128), jnp.bfloat16)
    mask = lambda q, k, v: _window_mask(q, k, v, 512, bq, bk)  # noqa: E731
    _compile_for(
        v5e, lambda q, k, v: _forward_call(mask(q, k, v), q, k, v, 128**-0.5),
        q, kv, kv,
    )
    _compile_for(
        v5e,
        lambda q, k, v, o, lse, do: _backward_call(
            mask(q, k, v), q, k, v, o, lse, do, 128**-0.5),
        q, kv, kv, q, ((64, 16384), jnp.float32), q,
    )


def test_windowed_kernels_lower_under_names_of_their_own(v5e, monkeypatch):
    """A trace prices a call by its kernel's name: a windowed call is none
    of the causal kernels', and without a window the causal kernels lower as
    before. (The kernels lower where the backend is the TPU: the probe is
    stood in for, as benchmarks/rehearse.py does.)"""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((1, 64, 2048, 128), jnp.bfloat16, sharding=v5e)
    kv = jax.ShapeDtypeStruct((1, 8, 2048, 128), jnp.bfloat16, sharding=v5e)

    def text(window):
        return jax.jit(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )).lower(q, kv, kv).as_text()

    windowed, causal = text(512), text(None)
    for name in ("_fwd", "_bwd_dkv", "_bwd_dq"):
        assert windowed.count(f'kernel_name = "{name}_window_kernel"') == 1
        assert f'kernel_name = "{name}_kernel"' not in windowed
        assert causal.count(f'kernel_name = "{name}_kernel"') == 1
    assert "window" not in causal
    # K and V reach the windowed kernels at their own 8 heads
    assert "8x2048x128xbf16" in windowed and "64x2048x128xbf16" in causal


def test_a_profile_names_each_flash_kernel_of_a_remat_step_by_its_own_name(
        v5e, monkeypatch):
    """The benchmark's readers name a device event's kernel by the first
    ``*_kernel`` identifier in its Mosaic module's string table
    (``benchmarks/lib/trace.py kernel_name``), and a cached trace of a jitted
    jax.numpy function (``//``, ``%``) carries the frames of the kernel that
    traced it first into the next one's module: in a step with a full and a
    sliding layer under remat, each of the six flash kernels still reads as
    itself."""
    import re

    import numpy as np

    from benchmarks.lib.trace import kernel_name
    from ray_tpu.models.laguna import LagunaForCausalLM, laguna_config
    from ray_tpu.models.llama import chunked_causal_lm_loss
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = laguna_config(
        num_layers=2, layer_types=["full_attention", "sliding_attention"],
        mlp_layer_types=["dense", "dense"], num_attention_heads_per_layer=[2, 4],
        sliding_window=512,
        rope_parameters={
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        shared_expert_intermediate_size=128, moe_intermediate_size=128,
        num_experts_held=8, num_experts=8, vocab_size=512, hidden_size=256,
        intermediate_size=512, num_heads=2, num_kv_heads=2, head_dim=128,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    model = LagunaForCausalLM(cfg)
    ids = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=v5e)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), shapes)
    mesh = jax.sharding.Mesh(np.array([v5e._device]), ("data",))
    with jax.set_mesh(mesh):  # as a cell's step is lowered
        text = jax.jit(jax.grad(
            lambda p, i: chunked_causal_lm_loss(model, p, i, i, chunk_size=1024)
        )).lower(params, ids).compile().as_text()
    named = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            mixer = re.search(r"/layers_\d/(attn|swa)/", line).group(1)
            named.setdefault(mixer, []).append(kernel_name(line))
    # One forward a layer: the replay holds none (models/llama.py
    # REPLAY_KEEPS keeps what it wrote). q and k turn through
    # ops/rotary.py's kernel forward, replayed and backward.
    turns = ["_rotary_kernel"] * 6
    assert sorted(named["attn"]) == [
        "_bwd_dkv_kernel", "_bwd_dq_kernel", "_fwd_kernel", *turns]
    assert sorted(named["swa"]) == [
        "_bwd_dkv_window_kernel", "_bwd_dq_window_kernel", "_fwd_window_kernel", *turns]


@pytest.mark.parametrize("t,block_size", [(16384, 64), (2048, 16)])
def test_sparse_kernels_compile_for_v5e_with_k_and_v_at_two_heads(v5e, t, block_size):
    from ray_tpu.ops.attention import _sparse_blocks

    h, g, d = 32, 2, 128
    _, block_k, t_p = _sparse_blocks(t, block_size)
    assert t_p == t and t // block_k <= 128
    q, kv = ((h, t, d), jnp.bfloat16), ((g, t, d), jnp.bfloat16)
    words = ((g, t, 128), jnp.int32)
    mask = lambda q, k: _bitmap_mask(q, k, block_size)  # noqa: E731
    text = _compile_for(
        v5e,
        lambda q, k, v, words: _forward_call(mask(q, k), q, k, v, d ** -0.5, words),
        q, kv, kv, words)
    assert f"bf16[{h},{t},{t}]" not in text and f"f32[{h},{t},{t}]" not in text
    _compile_for(
        v5e,
        lambda q, k, v, words, o, lse, do: _backward_call(
            mask(q, k), q, k, v, o, lse, do, d ** -0.5, words),
        q, kv, kv, words, q, ((h, t), jnp.float32), q)


# ------------------------------------------------ dots3's selection and band


def _steered(monkeypatch):
    """``flash_attention`` and ``index_keys`` take their kernels where the
    backend is the TPU; here it is the CPU, so the probe is stood in for."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


def test_the_indexer_kernel_compiles_for_v5e_at_the_cells_shapes(v5e, monkeypatch):
    """64 index heads of 128 over 8,192 tokens, top-2048: 256 rows a grid
    step against every key up to them, their scores resident in VMEM (8 MiB)
    while the threshold is bisected; the words are [8192, 256] int32."""
    _steered(monkeypatch)
    text = _compile_for(
        v5e, lambda q, k, w: index_keys(q, k, w, topk=2048),
        ((1, 64, 8192, 128), jnp.bfloat16), ((1, 8192, 128), jnp.bfloat16),
        ((1, 8192, 64), jnp.float32))
    assert "f32[1,8192,8192]" not in text  # no score leaves the kernel


@pytest.mark.parametrize("t", [8192, 2304])
def test_the_selection_kernels_compile_for_v5e_at_32_heads_of_192_and_128(
        v5e, monkeypatch, t):
    """A full dots3 layer's forward and both backward kernels under the words
    of a bit a (row, key): 1,024 x 1,024 tiles, a tile's bits 8 of a lane
    group's 32; 2,304 tokens pad to three tiles of one group."""
    _steered(monkeypatch)
    lanes = -(-(-(-t // 1024) * 1024) // 4096) * 128
    t_p = -(-t // 1024) * 1024

    def step(q, k, v, words):
        return jax.value_and_grad(lambda *qkv: flash_attention(
            *qkv, keys=words, sm_scale=192 ** -0.5).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    qk, v = ((1, 32, t, 192), jnp.bfloat16), ((1, 32, t, 128), jnp.bfloat16)
    text = _compile_for(v5e, step, qk, qk, v, ((1, t_p, lanes), jnp.int32))
    assert text.count("tpu_custom_call") >= 3  # the forward, dK/dV, dQ
    assert f"[32,{t_p},{t_p}]" not in text  # and no [T, T] array beside them


def test_the_windowed_kernels_compile_for_v5e_at_16_heads_of_256_and_128(v5e, monkeypatch):
    """A sliding dots3 layer: q and k heads of 192 | 64, v heads of 128, a
    band of 513 keys at 512 x 512 blocks (Laguna's run at 128 and 128)."""
    _steered(monkeypatch)

    def step(q, k, v):
        return jax.value_and_grad(lambda *qkv: flash_attention(
            *qkv, window=513, sm_scale=256 ** -0.5).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    qk, v = ((1, 16, 8192, 256), jnp.bfloat16), ((1, 16, 8192, 128), jnp.bfloat16)
    text = _compile_for(v5e, step, qk, qk, v)
    assert text.count("tpu_custom_call") >= 3
