"""Every Pallas kernel compiles for a v5e chip ahead of time, with no chip:
libtpu describes the topology and runs the real Mosaic and XLA:TPU
compilers. Catches a kernel the chip's compiler rejects before a chip run
is spent on it. Shapes are the ones chip_smoke.py and the benchmark run."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import kda, rotary
from ray_tpu.ops.attention import (
    _backward_call, _bitmap_mask, _causal_mask, _forward_call, _window_mask,
    flash_attention, index_keys,
)
from ray_tpu.ops.gmm import _tgmm_pallas, gmm, pairs_summed


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - libtpu absent or too old
        pytest.skip(f"libtpu gives no v5e topology here: {e}")


@pytest.fixture(scope="module")
def v5e(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_for(sharding, fn, *args):
    """args are (shape, dtype) pairs; returns the optimized HLO text."""
    specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in args]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "custom-call" in text and "tpu_custom_call" in text
    return text


def _causal_fwd(block):
    """The one forward call under the causal mask at ``block`` x ``block``,
    the default scale."""
    return lambda q, k, v: _forward_call(
        _causal_mask(q, k, v, True, block, block), q, k, v, q.shape[2] ** -0.5)


def _causal_bwd(block):
    return lambda q, k, v, o, lse, do: _backward_call(
        _causal_mask(q, k, v, True, block, block), q, k, v, o, lse, do,
        q.shape[2] ** -0.5)


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


# Kimi-Linear's MLA layer (the benchmark's longctx-16k cell): 32 heads over
# 16,384 tokens, q/k heads of 128 + 64 and v heads of 128; sarvam-105b's five
# (pretrain-4k): 64 heads over 4,096 tokens at the same head dims.
@pytest.mark.parametrize("bh,t", [(32, 16384), (64, 4096)])
def test_flash_at_192_and_128_compiles_for_v5e(v5e, bh, t):
    d, d_v, block = 192, 128, 1024
    qk, v = ((bh, t, d), jnp.bfloat16), ((bh, t, d_v), jnp.bfloat16)
    _compile_for(v5e, _causal_fwd(block), qk, qk, v)
    _compile_for(v5e, _causal_bwd(block), qk, qk, v, v, ((bh, t), jnp.float32), v)


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


# q's and k's rotation as one pass (``ops/rotary.py``), heads first in and
# out, and the pass back, which is the same kernel against the tables with
# the sines' sign turned: the Laguna cell's sliding layer (b1 x s16384, q at
# 64 heads and k at 8, the whole head), its full layer (q at 48, the leading
# half under YaRN's amplitude: two lane rotations against three tables) and
# the MiniCPM-SALA cell's Lightning layer (q and k at 32), at the blocks
# ``rotate`` gives them.
@pytest.mark.parametrize("heads,half,leading,amplitude", [
    (64, 64, True, 1.0), (8, 64, True, 1.0), (48, 32, True, 1.4158883),
    (32, 64, False, 1.0),
], ids=["laguna_swa_q", "laguna_swa_k", "laguna_attn_q", "lightning_q_and_k"])
def test_rotary_kernel_and_its_pass_back_compile_for_v5e(v5e, heads, half, leading, amplitude):
    from benchmarks.lib import trace

    shape = (1, heads, 16384, 128)
    turn = rotary._Turn(leading, amplitude, *rotary._blocks(shape), False)
    assert turn[2:4] == (rotary.ROWS, min(heads, rotary.HEADS))
    for back in (False, True):
        text = _compile_for(
            v5e, lambda x, p, f: rotary._turned(x, p, f, turn, back),  # noqa: B023
            (shape, jnp.bfloat16), ((1, 16384), jnp.int32), ((half,), jnp.float32))
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert [trace.kernel_name(line) for line in calls] == ["_rotary_kernel"]
        # the whole of x in and out as it lies: no copy, no transposition
        assert " copy(" not in text and " transpose(" not in text


# Latent attention's q and k from the projections to the flash kernels in one
# pass each and the two passes back (``ops/rotary.py`` ``latent_qkv``) at the
# cells' real sizes (b1 x s4096): sarvam's 64 heads under the per-head norm and
# the rotation, Xing4's 32 under the rotation alone, and the norm alone (what
# ``benchmarks/tools/wrong_sarvam.py``'s program without the rotation runs); q [1, H, 4096, 128 | 64],
# kv [1, H, 4096, 128 | 128], the shared key part [1, 4096, 64]. Each kernel
# reads its own name as a profile's reader names it.
@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("heads,eps,turns", [(64, 1e-6, True), (32, None, True), (64, 1e-6, False)],
                         ids=["sarvam", "xing4", "the_norm_alone"])
def test_latent_kernels_compile_for_v5e(v5e, heads, eps, turns, direction):
    from benchmarks.lib import trace

    t = 4096
    q, kv, v = ((1, heads, t, lanes) for lanes in (192, 256, 128))
    fuse = rotary._Fuse(eps, turns, *rotary._blocks(q), False)
    assert fuse[2:4] == (rotary.ROWS, rotary.HEADS)
    bf16 = jnp.bfloat16
    # positions and, where the layer turns, the table (None for a layer that does not)
    table = [((1, t), jnp.int32), ((32,), jnp.float32)][:1 + turns]
    weight = [((192,), jnp.float32)] if eps else []
    shared = ((1, t, 64), bf16)
    if direction == "forward":
        entries = {"_latent_q_kernel": (rotary._latent_q_forward, [(q, bf16)], []),
                   "_latent_k_kernel": (rotary._latent_k_forward, [(kv, bf16), shared], [])}
    else:  # cotangents, then what the norm's transpose reads again
        entries = {
            "_latent_q_back_kernel": (rotary._latent_q_backward, [(q, bf16)], [(q, bf16)]),
            "_latent_k_back_kernel": (rotary._latent_k_backward, [(q, bf16), (v, bf16)],
                                      [(kv, bf16), shared])}
    for kernel, (entry, arrays, read_again) in entries.items():
        # an entry takes None for what a layer without the norm has not
        norms = [*read_again, *weight] if eps else [None] * (len(read_again) + 1)
        given = [a for a in norms if a is not None]

        def call(*a, entry=entry, n=len(arrays), norms=norms, given=given):
            last = a[n + len(given):] if turns else (a[-1], None)
            return entry(*a[:n], *(a[n:n + len(given)] or norms), *last, fuse)

        text = _compile_for(v5e, call, *arrays, *given, *table)
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert [trace.kernel_name(line) for line in calls] == [kernel]


@pytest.fixture(scope="module")
def sarvams_step(v5e):
    return _lowered_step(v5e, "sarvam-105b-l5.pretrain-4k")


def test_sarvams_step_takes_q_and_k_to_the_flash_kernels_by_the_latent_kernels(sarvams_step):
    """Five layers: a forward body and the replay's copy of it behind each
    forward entry, one behind each backward entry; q's and k's pass a layer
    forward, replayed and backward. Nothing of the XLA road is left: no
    [., 64, 4096, 192] array is concatenated (``_rope``'s two and k's
    assembly were 30 in the parent's text), the shared key part is broadcast
    to no [1, 4096, 64, 64], and no ``_rope`` product stands in float32."""
    import re

    from benchmarks.lib import checks

    _, text = sarvams_step
    bodies = checks.count_pallas_kernels(text, (
        "_latent_q_kernel", "_latent_k_kernel", "_latent_q_back_kernel",
        "_latent_k_back_kernel"))
    assert bodies == {"_latent_q_kernel": 2, "_latent_k_kernel": 2,
                      "_latent_q_back_kernel": 1, "_latent_k_back_kernel": 1}
    calls = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text)) for entry in (
        "_latent_q_forward", "_latent_k_forward", "_latent_q_backward", "_latent_k_backward")}
    assert calls == {"_latent_q_forward": 10, "_latent_k_forward": 10,
                     "_latent_q_backward": 5, "_latent_k_backward": 5}
    assert not re.findall(r"stablehlo\.concatenate.*(1x64x4096x192|4096x64x192)x", text)
    assert "tensor<1x4096x64x64xbf16>" not in text
    assert "tensor<1x64x4096x32xf32>" not in text


# The models the benchmark already had lower to the Pallas kernels they had
# before a layer could choose its mixer and FFN: read by this same code at
# commit 57913f4, each configuration file at its rehearsal size, b1 x s256.
KERNELS_BEFORE = {
    "mistral-7b-l4": {"_fwd_kernel": 4, "_bwd_dkv_kernel": 2, "_bwd_dq_kernel": 2},
    "mixtral-8x7b-l2": {"_fwd_kernel": 4, "_bwd_dkv_kernel": 2, "_bwd_dq_kernel": 2},
    "olmoe-1b-7b-1chip": {"_fwd_kernel": 4, "_bwd_dkv_kernel": 2, "_bwd_dq_kernel": 2,
                          "_gmm_kernel": 18, "_tgmm_kernel": 6},
}


@pytest.mark.parametrize("name", sorted(KERNELS_BEFORE))
def test_the_models_that_were_there_lower_to_the_kernels_they_had(v5e, monkeypatch, name):
    import importlib

    import numpy as np
    import optax

    from benchmarks.lib import cells, checks
    from ray_tpu import train
    from ray_tpu.models.llama import causal_lm_loss
    from ray_tpu.models.mixtral import moe_lm_loss

    # The program takes its kernels where the backend is the TPU; here it is
    # the CPU, and the test stands in for that one probe.
    attention = importlib.import_module("ray_tpu.ops.attention")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    config = cells.load_json(f"{cells.BENCH_DIR}/configs/{name}.json")
    config = {**config, **config["rehearsal"]}
    cfg = cells.program_config(config)
    model = cells.resolve(config["program"]["model"])(cfg)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), tree)

    if hasattr(cfg, "num_experts"):
        loss = lambda p, ids, t: moe_lm_loss(model, p, ids, t)  # noqa: E731
    else:
        loss = lambda p, ids, t: causal_lm_loss(model.apply(p, ids), t)  # noqa: E731
    tx = optax.adamw(3e-4)
    batch = jax.ShapeDtypeStruct((1, 256), np.int32, sharding=v5e)
    text = train.make_train_step(loss, tx).lower(
        placed(shapes), placed(jax.eval_shape(tx.init, shapes)), batch, batch
    ).as_text()
    names = ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel", "_gmm_kernel",
             "_tgmm_kernel", "_kda_fwd_kernel", "_kda_bwd_kernel", "_unwritten_kernel",
             # none at a rehearsal's heads of 32 lanes: ROTARY_STEPS has the cells'
             "_rotary_kernel")
    counts = {k: n for k, n in checks.count_pallas_kernels(text, names).items() if n}
    assert counts == KERNELS_BEFORE[name]


def test_mixtrals_step_takes_its_weight_gradients_from_the_grouped_matmul(topo):
    """The Mixtral cell's step at its real size on seq=2 x expert=2 (the
    rehearsal size above runs the plain einsum: `_ffn_trips` is 0 there),
    lowered and not compiled: each layer's backward ends in three
    `_tgmm_kernel` calls over the five stacks its loop filled, and no
    float32 array of a chip's four expert matrices is left for a loop to
    carry."""
    import importlib

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.lib import cells, checks
    from benchmarks.loops.train_lm import make_loss_fn, make_optimizer
    from ray_tpu import train
    from ray_tpu.parallel import MeshSpec, logical_sharding
    from ray_tpu.parallel.mesh import spec_for_param

    cell = cells.load_cell("mixtral-8x7b-l2.ep2seq2-4k")
    config, traffic = cell["config"], cell["traffic"]
    mesh = MeshSpec(**traffic["mesh"]).build(topo.devices[: cell["chips"]])
    cfg = cells.program_config(config)
    model_cls = cells.resolve(config["program"]["model"])
    shapes = jax.eval_shape(
        model_cls(cfg).init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))

    def placed(path, leaf):
        keys = tuple(getattr(p, "key", getattr(p, "name", getattr(p, "idx", "")))
                     for p in path)
        keys = keys[keys.index("params"):] if "params" in keys else keys
        spec = spec_for_param(keys, leaf.shape) if leaf.ndim else PartitionSpec()
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec))

    tx = make_optimizer(traffic)
    batch = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"]), np.int32,
        sharding=logical_sharding(mesh, ("batch", "seq")))
    with pytest.MonkeyPatch.context() as patch, jax.set_mesh(mesh):
        patch.setattr(
            importlib.import_module("ray_tpu.ops.attention"), "_on_tpu", lambda: True)
        model = model_cls(cfg, mesh=mesh)
        text = train.make_train_step(make_loss_fn(traffic, model), tx).lower(
            jax.tree_util.tree_map_with_path(placed, shapes),
            jax.tree_util.tree_map_with_path(placed, jax.eval_shape(tx.init, shapes)),
            batch, batch,
        ).as_text()
    layers, width = config["num_hidden_layers"], config["intermediate_size"]
    counts = checks.count_pallas_kernels(text, ("_tgmm_kernel", "_unwritten_kernel"))
    assert counts == {"_tgmm_kernel": 3 * layers, "_unwritten_kernel": 5 * layers}
    # Its mesh splits the sequence: q and k at 128 lanes a head turn by _rope.
    assert "_rotary_kernel" not in text and "_turned" not in text
    hidden = config["hidden_size"]
    assert f"tensor<{19 * 512}x{width}xbf16>" in text  # a stack of 19 trips
    for shape in (f"4x{hidden}x{width}", f"4x{width}x{hidden}"):
        assert f"tensor<{shape}xbf16>" in text and f"tensor<{shape}xf32>" not in text


def _lowered_step(v5e, name):
    """(the cell ``name``, its step's StableHLO as lowered for a v5e chip)."""
    cell, lowered = _lower_step(v5e, name)
    return cell, lowered.as_text()


def _lower_step(v5e, name):
    """(the cell ``name``, its step lowered for a v5e chip)."""
    import importlib

    import numpy as np

    from benchmarks.lib import cells
    from benchmarks.loops.train_lm import make_loss_fn, make_optimizer
    from ray_tpu import train

    attention = importlib.import_module("ray_tpu.ops.attention")
    cell = cells.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    model = cells.resolve(config["program"]["model"])(cells.program_config(config))
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), tree)

    tx = make_optimizer(traffic)
    batch = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"]), np.int32, sharding=v5e)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "_on_tpu", lambda: True)
        lowered = train.make_train_step(make_loss_fn(traffic, model), tx).lower(
            placed(shapes), placed(jax.eval_shape(tx.init, shapes)), batch, batch
        )
    return cell, lowered


def test_long16ks_step_makes_the_heads_gradients_in_the_forward_loop_and_fits(
        topo, v5e, monkeypatch):
    """mistral-7b-l4.long16k's step at the benchmark's real size (b1 x s16384
    in eight chunks of 2,048 over a vocabulary of 32,768). The lowered text
    multiplies at the head's shape three times, the logits and the two
    products of their cotangent, all in the loss's one loop (``models/llama.py``
    ``_chunked_nll``; the replayed loss had four and a second loop under
    ``transpose(jvp(loss))``). Compiled for v5e as the benchmark lowers it
    (``benchmarks/rehearse.py``), the [32768, 4096] sum of the head's gradients
    is alive with every layer's residuals and the step still takes no more of
    the chip than the replayed loss's did (6.38 GiB of arguments + 7.50 of
    temporaries, PERF.md 6, PR 66)."""
    import importlib
    import os
    import re
    import sys

    # benchmarks/rehearse.py is a script: as it is imported it puts the repo
    # first on sys.path and names a log directory, and a cluster that a later
    # test of this process starts would hand its workers that sys.path[0]
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    rehearse = importlib.import_module("benchmarks.rehearse")

    _, lowered = _lower_step(v5e, "mistral-7b-l4.long16k")
    text = lowered.as_text(debug_info=True)
    matmuls = [line for line in text.splitlines()
               if "stablehlo.dot_general" in line and "32768" in line]
    assert len(matmuls) == 3, matmuls
    assert sorted(re.search(r"-> tensor<(\w+)>", line).group(1) for line in matmuls) == [
        "1x2048x32768xf32", "1x2048x4096xf32", "32768x4096xf32"]
    names = re.findall(r'"(jit\(train_step\)/[^"]*)"', text)
    assert any(n.startswith("jit(train_step)/jvp(loss)/while/") for n in names)
    assert not [n for n in names if n.startswith("jit(train_step)/transpose(jvp(loss))/while")]
    assert not [n for n in names if "(loss)" in n and "rematted_computation" in n]
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.attention"), "_on_tpu", lambda: True)
    found = rehearse.compile_cell("mistral-7b-l4.long16k", topo)
    assert found["holds_stated_kernels"]
    assert found["arguments_gib"] + found["temporaries_gib"] < 13.9, found


# (bodies, call sites) of ``_rotary_kernel`` in a cell's lowered step: a body
# a jitted entry (``ops/rotary.py`` ``_turned``: a shape, a part of a head, a
# direction, and a replay's copy of a forward one), a call for q and for k of
# every layer that turns heads of 128 lanes, forward, replayed where the cell
# replays, and backward. The Laguna cell: sliding q and k, full q and k. The
# MiniCPM-SALA cell: q and k are one shape, and its sparse layer turns
# nothing. The Mistral cells' lowered text holds the replay's calls too; no
# barrier stands there, XLA merges them with the forward's, and a trace
# counts 16 a step. sarvam's rotated part is 64 lanes of a 192-wide head:
# ``models/mla.py`` turns it inside ``latent_qkv``'s kernels, not this one.
ROTARY_STEPS = {
    "laguna-xs2-33b-a3b-l8.longctx-16k": (12, 48),
    "minicpm-sala-9b-l4.long16k": (3, 18),
    "mistral-7b-l4.short2k": (6, 24),
    "sarvam-105b-l5.pretrain-4k": (0, 0),
}


def _turns(text):
    from benchmarks.lib import checks

    bodies = checks.count_pallas_kernels(text, ("_rotary_kernel",))["_rotary_kernel"]
    return bodies, text.count("call @_turned")


@pytest.mark.parametrize("name", [
    "laguna-xs2-33b-a3b-l8.longctx-16k", "mistral-7b-l4.short2k",
    "sarvam-105b-l5.pretrain-4k"])
def test_a_step_turns_q_and_k_by_the_kernel_where_a_head_is_128_lanes(v5e, name):
    from benchmarks.lib import cells, checks

    cell, text = _lowered_step(v5e, name)
    assert _turns(text) == ROTARY_STEPS[name]
    # and lost none of the kernels its configuration states
    stated = cells.stated_kernels(cell)
    assert checks.holds_stated_kernels(checks.count_pallas_kernels(text, stated), stated)


# Kimi-Linear's step at the benchmark's real size (b1 x s16384, five layers at
# the published widths), lowered once for the tests below; and Solar-Open2's
# (b1 x s4096, four layers).
@pytest.fixture(scope="module")
def kimi_linears_step(v5e):
    return _lowered_step(v5e, "kimi-linear-48b-a3b-l5.longctx-16k")


@pytest.fixture(scope="module")
def solar_open2s_step(v5e):
    return _lowered_step(v5e, "solar-open2-250b-l4.pretrain-4k")


@pytest.mark.parametrize("step,layers", [("kimi_linears_step", 4), ("solar_open2s_step", 3)])
def test_a_steps_replay_runs_no_kda_forward_and_its_backward_reads_the_inverses(
        request, step, layers):
    """A KDA layer is one ``_kda_fwd_kernel`` and one ``_kda_bwd_kernel`` in
    the whole step, forward pass, replay and backward pass together: the
    remat policy keeps o, the states and every chunk's inverse T
    (``kda_o``, ``kda_states``, ``kda_t``), so no replay runs the forward
    kernel to remake one of them. T leaves the forward kernel as [B, N, H /
    2, 64, 128] in the matmuls' dtype, a pair's two blocks side by side, an
    eighth of the states' bytes."""
    from benchmarks.lib import checks

    cell, text = request.getfixturevalue(step)
    counts = checks.count_pallas_kernels(text, ("_kda_fwd_kernel", "_kda_bwd_kernel"))
    assert counts == {"_kda_fwd_kernel": layers, "_kda_bwd_kernel": layers}
    traffic, kda_cfg = cell["traffic"], cell["config"]["linear_attn_config"]
    b, n, h, d = (traffic["batch"], traffic["seq"] // kda.CHUNK, kda_cfg["num_heads"],
                  kda_cfg["head_dim"])
    states, inverses = f"tensor<{b}x{n}x{d}x{h * d}xf32>", f"tensor<{b}x{n}x{h // 2}x64x128xbf16>"
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    # the forward's last two results; the backward's operands before do
    wrote = sum(f"{states}, {inverses})" in line for line in calls)
    read = sum(f"{states}, {inverses}," in line for line in calls)
    assert (wrote, read) == (layers, layers)


@pytest.mark.parametrize("step,layers", [("kimi_linears_step", 4), ("solar_open2s_step", 3)])
def test_a_kda_layer_convolves_its_three_projections_by_the_kernels(request, step, layers):
    """A layer's q, k and v each go through ``_conv_forward`` in the forward
    pass and again in the replay (the remat policy keeps none of the
    convolution's outputs: 0.67 GB a layer at 16k tokens) and through
    ``_conv_backward`` once. The bodies stand behind the jitted entries: a
    backward one a cotangent's dtype (float32 of q and k, bfloat16 of v), a
    forward one a dtype and again for the replay, whose partial evaluation
    copies the entry. No pad of a [B, T, H * d] projection is left to XLA."""
    import re

    from benchmarks.lib import checks

    cell, text = request.getfixturevalue(step)
    calls = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text))
             for entry in ("_conv_forward", "_conv_backward")}
    assert calls == {"_conv_forward": 2 * 3 * layers, "_conv_backward": 3 * layers}
    bodies = checks.count_pallas_kernels(text, ("_conv_fwd_kernel", "_conv_bwd_kernel"))
    assert bodies == {"_conv_fwd_kernel": 4, "_conv_bwd_kernel": 2}
    traffic, kda_cfg = cell["traffic"], cell["config"]["linear_attn_config"]
    channels = kda_cfg["num_heads"] * kda_cfg["head_dim"]
    padded = f"tensor<{traffic['batch']}x{traffic['seq'] + 3}x{channels}xf32>"
    assert padded not in text


def test_kimi_linears_step_holds_its_kernels_and_no_gather_over_the_bound(kimi_linears_step):
    """Every kernel its configuration states, and the held share's rows moved
    a window of tiles at a time, never over the static bound of every
    (token, expert) pair."""
    import re

    from benchmarks.lib import cells, checks

    cell, text = kimi_linears_step
    config, traffic = cell["config"], cell["traffic"]
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert checks.holds_stated_kernels(counts, stated), (counts, stated)
    pairs = traffic["batch"] * traffic["seq"] * config["num_experts_per_token"]
    gathered = [
        int(rows) for rows in re.findall(
            r'"stablehlo\.gather".*\) -> tensor<(?:1x)?(\d+)x', text)
    ]
    width = config["hidden_size"]
    assert f"-> tensor<2048x{width}xbf16>" in text  # a window of sixteen tiles
    assert gathered and max(gathered) < pairs, sorted(set(gathered))


def test_kimi_linears_step_leaves_no_norm_over_a_heads_channels_to_xla(kimi_linears_step):
    """q's and k's L2 norm and o's gated RMSNorm happen on the scan kernels'
    own blocks in every KDA layer or in none: the lowered step reduces no
    [1, 16384, 32, 128] float32 array over a head's channels token by token
    (before the kernels took them: 36, forward, replay and backward of four
    layers; the sums over tokens that are left are the gradients of the
    decay's per-head parameters), and the scan's call sites are eight, a
    forward and a backward a layer: the replay holds none, the remat policy
    keeps o and the states."""
    import re

    from benchmarks.lib import checks

    cell, text = kimi_linears_step
    traffic, kda_cfg = cell["traffic"], cell["config"]["linear_attn_config"]
    rows = "x".join(str(n) for n in (
        traffic["batch"], traffic["seq"], kda_cfg["num_heads"], kda_cfg["head_dim"]))
    reduced = re.findall(
        rf"stablehlo\.reduce.* across dimensions = \[([\d, ]+)\] : \(tensor<{rows}xf32>",
        text)
    assert reduced and all("1" in dims.split(", ") for dims in reduced), reduced
    counts = checks.count_pallas_kernels(text, ("_kda_fwd_kernel", "_kda_bwd_kernel"))
    assert counts == {"_kda_fwd_kernel": 4, "_kda_bwd_kernel": 4}


@pytest.fixture(scope="module")
def olmo_hybrids_step(v5e):
    return _lowered_step(v5e, "olmo-hybrid-7b-l4.pretrain-8k")


def test_olmo_hybrids_step_holds_its_kernels_and_its_replay_runs_no_scan(olmo_hybrids_step):
    """Olmo-Hybrid's step at the benchmark's real size (b1 x s8192, four layers
    at the published widths): every kernel its configuration states; a linear
    layer is one ``_gdn_fwd_kernel`` and one ``_gdn_bwd_kernel`` in the whole
    step (the remat policy keeps ``gdn_o``, ``gdn_states``, ``gdn_t``), the
    states [B, N, H, 192, 96] float32 and the inverses a pair's two blocks side
    by side; and no KDA kernel."""
    from benchmarks.lib import cells, checks

    cell, text = olmo_hybrids_step
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert checks.holds_stated_kernels(counts, stated), (counts, stated)
    assert (counts["_gdn_fwd_kernel"], counts["_gdn_bwd_kernel"]) == (3, 3)
    assert (counts["_fwd_kernel"], counts["_bwd_dkv_kernel"], counts["_bwd_dq_kernel"]) == (1, 1, 1)
    assert checks.count_pallas_kernels(text, ("_kda_fwd_kernel", "_kda_bwd_kernel")) == {
        "_kda_fwd_kernel": 0, "_kda_bwd_kernel": 0}
    states, inverses = "tensor<1x128x30x192x96xf32>", "tensor<1x128x15x64x128xbf16>"
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    wrote = sum(f"{states}, {inverses})" in line for line in calls)
    read = sum(f"{states}, {inverses}," in line for line in calls)
    assert (wrote, read) == (3, 3)


def test_olmo_hybrids_step_convolves_by_the_kernels_and_broadcasts_no_decay(olmo_hybrids_step):
    """A linear layer's q with k (5,760 channels, float32 out) and its v (5,760,
    bfloat16 out) each go through ``_conv_forward`` in the forward pass and in
    the replay and through ``_conv_backward`` once: no ``short_conv`` fallback
    at these widths (no padded [B, T + 3, 5760] or [B, T + 3, 2880] copy). The
    decay reaches the scan as [B, H, T, 1]: no [B, T, H, 96] or [B, H, T, 96]
    array is made from it by a broadcast. And no norm over a head's channels
    is left to XLA: no float32 [1, 8192, 30, d] or [1, 30, 8192, d] array is
    reduced over its last axis."""
    import re

    from benchmarks.lib import checks

    _, text = olmo_hybrids_step
    calls = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text))
             for entry in ("_conv_forward", "_conv_backward")}
    assert calls == {"_conv_forward": 2 * 2 * 3, "_conv_backward": 2 * 3}
    bodies = checks.count_pallas_kernels(text, ("_conv_fwd_kernel", "_conv_bwd_kernel"))
    assert bodies == {"_conv_fwd_kernel": 4, "_conv_bwd_kernel": 2}
    for channels in (2880, 5760):
        assert f"tensor<1x8195x{channels}xf32>" not in text
    broadcasts = re.findall(
        r"stablehlo\.broadcast_in_dim.*\(tensor<1x(?:8192x30|30x8192)(?:x1)?xf32>\) -> "
        r"tensor<1x(?:8192x30|30x8192)x96xf32>", text)
    assert not broadcasts, broadcasts[:2]
    reduced = re.findall(
        r"stablehlo\.reduce.* across dimensions = \[3\] : "
        r"\(tensor<1x(?:8192x30|30x8192)x(?:96|192)xf32>", text)
    assert not reduced, reduced[:2]


def test_olmo_hybrids_step_moves_no_operand_of_the_scan_but_the_decay_and_beta(
        olmo_hybrids_step):
    """The convolution writes q with k as [1, 60, 8192, 96], which the scan's
    kernels read as it lies (one operand [1, 2, 30, 8192, 96], a reshape of
    major extents), and their cotangents come back the same way: the lowered
    step transposes no float32 array of 96-wide heads, slices no [1, 8192,
    5760] projection to q's or k's 2,880 lanes and pads or joins none back,
    and no [1, 8192, 2880] array exists. v, the gate, o and their cotangents
    go through the kernels [1, 8192, 5760], as v's convolution and ``g_proj``
    write and ``o_proj`` reads them: no array of 192-wide heads is transposed
    either (there were 15 one way and 12 the other: v's, the gate's and o's,
    forward, replayed and backward)."""
    import re

    _, text = olmo_hybrids_step
    turned = re.findall(
        r"stablehlo\.transpose.*: \(tensor<([\dx]+)x(f32|bf16)>\) -> tensor<([\dx]+)x", text)
    assert turned
    assert not [t for t in turned if t[0].endswith(("x96", "x192"))], turned
    assert "x2880xf32>" not in text
    # the scan's calls take what the convolution's return, a reshape apart
    scans = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "tensor<1x2x30x8192x96xf32>" in line]
    assert len(scans) == 2 * 3
    assert all("tensor<1x8192x5760xbf16>" in line for line in scans)
    assert text.count("-> tensor<1x60x8192x96xf32>") >= 2 * 3  # ``_conv_forward``'s


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


@pytest.fixture(scope="module")
def minicpm_salas_step(v5e):
    return _lowered_step(v5e, "minicpm-sala-9b-l4.long16k")


def test_minicpm_salas_step_holds_its_kernels_under_their_names(minicpm_salas_step):
    """MiniCPM-SALA's step at the benchmark's real size (b1 x s16384, four
    layers at the published widths): every kernel its configuration states and
    no more of any (the remat policy keeps ``sparse_o``, ``sparse_lse``,
    ``lightning_o``, ``lightning_states``: no replay runs a forward kernel);
    the Lightning states [1, 32, 64, 128, 128] float32, written thrice and
    read thrice; no causal flash kernel, no delta-rule kernel."""
    from benchmarks.lib import cells, checks

    cell, text = minicpm_salas_step
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert counts == {k: s["least"] for k, s in stated.items()} == {
        "_sparse_fwd_kernel": 1, "_bwd_dkv_sparse_kernel": 1,
        "_bwd_dq_sparse_kernel": 1, "_lightning_fwd_kernel": 3,
        "_lightning_bwd_kernel": 3}
    assert _turns(text) == ROTARY_STEPS[cell["name"]]
    others = ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel",
              "_gdn_fwd_kernel", "_kda_fwd_kernel")
    assert not any(checks.count_pallas_kernels(text, others).values())
    states = f"tensor<1x32x{16384 // kda.LIGHTNING_CHUNK}x128x128xf32>"
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum(f"{states})" in line for line in calls) == 3
    assert sum(f"{states}," in line for line in calls) == 3


def test_minicpm_salas_step_repeats_no_k_or_v_and_makes_no_t_by_t_array(minicpm_salas_step):
    """K and V reach the sparse kernels at their own 2 heads: no [1, 32, 16384,
    128] array is made from a [1, 2, ...] one by a broadcast (``jnp.repeat``'s
    lowering), and the kernels' K and V operands are [2, 16384, 128]. No array
    of scores or of a mask is [.., 16384, 16384] (the SwiGLU's [1, 16384,
    16384] bfloat16 products are the only ones of that extent: the
    intermediate size is the sequence's length here): the selection is [1, 2,
    16384, 256] bits and [2, 16384, 128] words. The replay is handed the set and
    chooses nothing again: one while loop of the selection in the step."""
    import re

    _, text = minicpm_salas_step
    assert not re.findall(r"16384x16384x(?:f32|i1|i8|i32)|(?:32|16|2)x16384x16384x", text)
    repeats = re.findall(
        r"stablehlo\.broadcast_in_dim.*\(tensor<1x2x(?:1x)?16384x128xbf16>\) -> "
        r"tensor<1x2x16x16384x128xbf16>", text)
    assert not repeats, repeats[:2]
    sparse = [line for line in text.splitlines()
              if "tpu_custom_call" in line and "_sparse_fwd_kernel" in line]
    assert len(sparse) == 1 and sparse[0].count("tensor<2x16384x128xbf16>") >= 2
    assert "tensor<2x16384x128xi32>" in sparse[0]  # the words, a lane a key tile
    assert "tensor<1x2x16384x256xi1>" in text  # the chosen blocks
    assert text.count("tensor<1x2x16384x256xi1>") >= 2


# One of Xing4's hyper-connections at the benchmark's real size: four streams
# of b1 x s4096 tokens at the published 3584 channels.
HC_STREAMS = ((4, 1, 4096, 3584), jnp.bfloat16)
HC_ONE = ((1, 4096, 3584), jnp.bfloat16)
HC_MAPS = {k: ((k, 1, 4096), jnp.float32) for k in (4, 24)}


def hc_entries():
    from ray_tpu.models import hyper_connections as hcs

    phi = ((4 * 3584, 24), jnp.bfloat16)
    return {
        "_hc_pre_fwd_kernel": (
            lambda x, phi, alpha, b: hcs._pre_fwd(x, phi, alpha, b, 1e-6),
            HC_STREAMS, phi, ((), jnp.float32), ((4,), jnp.float32)),
        "_hc_post_fwd_kernel": (
            hcs._post_fwd, HC_STREAMS, HC_ONE, HC_MAPS[4], ((4, 4, 1, 4096), jnp.float32)),
        "_hc_post_bwd_kernel": (
            hcs._post_bwd, HC_STREAMS, HC_STREAMS, HC_ONE, HC_MAPS[4],
            ((4, 4, 1, 4096), jnp.float32)),
        "_hc_pre_sums_kernel": (hcs._pre_sums, HC_ONE, HC_STREAMS),
        "_hc_pre_bwd_kernel": (
            hcs._pre_bwd, HC_STREAMS, HC_STREAMS, HC_ONE, HC_MAPS[4],
            ((1, 4096), jnp.float32), ((48, 1, 4096), jnp.bfloat16),
            ((4, 3584, 24), jnp.bfloat16)),
    }


@pytest.mark.parametrize("kernel", [
    "_hc_pre_fwd_kernel", "_hc_post_fwd_kernel", "_hc_post_bwd_kernel", "_hc_pre_sums_kernel", "_hc_pre_bwd_kernel"])
def test_a_hyper_connections_kernel_compiles_for_v5e(v5e, kernel):
    fn, *args = hc_entries()[kernel]
    text = _compile_for(v5e, fn, *args)
    # the streams' cotangent is written over the one that came down to it
    if kernel == "_hc_pre_bwd_kernel":
        assert "output_to_operand_aliasing={{}: (0, {})}" in text


def test_xing4s_step_calls_each_hyper_connection_kernel_once_a_connection(v5e):
    """Two layers and the module's, at the rehearsal's widths (128 channels)
    over 256 tokens, which tile: six hyper-connections, each a read and a
    write forward and the three backward kernels; no replay holds a read or
    a write, the remat policy keeps what they wrote (models/llama.py
    REPLAY_KEEPS). A backward kernel's body stands once in the text,
    behind its jitted entry; a forward one's twice, a layer's first
    connection's and its second's, which remat's partial evaluation tells
    apart because the second's streams are a kept value; with one policy
    object for every ``_through`` the module's layer shares them
    (``models.llama._KEEP``). Every call
    is under /hc/pre/ or /hc/post/, where the benchmark's model.hc_share and
    model.hc_roofline look for it."""
    import importlib
    import re

    import numpy as np

    from benchmarks.lib import cells, checks
    from benchmarks.loops.train_lm import make_loss_fn, make_optimizer
    from ray_tpu import train

    attention = importlib.import_module("ray_tpu.ops.attention")
    cell = cells.load_cell("xing4-29b-a4b-l5.pretrain-mtp-4k")
    config, traffic = cell["config"], {**cell["traffic"], "seq": 256}
    config = {**config, **config["rehearsal"], "num_hidden_layers": 2}
    model = cells.resolve(config["program"]["model"])(cells.program_config(config))
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), tree)

    tx = make_optimizer(traffic)
    batch = jax.ShapeDtypeStruct((1, traffic["seq"]), np.int32, sharding=v5e)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "_on_tpu", lambda: True)
        text = train.make_train_step(make_loss_fn(traffic, model), tx).lower(
            placed(shapes), placed(jax.eval_shape(tx.init, shapes)), batch, batch
        ).as_text(debug_info=True)
    connections = 2 * (config["num_hidden_layers"] + config["num_nextn_predict_layers"])
    # entry: (kernel, bodies, calls, scope)
    entries = {"_pre_fwd": ("_hc_pre_fwd_kernel", 2, connections, "/hc/pre/"),
               "_post_fwd": ("_hc_post_fwd_kernel", 2, connections, "/hc/post/"),
               "_post_bwd": ("_hc_post_bwd_kernel", 1, connections, "/hc/post/"),
               "_pre_sums": ("_hc_pre_sums_kernel", 1, connections, "/hc/pre/"),
               "_pre_bwd": ("_hc_pre_bwd_kernel", 1, connections, "/hc/pre/")}
    bodies = checks.count_pallas_kernels(text, [k for k, *_ in entries.values()])
    assert bodies == {k: n for k, n, *_ in entries.values()}
    locations = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    for entry, (_, _, calls, scope) in entries.items():
        sites = re.findall(rf"call @{entry}(?:_\d+)?\(.*loc\((#loc\d+)\)$", text, re.M)
        assert len(sites) == calls, (entry, len(sites))
        for site in sites:
            assert scope in locations[site], (entry, locations[site])


# The Granite cell's kernels at the benchmark's real size (b1 x s8192): the
# Mamba-2 scan at 64 heads of 64 over a state of 128, u as [1, 8192, 64 x 64]
# (what the kernels take of ``chunk_ssd``'s [1, 8192, 64, 64]; nothing is
# transposed), B and C one [1, 8192, 128] pair, the steps as rows [1, 8, 8,
# 8192]; and at an odd number of groups. Each reads its own name as a
# profile's reader names it.
def _kernels(text):
    from benchmarks.lib import trace

    return [trace.kernel_name(line) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


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


def test_causal_flash_at_32_heads_of_64_compiles_for_v5e(v5e):
    """The Granite cell's one attention layer: K and V repeated from 8 to 32
    heads of 64 lanes, b1 x s8192, at the blocks ``flash_attention`` runs."""
    qkv = ((32, 8192, 64), jnp.bfloat16)
    _compile_for(v5e, _causal_fwd(1024), qkv, qkv, qkv)
    _compile_for(
        v5e, _causal_bwd(1024), qkv, qkv, qkv, qkv, ((32, 8192), jnp.float32), qkv)


@pytest.fixture(scope="module")
def granites_step(v5e):
    return _lowered_step(v5e, "granite-4-h-micro-l10.pretrain-8k")


def test_granites_step_holds_its_kernels_and_its_replay_runs_no_scan(granites_step):
    """The Granite cell's step at the benchmark's real size (b1 x s8192, ten
    layers at the published widths): every kernel its configuration states; a
    mamba layer is one ``_ssd_fwd_kernel`` and one ``_ssd_bwd_kernel`` in the
    whole step (the remat policy keeps ``ssd_y`` and ``ssd_states``), the
    states [1, 32, 8, 4, 128, 128] float32 written nine times and read nine
    times; B and C reach the kernels as [1, 8192, 128], never a head's copy;
    the one attention layer's three causal kernels; no other scan's kernel."""
    import re

    from benchmarks.lib import cells, checks

    cell, text = granites_step
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert checks.holds_stated_kernels(counts, stated), (counts, stated)
    assert (counts["_ssd_fwd_kernel"], counts["_ssd_bwd_kernel"]) == (9, 9)
    assert (counts["_fwd_kernel"], counts["_bwd_dkv_kernel"], counts["_bwd_dq_kernel"]) == (1, 1, 1)
    others = ("_kda_fwd_kernel", "_gdn_fwd_kernel", "_lightning_fwd_kernel",
              "_sparse_fwd_kernel", "_fwd_window_kernel")
    assert not any(checks.count_pallas_kernels(text, others).values())
    states = f"tensor<1x{8192 // kda.SSD_CHUNK}x8x4x128x128xf32>"
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum(f"{states})" in line for line in calls) == 9
    assert sum(f"{states}," in line for line in calls) == 9
    assert "tensor<1x8192x64x128x" not in text  # no B or C a head
    # the convolution by the kernels, forward, replayed and backward, a layer
    entries = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text))
               for entry in ("_conv_forward", "_conv_backward")}
    assert entries == {"_conv_forward": 2 * 9, "_conv_backward": 9}
    assert "tensor<1x8195x4352xf32>" not in text  # no short_conv fallback


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


@pytest.fixture(scope="module")
def lfm2s_step(v5e):
    return _lowered_step(v5e, "lfm2-8b-a1b-l5.dropfree-4k")


def test_lfm2s_step_holds_its_kernels_and_reads_the_projections_thirds_in_place(lfm2s_step):
    """The LFM2 cell's step at the benchmark's real size (b2 x s4096, layers
    1-5 at the published widths, all 32 experts): every kernel its
    configuration states; the four conv layers' gated convolutions by the
    kernels, forward, replayed and backward, each reading the one [2, 4096,
    6144] array and the pass back writing its cotangent whole (no slice of a
    third, no float32 copy, no concatenation, no padded copy of the XLA
    road); four expert layers of six ``_gmm_kernel`` and three
    ``_tgmm_kernel`` calls and a replay's three; the attention layer's three
    causal kernels; no scan's kernel and no un-gated convolution."""
    import re

    from benchmarks.lib import cells, checks

    cell, text = lfm2s_step
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert checks.holds_stated_kernels(counts, stated), (counts, stated)
    assert counts == {
        "_fwd_kernel": 2, "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1,
        "_gmm_kernel": 36, "_tgmm_kernel": 12,
        "_gated_conv_fwd_kernel": 2, "_gated_conv_bwd_kernel": 1}
    others = ("_conv_fwd_kernel", "_conv_bwd_kernel", "_kda_fwd_kernel", "_gdn_fwd_kernel",
              "_ssd_fwd_kernel", "_lightning_fwd_kernel", "_rotary_kernel")
    assert not any(checks.count_pallas_kernels(text, others).values())
    entries = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text))
               for entry in ("_gated_forward", "_gated_backward")}
    assert entries == {"_gated_forward": 2 * 4, "_gated_backward": 4}
    assert "tensor<2x4096x6144xf32>" not in text and "tensor<2x4098x2048xf32>" not in text
    thirds = [line for line in text.splitlines()
              if "stablehlo.slice" in line and "tensor<2x4096x6144xbf16>" in line]
    joined = [line for line in text.splitlines()
              if "stablehlo.concatenate" in line and "tensor<2x4096x6144xbf16>" in line]
    assert not thirds and not joined


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
