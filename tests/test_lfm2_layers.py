"""LFM2's architecture through the program's models, on the CPU, what needs no
model: the gated convolution against its line and ``jax.grad`` of it, causal,
depthwise and padded with zeros; ``conv_silu`` beside it lowers what it
lowered; the expert layer whole and as four ranks' shares
(``tests/test_lfm2_model.py`` has the model against its reference).
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe_decoder as reference
from ray_tpu.models.lfm2 import Lfm2Config
from ray_tpu.models.mixtral import MoELayer
from ray_tpu.ops import kda


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # The convolution's and the grouped matmuls' kernels and, from 128 rows,
    # the flash ones.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


# --------------------------------------------------- the gated convolution


def gated_reference(p, w, dtype):
    """The line ``gated_conv`` stands for, by the reference's three shifted
    products."""
    b, c, x = jnp.split(p.astype(jnp.float32), 3, axis=-1)
    conv = jax.vmap(lambda u: reference.short_conv(u, w.astype(jnp.float32)))(b * x)
    return (c * conv).astype(dtype)


def gated_inputs(batch, t, channels, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (batch, t, 3 * channels), jnp.float32).astype(dtype),
            jax.random.uniform(keys[1], (3, channels), jnp.float32, -0.5, 0.5),
            jax.random.normal(keys[2], (batch, t, channels), jnp.float32).astype(dtype))


def with_gradients(fn, p, w, dy):
    y, vjp = jax.vjp(lambda p, w: fn(p, w, dy.dtype), p, w)
    return (y, *vjp(dy))


# (batch, tokens, channels a third, dtype, the kernels' blocks or None): three
# blocks of 512 rows and two of 128 lanes in tiles of 64, so that the halo
# crosses tile and block edges both ways, over two batch rows, across which
# and across whose blocks the filter's gradient adds up; bfloat16 in and out,
# whose halo is the sublane tile of 16 rows; one tile of 16 rows, most of it
# the filter's reach from t < 0; and shapes that do not tile.
GATED_CASES = {
    "three-blocks": (2, 1536, 256, jnp.float32, (512, 256, 64, True, 0)),
    "bfloat16": (2, 192, 128, jnp.bfloat16, (64, 128, 64, True, 0)),
    "one-tile": (1, 16, 128, jnp.bfloat16, (16, 128, 16, True, 0)),
    "tokens-do-not-tile": (2, 100, 128, jnp.float32, None),
    "lanes-do-not-tile": (1, 64, 96, jnp.bfloat16, None),
}


@pytest.mark.parametrize("case", sorted(GATED_CASES))
def test_the_gated_convolution_is_its_line_and_its_gradients(case):
    """Under the interpreter ``gated_conv`` is the two Pallas passes where a
    third tiles and XLA's lines where it does not: the values, the cotangent
    of the projection's output whole, [B, T, 3 D] in its own dtype, and the
    filter's, to float32's reassociation and one rounding."""
    batch, t, channels, dtype, blocks = GATED_CASES[case]
    p, w, dy = gated_inputs(batch, t, channels, dtype)
    assert kda._gated_blocks(p, w) == blocks
    from kda_cases import pallas_calls

    both = jax.make_jaxpr(lambda *a: with_gradients(kda.gated_conv, *a))(p, w, dy)
    names = [eqn.params["jaxpr"].debug_info.func_name for eqn in pallas_calls(both.jaxpr, [])]
    assert names == (["_gated_conv_fwd_kernel", "_gated_conv_bwd_kernel"] if blocks else [])
    y, dp, dw = with_gradients(kda.gated_conv, p, w, dy)
    y_ref, dp_ref, dw_ref = with_gradients(gated_reference, p, w, dy)
    assert (y.dtype, dp.dtype, dw.dtype) == (dtype, dtype, jnp.float32)
    assert y.shape == dy.shape and dp.shape == p.shape and dw.shape == w.shape
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    tol = {"rtol": 2e-2, "atol": 2e-2} if dtype == jnp.bfloat16 else {"rtol": 1e-5, "atol": 1e-5}
    np.testing.assert_allclose(f32(y), f32(y_ref), **tol)
    np.testing.assert_allclose(f32(dp), f32(dp_ref), **tol)
    np.testing.assert_allclose(dw, dw_ref, rtol=1e-4, atol=1e-5 * float(jnp.abs(dw_ref).max()))
    if dtype == jnp.bfloat16 and blocks:  # one rounding, of values an ulp apart at most
        assert float(jnp.mean(y != y_ref)) < 2e-2 and float(jnp.mean(dp != dp_ref)) < 2e-2


def test_the_gated_convolution_is_causal_depthwise_and_padded_with_zeros(monkeypatch):
    """Blocks of 32 rows in tiles of 16. The first two tokens see zeros
    before the sequence: y_0 = C_0 w[2] u_0 and y_1 = C_1 (w[1] u_0 + w[2]
    u_1). A bump in B or in x~ at token t moves tokens t .. t + 2 of its own
    channel and batch row and nothing else, also where t is a block's last
    row (the halo); a bump in C moves its own token alone; the gradient reaches
    back as far and no further."""
    monkeypatch.setattr(kda, "_CONV_ROWS", 32)
    monkeypatch.setattr(kda, "_CONV_TILE", 16)
    p, w, _ = gated_inputs(2, 96, 128, jnp.float32, seed=1)
    assert kda._gated_blocks(p, w) == (32, 128, 16, True, 0)
    y = np.asarray(kda.gated_conv(p, w))
    np.testing.assert_allclose(y, gated_reference(p, w, jnp.float32), rtol=1e-5, atol=1e-6)
    b, c, x = np.split(np.asarray(p), 3, axis=-1)
    u, w_ = b * x, np.asarray(w)
    np.testing.assert_allclose(y[:, 0], c[:, 0] * w_[2] * u[:, 0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y[:, 1], c[:, 1] * (w_[1] * u[:, 0] + w_[2] * u[:, 1]), rtol=1e-5, atol=1e-6)
    for third, reach in ((0, 3), (1, 1), (2, 3)):
        for token in (7, 15, 31, 95):
            moved = np.asarray(kda.gated_conv(
                p.at[1, token, third * 128 + 2].add(1.0), w)) != y
            assert moved[1, token:token + reach, 2].all()
            moved[1, token:token + reach, 2] = False
            assert not moved.any(), (third, token)
    for token in (7, 31, 95):
        grad = jax.grad(lambda p: kda.gated_conv(p, w)[1, token, 2].sum())(p)
        reached = np.argwhere(np.asarray(grad) != 0)
        # B and x~ at the token and the two before it, C at the token
        assert {tuple(at) for at in reached} == (
            {(1, s, j * 128 + 2) for j in (0, 2) for s in range(max(token - 2, 0), token + 1)}
            | {(1, token, 128 + 2)})


def test_conv_silu_without_gates_lowers_what_it_lowered():
    """The gated kernels stand beside ``conv_silu``'s and change none of its
    operands, blocks, index maps or operations: the digests the Kimi-Linear,
    Solar-Open2 and Granite cells' calls are held to
    (``tests/test_conv_silu_op.py``), read here by that file's own reader
    (``tests/kda_cases.py``)."""
    from kda_cases import CONV_BEFORE, conv_calls_text

    for name, (shape, before) in CONV_BEFORE.items():
        text = conv_calls_text(*shape)
        assert hashlib.sha1(text.encode()).hexdigest()[:16] == before, name
        assert "_gated_conv" not in text


# ------------------------------------------------- the expert layer alone


def expert_layer(held):
    """One expert layer at LFM2's routing: 32 experts scored by sigmoid,
    top-4 of score plus bias, renormalised, x 1, no shared expert; ``held`` of
    them here (None: all)."""
    cfg = Lfm2Config(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_experts=32, num_experts_per_tok=4, experts_held=held,
        initializer_range=0.5, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return MoELayer(cfg)


LAYER = {"num_experts": 32, "num_experts_per_tok": 4, "norm_topk_prob": True,
         "routed_scaling_factor": 1}


@pytest.mark.parametrize("biased", [False, True], ids=["zero-bias", "a-bias"])
def test_every_expert_held_is_the_dense_loop_and_four_ranks_shares_add_up_to_it(biased):
    """The sigmoid router over every expert (``experts_held`` None: the road
    the cell runs, which no cell ran before) against the reference's dense
    loop over 32 experts; and the same layer as four expert-parallel ranks of
    eight experts each still adds up to it, every pair held by exactly one."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 48, 32)), jnp.float32)
    params = expert_layer(None).init(jax.random.PRNGKey(1), x)["params"]
    if biased:
        params = {**params, "router_bias": jax.random.normal(
            jax.random.PRNGKey(2), (32,), jnp.float32) * 0.3}
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(params, tokens, LAYER)
        gates = np.asarray(reference.router_gates(params, tokens, LAYER))
    whole = expert_layer(None).apply({"params": params}, x).reshape(-1, 32)
    np.testing.assert_allclose(whole, uncut, rtol=1e-4, atol=2e-5)
    # gates: four a token, renormalised, times 1
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-5)
    assert ((gates > 0).sum(-1) == 4).all()
    total, pairs = 0.0, 0
    for rank in range(4):
        held = (8 * rank, 8 * rank + 8)
        mine = {**params, **{k: params[k][held[0]:held[1]]
                             for k in ("w_gate", "w_up", "w_down")}}
        total = total + expert_layer(held).apply({"params": mine}, x).reshape(-1, 32)
        pairs += int((gates[:, held[0]:held[1]] > 0).sum())
    assert pairs == 96 * 4
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=2e-5)
