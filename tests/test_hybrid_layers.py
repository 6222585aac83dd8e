"""What the hybrid configuration forced on shared code, held on the CPU:
flash attention with q/k and v of different head dims and a scale the
caller gives; the grouped matmul told how many of its tiles hold rows; and
the models the benchmark already had, whose parameter trees and lowered
steps' Pallas kernels are what they were before a layer could choose its
mixer and FFN.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from ray_tpu.ops.attention import attention_reference, flash_attention
from ray_tpu.ops.gmm import aligned_group_layout, gmm


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


# ------------------------------------------------------- flash at 192 / 128


def qkv(t, d, d_v, heads=2, kv_heads=2, seed=0):
    r = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa: E731
    return draw(1, heads, t, d), draw(1, kv_heads, t, d), draw(1, kv_heads, t, d_v)


@pytest.mark.parametrize("d,d_v", [(192, 128), (128, 128), (64, 128)])
@pytest.mark.parametrize("t", [256, 320])
def test_flash_kernels_take_v_of_another_head_dim(t, d, d_v):
    """Forward and the three gradients through the Pallas kernels against
    ``attention_reference``, blocks of 128 so that the causal skip and the
    padding of a ragged last block run."""
    q, k, v = qkv(t, d, d_v)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(1, 2, t, d_v)), jnp.float32)
    scale = d ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=scale,
                               block_q=128, block_k=128)

    def plain(q, k, v):
        return attention_reference(q, k, v, causal=True, sm_scale=scale)

    out = flash(q, k, v)
    assert out.shape == (1, 2, t, d_v)
    np.testing.assert_allclose(out, plain(q, k, v), rtol=1e-4, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


def test_the_scale_is_the_callers_and_defaults_to_q_and_ks_head_dim():
    q, k, v = qkv(128, 192, 128)
    default = flash_attention(q, k, v)
    np.testing.assert_allclose(
        default, attention_reference(q, k, v, sm_scale=192 ** -0.5), rtol=1e-4, atol=1e-5)
    other = flash_attention(q, k, v, sm_scale=0.05)
    np.testing.assert_allclose(
        other, attention_reference(q, k, v, sm_scale=0.05), rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(other - default).max()) > 1e-3


# ------------------------------------------- the grouped matmul with a bound


def bounded_case(sizes, tail, k=64, n=128, seed=0):
    """Rows of groups of ``sizes`` and ``tail`` rows of one more group,
    sorted last, which holds nothing of this device's."""
    r = np.random.default_rng(seed)
    groups = len(sizes)
    ids = np.concatenate([np.full(s, g) for g, s in enumerate(sizes)]
                         + [np.full(tail, groups)]).astype(np.int32)
    ids = jnp.asarray(r.permutation(ids))
    order, dst, tile_group, m_pad = aligned_group_layout(ids, groups + 1)
    used = jnp.sum(tile_group < groups, dtype=jnp.int32).reshape(1)
    rows = jnp.asarray(r.normal(size=(ids.shape[0], k)), jnp.float32)
    here = (ids < groups)[order]
    lhs = jnp.zeros((m_pad, k), jnp.float32).at[dst].set(
        jnp.where(here[:, None], rows[order], 0.0))
    rhs = jnp.asarray(r.normal(size=(groups, k, n)), jnp.float32)
    return lhs, rhs, jnp.minimum(tile_group, groups - 1), used, tile_group


@pytest.mark.parametrize("sizes,tail", [
    ((100, 0, 300, 28), 900),  # an empty group among them; most rows elsewhere
    ((128, 128), 0),  # nothing elsewhere: the tail is the layout's padding
    ((5,), 2000),
], ids=["mixed", "all-here", "nearly-none"])
def test_tiles_past_the_used_ones_are_not_touched_and_cost_no_gradient(sizes, tail):
    """A bounded call promises nothing about the rows past ``tiles_used``,
    in its output or in ``lhs``'s gradient, and reads none of them: NaN
    there, in the rows and in the cotangent, reaches no row it does
    promise and no weight gradient."""
    lhs, rhs, tile_group, used, raw = bounded_case(sizes, tail)
    rows = int(used[0]) * 128
    assert (np.asarray(raw)[:rows // 128] < len(sizes)).all()
    assert (np.asarray(raw)[rows // 128:] == len(sizes)).all()
    w = jnp.asarray(np.random.default_rng(2).normal(size=(lhs.shape[0], rhs.shape[2])), jnp.float32)

    def loss(call):
        return lambda a, b: jnp.sum((call(a, b) * w)[:rows])

    # the oracle: every tile computed, on rows that hold zeros past the used ones
    want = gmm(lhs, rhs, tile_group)
    g_want = jax.grad(loss(lambda a, b: gmm(a, b, tile_group)), (0, 1))(lhs, rhs)
    dirty = lhs.at[rows:].set(jnp.nan)
    bounded = lambda a, b: gmm(a, b, tile_group, 128, used)  # noqa: E731
    got = bounded(dirty, rhs)
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=1e-5, atol=1e-5)
    # the cotangent of an unused row is whatever its consumer left there
    g_got = jax.grad(
        lambda a, b: jnp.sum(bounded(a, b) * w.at[rows:].set(jnp.nan)), (0, 1)
    )(dirty, rhs)
    np.testing.assert_allclose(g_got[0][:rows], g_want[0][:rows], rtol=1e-4, atol=1e-4)
    assert np.isfinite(np.asarray(g_got[1])).all()
    np.testing.assert_allclose(g_got[1], g_want[1], rtol=1e-4, atol=1e-4)


# ------------------------------------------------ the models that were there


def tree_digest(tree) -> tuple:
    """Names, shapes and dtypes of a parameter tree in one digest, and the
    number of leaves."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    text = ";".join(f"{jax.tree_util.keystr(p)}:{x.shape}:{x.dtype}" for p, x in flat)
    return hashlib.sha1(text.encode()).hexdigest()[:16], len(flat)


# Read by this same code at the parent of the PR that let a layer choose
# its mixer and FFN (commit 57913f4), at each file's rehearsal size. The
# lowered steps' kernel counts are in tests/test_aot_v5e_steps_before.py.
TREES_BEFORE = {
    "mistral-7b-l4": ("06a35641bbb39a58", 21),
    "mixtral-8x7b-l2": ("ec5224b367a49c8f", 22),
    "olmoe-1b-7b-1chip": ("41194dbd4d5dc32a", 27),
}


@pytest.mark.parametrize("name", sorted(TREES_BEFORE))
def test_the_models_that_were_there_keep_their_parameter_trees(name):
    config = cells.load_json(f"{cells.BENCH_DIR}/configs/{name}.json")
    config = {**config, **config["rehearsal"]}
    cfg = cells.program_config(config)
    model = cells.resolve(config["program"]["model"])(cfg)
    # one kind of layer, said once
    assert len(set(cfg.layers)) == 1 and len(cfg.layers) == cfg.num_layers
    assert cfg.layers[0] == ("attn", "moe" if hasattr(cfg, "num_experts") else "mlp")
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    assert tree_digest(shapes) == TREES_BEFORE[name]
    if hasattr(cfg, "num_experts"):
        # the defaults of what the hybrid added leave the layer as it was
        assert (cfg.router_score, cfg.routed_scaling_factor, cfg.num_shared_experts,
                cfg.experts_held, cfg.expert_width) == (
            "softmax", 1.0, 0, None, cfg.intermediate_size)
    assert cfg.remat_prevent_cse is False
