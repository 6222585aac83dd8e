"""dots3-note-prev's architecture through the program's models, on the CPU: a
hand-written line each for the rescale, the headwise gate, the band and the
threshold with ties; the selection of a short sequence; the selection's
kernels against explicit masks (``tests/test_dots3_model.py`` has the model
against its reference and says what the reference is; ``tests/dots3_cases.py``
what the files share).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.mla import LatentKind
from ray_tpu.ops import attention

from dots3_cases import interpret, one_mixer  # noqa: F401 - fixtures


KIND = LatentKind(4, 32, 16, 16, 16, 1e4, mla_rope=True, q_lora_rank=24)


def test_the_rescale_is_each_latent_times_the_root_of_hidden_over_its_rank():
    """c_q x (64 / 24)^1/2 and c x (64 / 32)^1/2: the same as the plain layer
    with the factors in the up-projections that read the latents."""
    plain, params, x, positions = one_mixer(KIND)
    rescaled = one_mixer(dataclasses.replace(KIND, rescale=True))[0]
    p = params["params"]
    folded = {"params": {
        **p,
        "q_b_proj": {"kernel": p["q_b_proj"]["kernel"] * (64 / 24) ** 0.5},
        "kv_b_proj": {"kernel": p["kv_b_proj"]["kernel"] * (64 / 32) ** 0.5},
    }}
    np.testing.assert_allclose(
        rescaled.apply(params, x, positions), plain.apply(folded, x, positions),
        rtol=2e-5, atol=3e-5)


def test_the_gate_is_one_sigmoid_a_head_and_token_before_o_proj():
    """out = sum_n (o_n sigmoid(x W_g)_n) W_o[n]: with W_g = 0 half the plain
    layer's; with head 0's column far below zero, the plain layer's without
    head 0."""
    plain, params, x, positions = one_mixer(KIND)
    gated = one_mixer(dataclasses.replace(KIND, gate=True))[0]
    out = plain.apply(params, x, positions)
    p = params["params"]
    zero = {"params": {**p, "g_proj": {"kernel": jnp.zeros((64, 4))}}}
    np.testing.assert_allclose(
        gated.apply(zero, x, positions), 0.5 * out, rtol=2e-5, atol=1e-6)
    # x has a constant channel: its column of W_g is a bias a head
    x1 = x.at[..., 0].set(1.0)
    shut = jnp.zeros((64, 4)).at[0].set(jnp.asarray([-40.0, 40.0, 40.0, 40.0]))
    headless = {"params": {
        **p, "o_proj": {"kernel": p["o_proj"]["kernel"].at[0].set(0.0)}}}
    np.testing.assert_allclose(
        gated.apply({"params": {**p, "g_proj": {"kernel": shut}}}, x1, positions),
        plain.apply(headless, x1, positions), rtol=2e-5, atol=1e-6)


def test_a_window_of_513_is_the_row_and_the_512_before_it():
    """Row t sees keys t - 512 .. t: the reference's band, by hand, and the
    program's ``flash_attention(window=)`` under it at 1,100 rows."""
    t, window = 1100, 513
    ahead = np.arange(t)[:, None] - np.arange(t)[None, :]
    band = (ahead >= 0) & (ahead <= 512)
    assert band.sum(1).tolist() == [min(i + 1, 513) for i in range(t)]
    assert band[1000].nonzero()[0][[0, -1]].tolist() == [488, 1000]
    np.testing.assert_array_equal(attention._visible(t, t, window), band)
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, t, 8)), jnp.float32)
               for _ in range(3))
    s = np.einsum("td,sd->ts", q[0, 0], k[0, 0]) * 8 ** -0.5
    p = np.where(band, np.exp(s - s.max(1, keepdims=True)), 0.0)
    want = (p / p.sum(1, keepdims=True)) @ np.asarray(v[0, 0])
    got = attention.flash_attention(q, k, v, window=window)[0, 0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def chosen_by_hand(scores: np.ndarray, topk: int) -> np.ndarray:
    """Row t keeps {s <= t : I[t, s] >= the topk-th largest of I[t, :t+1]}."""
    t = scores.shape[0]
    seen = np.zeros((t, t), bool)
    for row in range(t):
        mine = scores[row, :row + 1]
        least = np.sort(mine)[::-1][min(topk, row + 1) - 1]
        seen[row, :row + 1] = mine >= least
    return seen


@pytest.mark.parametrize("t", [96, 256], ids=["xla", "kernel"])
def test_the_threshold_keeps_ties(t):
    """One index head of one channel, w = 1: I[t, s] = ReLU(q_t k_s), and with
    q and k from a few small integers whole heaps of keys score alike (and
    half score 0): a row keeps every key at its threshold, so more than
    ``topk`` where the threshold's heap is cut."""
    rng = np.random.default_rng(5)
    q = rng.integers(1, 3, size=(1, 1, t, 8)).astype(np.float32) * (np.arange(8) == 0)
    k = rng.integers(-2, 4, size=(1, t, 8)).astype(np.float32) * (np.arange(8) == 0)
    w = np.ones((1, t, 1), np.float32)
    topk = 24
    words = attention.index_keys(jnp.asarray(q), jnp.asarray(k), jnp.asarray(w), topk=topk)
    scores = np.maximum(q[0, 0, :, :1] * k[0, :, 0][None, :], 0.0)
    want = chosen_by_hand(scores, topk)
    np.testing.assert_array_equal(attention._unpack_keys(words, t)[0], want)
    kept = want.sum(1)
    assert (kept[:topk] == np.arange(1, topk + 1)).all()  # every key while few
    assert (kept >= np.minimum(np.arange(t) + 1, topk)).all() and kept.max() > topk


@pytest.mark.parametrize("t", [64, 160], ids=["xla", "kernel"])
def test_the_selection_of_a_short_sequence_is_the_causal_mask(t):
    rng = np.random.default_rng(6)
    q, k = rng.normal(size=(1, 2, t, 16)), rng.normal(size=(1, t, 16))
    w = rng.normal(size=(1, t, 2))
    words = attention.index_keys(
        *(jnp.asarray(a, jnp.float32) for a in (q, k, w)), topk=t)
    np.testing.assert_array_equal(
        attention._unpack_keys(words, t)[0], np.tril(np.ones((t, t), bool)))


# ------------------------------------- the fourth mask's kernels, interpreted


def explicit(q, k, v, seen, scale):
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * scale
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


@pytest.mark.parametrize("t,topk", [(256, 100), (300, 64), (1280, 200)])
def test_the_selection_kernels_agree_with_an_explicit_mask(t, topk):
    """The indexer kernel's words against the scores' own threshold, and the
    three flash kernels under them (forward, dK/dV, dQ) against a masked
    soft-max and its autodiff: 1,280 rows are two tiles of 1,024 with a dead
    one above the diagonal, 300 a padded tile of 256."""
    rng = np.random.default_rng(t)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q, k, v = f32(1, 2, t, 24), f32(1, 2, t, 24), f32(1, 2, t, 16)
    q_i, k_i, w = f32(1, 3, t, 16), f32(1, t, 16), f32(1, t, 3)
    words = attention.index_keys(q_i, k_i, w, topk=topk)
    scores = np.asarray(attention.index_scores(q_i, k_i, w))[0]
    seen = chosen_by_hand(scores, topk)
    np.testing.assert_array_equal(attention._unpack_keys(words, t)[0], seen)
    seen, scale = jnp.asarray(seen)[None], 24 ** -0.5
    weight = jnp.cos(jnp.arange(16.0))
    got, back = jax.value_and_grad(
        lambda *qkv: (attention.flash_attention(
            *qkv, keys=words, sm_scale=scale) * weight).sum(), (0, 1, 2))(q, k, v)
    want, wanted = jax.value_and_grad(
        lambda *qkv: (explicit(*qkv, seen, scale) * weight).sum(), (0, 1, 2))(q, k, v)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(back, wanted):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


def test_keys_are_refused_where_they_have_no_meaning():
    q = jnp.zeros((1, 2, 64, 8))
    words = attention._pack_keys(jnp.ones((1, 64, 64), bool), 128)
    with pytest.raises(ValueError, match="keys= is causal"):
        attention.flash_attention(q, q[:, :1], q[:, :1], keys=words)
    with pytest.raises(ValueError, match="keys= is causal"):
        attention.flash_attention(q, q, q, keys=words, window=8)
