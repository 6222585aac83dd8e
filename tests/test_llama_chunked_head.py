"""The chunked head's own derivative rule (``models/llama.py`` ``_chunked_nll``)
against the full loss's gradient, over masks, lengths, vocabularies, tied
tables and upstream cotangents, and its float32 logits over bfloat16 operands
(``tests/test_llama.py`` has the model).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import causal_lm_loss


def _replayed_chunked_head_loss(hidden, head, targets, mask, chunk_size):
    """``chunked_head_loss`` as it stood while autodiff differentiated it: each
    chunk under ``jax.checkpoint``, the count summed in the scan. What the
    rule's loss is held to, bit for bit."""
    b, t = targets.shape
    m = (jnp.ones((b, t), jnp.float32) if mask is None
         else jnp.broadcast_to(mask.astype(jnp.float32), (b, t)))
    chunk_size = min(chunk_size, t)
    pad = (-t) % chunk_size
    hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    targets, m = (jnp.pad(a, ((0, 0), (0, pad))) for a in (targets, m))
    chunks = lambda a: a.reshape(b, -1, chunk_size, *a.shape[2:]).swapaxes(0, 1)  # noqa: E731

    @jax.checkpoint
    def chunk_nll(h, tg, mk):
        logits = jnp.matmul(h.astype(head.dtype), head.T,
                            preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        if logits.shape[-1] % 128:
            hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) == tg[..., None]
            gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        else:
            gold = jnp.take_along_axis(logits, tg[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mk), jnp.sum(mk)

    def body(carry, inp):
        nll, cnt = chunk_nll(*inp)
        return (carry[0] + nll, carry[1] + cnt), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (chunks(hidden), chunks(targets), chunks(m)),
    )
    return total / jnp.maximum(count, 1.0)


@pytest.mark.parametrize("upstream", [1.0, 0.3])
@pytest.mark.parametrize("tied", [False, True], ids=["lm_head", "tied"])
@pytest.mark.parametrize("vocab", [256, 200], ids=["lanes", "no-lanes"])
@pytest.mark.parametrize("seq", [32, 29], ids=["whole", "padded"])
@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
def test_the_chunked_heads_rule_is_the_full_losses_gradient(
        masked, seq, vocab, tied, upstream):
    """``chunked_head_loss`` differentiates by its own rule (the chunk's
    gradients made in the forward scan, then scaled by the cotangent). Its
    value is the replayed formula's to the bit; its gradients with respect to
    the hidden states and to the head (a dedicated kernel [H, V], or the
    table the tokens were also looked up in) are autodiff's through the
    unchunked ``causal_lm_loss`` to float32 rounding."""
    from ray_tpu.models.llama import chunked_head_loss, lm_head_weight
    from ray_tpu.util import tracing

    width, batch = 16, 2
    keys = jax.random.split(jax.random.PRNGKey(seq + vocab), 4)
    ids = jax.random.randint(keys[0], (batch, seq), 0, vocab)
    targets = jnp.roll(ids, -1, axis=1)
    mask = (jnp.arange(seq)[None] < seq - 3) if masked else None
    table = jax.random.normal(keys[1], (vocab, width), jnp.float32)
    if tied:
        params = {tracing.EMBED: {"embedding": table}}
    else:
        params = {tracing.EMBED: {"embedding": table},
                  tracing.LM_HEAD: {"kernel": jax.random.normal(keys[2], (width, vocab))}}
    mix = jax.random.normal(keys[3], (width, width)) / 4.0

    def hidden_of(p):
        return jnp.tanh(p[tracing.EMBED]["embedding"][ids] @ mix)

    def chunked(p, loss=chunked_head_loss):
        return upstream * loss(hidden_of(p), lm_head_weight(p), targets, mask, 8)

    def full(p):
        logits = hidden_of(p) @ lm_head_weight(p).T
        return upstream * causal_lm_loss(logits, targets, mask)

    primal = float(chunked(params))
    assert primal == float(chunked(params, _replayed_chunked_head_loss))
    # (under value_and_grad XLA fuses the scan's body with the gradient's lines
    # and sums in another order: the replayed formula's own value moves in its
    # last bit there too)
    value, grads = jax.value_and_grad(chunked)(params)
    want_value, want = jax.value_and_grad(full)(params)
    np.testing.assert_allclose(float(value), primal, rtol=1e-6)
    np.testing.assert_allclose(float(value), float(want_value), rtol=2e-6)
    # (the table's gradient holds the hidden states' cotangent: every row of
    # it came through ``hidden_of``)
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(want)
    for got, ref in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-7)


def test_the_chunked_heads_rule_keeps_float32_logits_over_bfloat16_operands():
    """bfloat16 hidden states and head (the benchmark's dtypes): the logits,
    the soft-max and both gradient products accumulate in float32, and the
    cotangents come back in the operands' dtypes, as close to the float32
    program's as bfloat16 operands allow."""
    from ray_tpu.models.llama import chunked_head_loss

    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    hidden = jax.random.normal(keys[0], (2, 32, 16), jnp.float32)
    head = jax.random.normal(keys[1], (256, 16), jnp.float32)
    targets = jax.random.randint(keys[2], (2, 32), 0, 256)
    low = (hidden.astype(jnp.bfloat16), head.astype(jnp.bfloat16))

    def chunked(h, w):
        return chunked_head_loss(h, w, targets, None, 8)

    value, grads = jax.value_and_grad(chunked, (0, 1))(*low)
    assert value.dtype == jnp.float32
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.bfloat16]
    # the same rounded operands, multiplied and differentiated in float32
    exact = [a.astype(jnp.float32) for a in low]
    want_value, want = jax.value_and_grad(
        lambda h, w: causal_lm_loss(h @ w.T, targets), (0, 1))(*exact)
    np.testing.assert_allclose(float(value), float(want_value), rtol=1e-6)
    for got, ref in zip(grads, want):
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref), atol=scale * 2.0 ** -7)
