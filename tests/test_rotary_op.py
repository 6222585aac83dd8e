"""``ray_tpu/ops/rotary.py`` on the CPU: ``rotate`` through the Pallas
interpreter against ``models/llama.py`` ``_rope``, values and gradients, at
bfloat16 and float32: a whole head, the leading half under an amplitude
(Laguna's full layer), the trailing half, K at fewer heads than q, between a
projection's [B, T, H, D] and either layout a mixer hands on (heads first to
``flash_attention``, tokens first to ``chunk_lightning``), positions that do
not start at 0 and differ by batch row, a length of several blocks and more
heads than a grid step holds; and the road a call takes, which
``rotary_road`` says without running anything: ``_rope``'s at a head of 64
lanes, at a length that is not whole blocks, under a mesh that splits the
sequence, the batch or the heads, and where there is neither a TPU nor the
interpreter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import _rope, rope_frequencies
from ray_tpu.ops import rotary
from ray_tpu.parallel import MeshSpec

B, T, D = 2, 64, 128
# what turns: (len(freqs), leading, amplitude)
WHOLE, LEADING_HALF, TRAILING_HALF = (64, False, 1.0), (32, True, 1.4158883), (32, False, 1.0)


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def operands(heads, dtype, t=T, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, t, heads, D), jnp.float32)
    # a row that starts at 3 and one far along a long context
    positions = jnp.arange(t)[None, :] + jnp.array([[3], [16000]])
    return x.astype(dtype), positions


def between(turn, heads_first):
    """``turn`` as a mixer calls it: on the projection's [B, T, H, D] moved
    heads first, handed on so or moved back."""
    def mixer(x, positions):
        y = turn(x.transpose(0, 2, 1, 3), positions)
        return y if heads_first else y.transpose(0, 2, 1, 3)
    return mixer


def both(part, heads_first=True):
    """(``rotate``, ``_rope``) as functions of x [B, T, H, D] and positions,
    and the table."""
    half, leading, amplitude = part
    freqs = rope_frequencies(2 * half, 10000.0)
    kwargs = dict(leading=leading, amplitude=amplitude)
    return (between(lambda x, p: rotary.rotate(x, p, freqs, **kwargs), heads_first),
            between(lambda x, p: _rope(x, p, freqs, **kwargs), heads_first)), freqs


def close(a, b, dtype):
    """Within an ulp of ``dtype``: the order of a multiply-add is all that
    may differ."""
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -22
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               rtol=ulp, atol=ulp)


@pytest.mark.parametrize("heads_first", [True, False], ids=["heads_first", "tokens_first"])
@pytest.mark.parametrize("heads", [4, 1], ids=["q", "k_at_fewer_heads"])
@pytest.mark.parametrize("part", [WHOLE, LEADING_HALF, TRAILING_HALF],
                         ids=["whole", "leading_half_amplified", "trailing_half"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_rotate_is_rope_forward_and_backward(interpreter, dtype, part, heads, heads_first):
    (new, old), freqs = both(part, heads_first)
    x, positions = operands(heads, dtype)
    assert rotary.rotary_road((B, heads, T, D), freqs) == "kernel"
    got, want = new(x, positions), old(x, positions)
    assert got.shape == want.shape and got.dtype == dtype
    close(got, want, dtype)
    weights = jax.random.normal(jax.random.PRNGKey(1), want.shape, jnp.float32)
    loss = lambda f: lambda x: jnp.sum(f(x, positions).astype(jnp.float32) * weights)  # noqa: E731
    d_got, d_want = jax.grad(loss(new))(x), jax.grad(loss(old))(x)
    assert d_got.dtype == dtype
    close(d_got, d_want, dtype)


@pytest.mark.parametrize("part", [WHOLE, LEADING_HALF], ids=["whole", "leading_half"])
def test_rotate_over_row_blocks_and_head_groups(interpreter, monkeypatch, part):
    """Four blocks of rows, three grid steps of heads, two tiles a block: the
    tables' block stands across a row block's heads and moves with the rows."""
    monkeypatch.setattr(rotary, "ROWS", 64)
    monkeypatch.setattr(rotary, "HEADS", 2)
    (new, old), _ = both(part, True)
    x, positions = operands(6, jnp.bfloat16, t=256, seed=2)
    assert rotary._blocks((B, 6, 256, D)) == (64, 2)
    close(jax.jit(new)(x, positions), old(x, positions), jnp.bfloat16)
    loss = lambda f: lambda x: jnp.sum(f(x, positions).astype(jnp.float32) ** 2)  # noqa: E731
    close(jax.grad(loss(new))(x), jax.grad(loss(old))(x), jnp.bfloat16)


def test_the_cotangents_pass_is_the_rotation_back(interpreter):
    """Turned and turned back is where it started, to float32's rounding."""
    freqs = rope_frequencies(D, 10000.0)
    x, positions = operands(2, jnp.float32, seed=3)
    turn = rotary._Turn(False, 1.0, *rotary._blocks((B, 2, T, D)), True)
    x = x.transpose(0, 2, 1, 3)
    there = rotary._turned(x, positions, freqs, turn, False)
    assert np.abs(np.asarray(there - x)).max() > 1.0
    np.testing.assert_allclose(rotary._turned(there, positions, freqs, turn, True), x, atol=2e-6)


def test_freqs_take_no_cotangent_and_the_pass_back_reads_nothing_of_x(interpreter):
    freqs = rope_frequencies(D, 10000.0)
    x, positions = operands(2, jnp.bfloat16)
    x = x.transpose(0, 2, 1, 3)
    _, pullback = jax.vjp(lambda x, f: rotary.rotate(x, positions, f), x, freqs)
    dx, dfreqs = pullback(jnp.ones_like(x))
    assert dx.shape == x.shape and not np.any(np.asarray(dfreqs))
    # The pullback closes over positions and freqs: no array of x's size.
    kept = jax.tree_util.tree_leaves(pullback)
    assert kept and all(leaf.size < x.size for leaf in kept), [k.shape for k in kept]


ROADS = {
    "a_head_of_128_lanes": ((1, 8, 256, 128), 64, None, "kernel"),
    "the_leading_half_of_128": ((1, 8, 256, 128), 32, None, "kernel"),
    "a_head_of_64_lanes": ((1, 8, 256, 64), 32, None, "xla"),
    "a_head_of_192_lanes": ((1, 8, 256, 192), 32, None, "xla"),
    "a_length_of_no_whole_blocks": ((1, 8, 250, 128), 64, None, "xla"),
    "sixteen_rows": ((1, 8, 16, 128), 64, None, "kernel"),
    "eight_rows": ((1, 8, 8, 128), 64, None, "xla"),
    "a_mesh_that_splits_the_sequence": ((1, 8, 256, 128), 64, dict(seq=2), "xla"),
    "a_mesh_that_splits_the_batch": ((2, 8, 256, 128), 64, dict(data=2), "xla"),
    "a_mesh_that_splits_the_heads": ((1, 8, 256, 128), 64, dict(tensor=2), "xla"),
    "a_mesh_of_one_device": ((1, 8, 256, 128), 64, dict(), "kernel"),
    "a_mesh_that_splits_the_experts": ((1, 8, 256, 128), 64, dict(expert=2), "kernel"),
}


@pytest.mark.parametrize("case", sorted(ROADS))
def test_the_road_follows_from_shape_and_mesh(interpreter, case):
    shape, half, mesh, road = ROADS[case]
    freqs = jax.ShapeDtypeStruct((half,), jnp.float32)  # nothing runs
    if mesh is None:
        assert rotary.rotary_road(shape, freqs) == road
        return
    with jax.set_mesh(MeshSpec(**mesh).build()):
        assert rotary.rotary_road(shape, freqs) == road


def test_without_a_tpu_or_the_interpreter_the_road_is_ropes(monkeypatch):
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    (new, old), freqs = both(WHOLE)
    assert rotary.rotary_road((1, 8, 256, D), freqs) == "xla"
    x, positions = operands(2, jnp.bfloat16)
    assert "pallas_call" not in str(jax.make_jaxpr(new)(x, positions))
    np.testing.assert_array_equal(np.asarray(new(x, positions), np.float32),
                                  np.asarray(old(x, positions), np.float32))


@pytest.mark.parametrize("road", ["kernel", "xla"])
def test_under_a_mesh_that_splits_the_sequence_rotate_is_rope(interpreter, road):
    """The same call inside and outside the mesh: one road each, one answer."""
    (new, old), _ = both(WHOLE)
    x, positions = operands(2, jnp.bfloat16)
    if road == "xla":
        with jax.set_mesh(MeshSpec(seq=2).build()):
            text, got = str(jax.make_jaxpr(new)(x, positions)), new(x, positions)
    else:
        text, got = str(jax.make_jaxpr(new)(x, positions)), new(x, positions)
    assert ("pallas_call" in text) == (road == "kernel")
    close(got, old(x, positions), jnp.bfloat16)
