"""``ray_tpu/ops/rotary.py`` ``latent_qkv`` on the CPU: latent attention's q
and k from the projections' outputs to the arrays ``flash_attention`` reads,
one Pallas pass each under the interpreter, against the lines
``models/mla.py`` keeps as its XLA road (``RMSNorm`` over a head's channels,
the transposition, ``_rope`` on the trailing 64 lanes, k's ``broadcast_to``
and ``concatenate``) computed in float32: q, k, v and every cotangent (the
two projections' outputs, the shared key part's summed over the heads, the
two norms' weights), for the kinds of layer the mixer has (norm and rotation,
the rotation alone, the norm alone), at bfloat16 and float32, over more than
one block of rows and grid step of heads; that freqs take no cotangent; the
road a call takes, which ``latent_road`` says without running anything; and
``MLAMixer`` itself on either road, one parameter tree.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import RMSNorm, _rope
from ray_tpu.models.mla import MLAConfig, MLAMixer, yarn_frequencies, yarn_scaling
from ray_tpu.ops import rotary
from ray_tpu.parallel import MeshSpec

B, T, H, NOPE, PE = 2, 64, 4, 128, 64
EPS = 1e-6
KINDS = {"norm_and_rotation": (True, True), "rotation_alone": (False, True),
         "norm_alone": (True, False)}


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def operands(dtype, t=T, heads=H, seed=0):
    """The projections' outputs as the mixer holds them, [B, T, H, .], the
    shared key part, the two weights, positions and the table."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (B, t, heads, NOPE + PE), jnp.float32)
    kv = jax.random.normal(keys[1], (B, t, heads, 2 * NOPE), jnp.float32)
    shared = jax.random.normal(keys[2], (B, t, PE), jnp.float32)
    q_w = 1.0 + 0.2 * jax.random.normal(keys[3], (NOPE + PE,), jnp.float32)
    k_w = 1.0 + 0.2 * jax.random.normal(keys[4], (NOPE + PE,), jnp.float32)
    # a row that starts at 3 and one far along a long context
    positions = jnp.arange(t)[None, :] + jnp.array([[3], [16000]])
    return (q.astype(dtype), kv.astype(dtype), shared.astype(dtype), q_w, k_w), positions


def table(kind):
    scaling = yarn_scaling({"type": "deepseek_yarn", "factor": 40,
                            "original_max_position_embeddings": 4096,
                            "mscale": 1, "mscale_all_dim": 1})
    return jnp.asarray(yarn_frequencies(PE, 10000.0, scaling)) if KINDS[kind][1] else None


def mixers_lines(kind, positions, dtype=jnp.float32):
    """``models/mla.py``'s XLA road from the projections to the kernels, on
    operands moved to ``dtype``."""
    norms, freqs = KINDS[kind][0], table(kind)

    def lines(q, kv, shared, q_w, k_w):
        q, kv, shared = (a.astype(dtype) for a in (q, kv, shared))
        k_pe = jnp.broadcast_to(shared[:, :, None, :], (*kv.shape[:3], PE))
        k = jnp.concatenate([kv[..., :NOPE], k_pe], axis=-1)
        v = kv[..., NOPE:]
        if norms:
            norm = RMSNorm(EPS, jnp.float32)
            q = norm.apply({"params": {"scale": q_w}}, q)
            k = norm.apply({"params": {"scale": k_w}}, k)
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        if freqs is not None:
            q, k = _rope(q, positions, freqs), _rope(k, positions, freqs)
        return q, k, v
    return lines


def kernels(kind, positions):
    norms, freqs = KINDS[kind][0], table(kind)

    def passes(q, kv, shared, q_w, k_w):
        return rotary.latent_qkv(
            q.transpose(0, 2, 1, 3), kv.transpose(0, 2, 1, 3), shared, positions, freqs,
            q_w if norms else None, k_w if norms else None, EPS)
    return passes


def close(got, want, dtype, scale=1.0):
    """Within two ulps of ``dtype`` at ``scale``: the reference is float32
    throughout, the kernel rounds once, the order of the sums differs."""
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -19
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=2 * ulp, atol=2 * ulp * scale)


def loss_of(f, weights):
    def loss(*args):
        return sum(jnp.sum(out.astype(jnp.float32) * w) for out, w in zip(f(*args), weights))
    return loss


NAMES = ("q_proj", "kv_b_proj", "shared_part", "q_norm", "k_norm")


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_latent_qkv_is_the_mixers_lines_forward_and_backward(interpreter, dtype, kind):
    args, positions = operands(dtype)
    norms, turns = KINDS[kind]
    assert rotary.latent_road((B, H, T, NOPE + PE), (B, H, T, 2 * NOPE), PE,
                              norms=norms, turns=turns) == "kernel"
    new, old = kernels(kind, positions), mixers_lines(kind, positions)
    got, want = new(*args), old(*args)
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape and g.dtype == dtype, name
        close(g, w, dtype, scale=4.0)
    np.testing.assert_array_equal(  # v is kv's second half as it was
        np.asarray(got[2], np.float32),
        np.asarray(args[1][..., NOPE:].transpose(0, 2, 1, 3), np.float32))
    weights = [jax.random.normal(jax.random.PRNGKey(7 + i), w.shape, jnp.float32)
               for i, w in enumerate(want)]
    d_got = jax.grad(loss_of(new, weights), argnums=range(5))(*args)
    d_want = jax.grad(loss_of(old, weights), argnums=range(5))(*args)
    for name, g, w, arg in zip(NAMES, d_got, d_want, args):
        assert g.shape == arg.shape and g.dtype == arg.dtype, name
        if name.endswith("_norm") and not norms:
            assert not np.any(np.asarray(g)), name
            continue
        # the shared part's cotangent is a sum over H heads; a weight's over
        # B H T rows of cotangents that came rounded to ``dtype``: to its size
        scale = 4.0 * H ** 0.5 if name == "shared_part" else 4.0
        close(g, w, dtype, scale=float(jnp.abs(w).max()) if name.endswith("_norm") else scale)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_latent_qkv_over_row_blocks_and_head_groups(interpreter, monkeypatch, kind):
    """Four blocks of rows, three grid steps of heads, two tiles a block: the
    shared part's cotangent sums over the grid's steps of heads, the weights'
    over every step's partial sums."""
    monkeypatch.setattr(rotary, "ROWS", 64)
    monkeypatch.setattr(rotary, "HEADS", 2)
    args, positions = operands(jnp.bfloat16, t=256, heads=6, seed=2)
    assert rotary._blocks((B, 6, 256, NOPE + PE)) == (64, 2)
    new, old = kernels(kind, positions), mixers_lines(kind, positions)
    for g, w in zip(jax.jit(new)(*args), old(*args)):
        close(g, w, jnp.bfloat16, scale=4.0)
    squares = lambda f: lambda *a: sum(  # noqa: E731
        jnp.sum(out.astype(jnp.float32) ** 2) for out in f(*a))
    d_got = jax.grad(squares(new), argnums=range(5))(*args)
    d_want = jax.grad(squares(old), argnums=range(5))(*args)
    for name, g, w in zip(NAMES, d_got, d_want):
        if name.endswith("_norm"):
            # a weight's gradient is a sum over B H T rows: to its own size
            np.testing.assert_allclose(g, w, rtol=2e-2, atol=2e-2 * float(jnp.abs(w).max() + 1))
        else:
            close(g, w, jnp.bfloat16, scale=16.0 if name == "shared_part" else 8.0)


@pytest.mark.parametrize("kind", ["norm_and_rotation", "rotation_alone"])
def test_freqs_take_no_cotangent_and_the_pass_back_keeps_what_the_norm_reads_again(
        interpreter, kind):
    args, positions = operands(jnp.bfloat16)
    norms, freqs = KINDS[kind][0], table(kind)
    weights = args[3:] if norms else (None, None)
    heads_first = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    q, kv = heads_first(args[0]), heads_first(args[1])
    outs, pullback = jax.vjp(
        lambda q, kv, f: rotary.latent_qkv(q, kv, args[2], positions, f, *weights, EPS),
        q, kv, freqs)
    dq, dkv, dfreqs = pullback(tuple(jnp.ones_like(o) for o in outs))
    assert dq.shape == q.shape and dkv.shape == kv.shape and not np.any(np.asarray(dfreqs))
    # Kept for the pass back: the projections' outputs where the norm's
    # transpose reads them again and nothing of their size where the layer
    # only turns; never a normed or turned copy of q or k.
    kept = sorted(leaf.size for leaf in jax.tree_util.tree_leaves(pullback))
    if norms:
        assert kept[-2:] == sorted([q.size, kv.size]) and kept[-3] <= args[2].size, kept
    else:
        assert kept[-1] <= positions.size, kept


WHOLE = ((1, 8, 256, 192), (1, 8, 256, 256), 64)
ROADS = {
    "sarvams_head": (*WHOLE, (True, True), None, "kernel"),
    "xing4s_head_without_the_norm": (*WHOLE, (False, True), None, "kernel"),
    "a_norm_and_no_rotation": (*WHOLE, (True, False), None, "kernel"),
    "kimi_linears_neither": (*WHOLE, (False, False), None, "xla"),
    "a_toy_head_of_16_and_16": ((1, 4, 256, 32), (1, 4, 256, 32), 16, (True, True), None, "xla"),
    "a_nope_part_of_64": ((1, 8, 256, 128), (1, 8, 256, 128), 64, (True, True), None, "xla"),
    "a_turning_part_of_128": ((1, 8, 256, 256), (1, 8, 256, 256), 128, (True, True), None, "xla"),
    "values_of_64": ((1, 8, 256, 192), (1, 8, 256, 192), 64, (True, True), None, "xla"),
    "a_length_of_no_whole_blocks": ((1, 8, 250, 192), (1, 8, 250, 256), 64, (True, True), None, "xla"),
    "sixteen_rows": ((1, 8, 16, 192), (1, 8, 16, 256), 64, (True, True), None, "kernel"),
    "a_mesh_that_splits_the_sequence": (*WHOLE, (True, True), dict(seq=2), "xla"),
    "a_mesh_that_splits_the_batch": ((2, 8, 256, 192), (2, 8, 256, 256), 64, (True, True),
                                     dict(data=2), "xla"),
    "a_mesh_that_splits_the_heads": (*WHOLE, (True, True), dict(tensor=2), "xla"),
    "a_mesh_of_one_device": (*WHOLE, (True, True), dict(), "kernel"),
    "a_mesh_that_splits_the_experts": (*WHOLE, (True, True), dict(expert=2), "kernel"),
}


@pytest.mark.parametrize("case", sorted(ROADS))
def test_the_latent_road_follows_from_the_layer_the_shapes_and_the_mesh(interpreter, case):
    q_shape, kv_shape, pe, (norms, turns), mesh, road = ROADS[case]
    if mesh is None:
        assert rotary.latent_road(q_shape, kv_shape, pe, norms=norms, turns=turns) == road
        return
    with jax.set_mesh(MeshSpec(**mesh).build()):
        assert rotary.latent_road(q_shape, kv_shape, pe, norms=norms, turns=turns) == road


def test_without_a_tpu_or_the_interpreter_the_latent_road_is_xlas(monkeypatch):
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    assert rotary.latent_road(*WHOLE, norms=True, turns=True) == "xla"


def mixer(kind, dtype=jnp.bfloat16):
    norms, turns = KINDS.get(kind, (False, False))
    cfg = MLAConfig(
        hidden_size=64, num_heads=2, kv_lora_rank=32, qk_nope_head_dim=NOPE,
        qk_rope_head_dim=PE, v_head_dim=NOPE, mla_rope=turns, qk_head_norm=norms,
        rope_scaling=yarn_scaling({"type": "yarn", "factor": 40,
                                   "original_max_position_embeddings": 4096,
                                   "mscale": 1, "mscale_all_dim": 1}) if turns else None,
        rms_eps=EPS, dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 64), jnp.float32).astype(dtype)
    return MLAMixer(cfg), x, jnp.arange(128)[None, :]


@pytest.mark.parametrize("kind", [*sorted(KINDS), "neither"])
def test_the_mixer_gives_one_answer_and_one_parameter_tree_on_either_road(monkeypatch, kind):
    """``MLAMixer`` under the interpreter (the kernels where the layer norms
    or turns) and without it (today's lines): the same paths and shapes, an
    output and gradients that agree to bfloat16's rounding."""
    model, x, positions = mixer(kind)
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    params = model.init(jax.random.PRNGKey(1), x, positions)
    # weights that are not all ones, so that a norm's weight is seen
    leaves, tree = jax.tree_util.tree_flatten(params)
    params = tree.unflatten([
        leaf + 0.1 * jax.random.normal(jax.random.PRNGKey(i), leaf.shape, leaf.dtype)
        for i, leaf in enumerate(leaves)])

    def loss():  # a new function a road: a trace is kept by the function's identity
        return lambda p: jnp.sum(model.apply(p, x, positions).astype(jnp.float32) ** 2)

    xla_text = str(jax.make_jaxpr(loss())(params))
    want, d_want = jax.value_and_grad(loss())(params)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    again = model.init(jax.random.PRNGKey(1), x, positions)
    assert jax.tree_util.tree_structure(again) == tree
    assert [a.shape for a in jax.tree_util.tree_leaves(again)] == [a.shape for a in leaves]
    text = str(jax.make_jaxpr(loss())(params))
    for name in ("_latent_q_forward", "_latent_k_forward"):  # the jitted entries
        assert (name in text) == (kind != "neither"), name
        assert name not in xla_text
    got, d_got = jax.value_and_grad(loss())(params)
    np.testing.assert_allclose(got, want, rtol=2e-2)
    flat = jax.tree_util.tree_flatten_with_path
    for (path, g), (_, w) in zip(flat(d_got)[0], flat(d_want)[0]):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32), rtol=5e-2,
            atol=5e-2 * float(jnp.abs(w).max()) + 1e-6, err_msg=jax.tree_util.keystr(path))
    if KINDS.get(kind, (False,))[0]:
        assert {"q_norm", "k_norm"} <= set(params["params"])
        assert params["params"]["q_norm"]["scale"].shape == (NOPE + PE,)
