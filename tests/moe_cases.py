"""What the test files of the expert layer share (``tests/test_moe_*.py``): the
tiny Mixtral and its parameters, the interpreter's switch, a mesh as a
context, the routings and meshes the expert FFN is run over, and the body of
the case that holds it to the plain einsum, which
``tests/test_moe_expert_ffn.py`` runs on one device and on expert-only meshes
and ``tests/test_moe_expert_ffn_split_rows.py`` where the rows or the width
are split too. A plain module: a piece imports what it reads by name.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny_moe():
    from ray_tpu.models.mixtral import CONFIGS, MixtralForCausalLM

    cfg = CONFIGS["mixtral-tiny"]
    import dataclasses

    cfg = dataclasses.replace(cfg, dtype=jnp.float32, remat=False)
    model = MixtralForCausalLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    return cfg, model, ids, params


# ------------------------------------------------- the capacity branch's FFN
#
# expert_ffn against the plain einsum (_swiglu) on the same buffers. The
# tiny configuration's widths with Mixtral's eight experts, top-2, at
# capacity factor 4.0 and 2,048 tokens a row: C = 2,048 slots, four tiles of
# 512, and a buffer that one expert's pairs can fill. The tiled FFN's weight
# gradients are ops/gmm.py's kernel, interpreted here: a block of them that
# no trip visits reads NaN, and one written twice holds its last visit alone.

FFN_ROWS, FFN_TOKENS, FFN_EXPERTS, FFN_TOP_K = 2, 2048, 8, 2
# counts[row][expert]: pairs in the buffer of an (expert, row); the slots
# that hold them are the prefix, as arrival order fills them. A row's counts
# come to its 4,096 pairs and none is over its 2,048 tokens, as a router's do.
ROUTINGS = {
    "expert_with_no_pair": [[0, 1400, 600, 80, 1000, 1016, 0, 0],
                            [5, 0, 512, 1, 2048, 1500, 30, 0]],
    "prefix_ends_inside_a_tile": [[700, 3, 0, 130, 513, 1100, 1550, 100],
                                  [600, 513, 100, 1, 1027, 1300, 255, 300]],
    "prefix_ends_at_a_tiles_edge": [[1024, 512, 512, 0, 1536, 512, 0, 0],
                                    [0, 1024, 0, 1024, 512, 512, 1024, 0]],
    "full_buffer": [[2048, 2048, 0, 0, 0, 0, 0, 0],
                    [1, 2048, 600, 1447, 0, 0, 0, 0]],
    # Every pair of a row to one half of the experts, in prefixes that end
    # just inside a tile: the 11 tiles of 16 that _ffn_trips allows a chip
    # of seq=2 x expert=2.
    "most_tiles_a_chip_can_reach": [[1152, 1152, 1152, 640, 0, 0, 0, 0],
                                    [0, 0, 0, 0, 640, 1152, 1152, 1152]],
    # One expert of a chip's four holds all of a row's pairs that come to
    # the chip: the three others' only trips are empty tiles.
    "every_pair_to_one_expert_a_chip": [[2048, 0, 0, 0, 0, 2048, 0, 0],
                                        [0, 0, 0, 2048, 0, 0, 2048, 0]],
    # Experts that reach tiles between experts that reach none: the empty
    # tiles that fill the trips lie before, between and after the reached
    # ones, and each expert's trips still have to be consecutive.
    "reached_and_empty_interleave": [[1, 0, 2047, 0, 2048, 0, 0, 0],
                                     [0, 600, 0, 1448, 0, 2048, 0, 0]],
}
FFN_MESHES = {
    "single_device": None,
    "expert2": dict(expert=2),
    # Two experts a chip can be sent every pair of a row: nothing to skip,
    # and the FFN is the plain einsum on the mesh.
    "expert4": dict(expert=4),
    "seq2_expert2": dict(seq=2, expert=2),
    "data2_expert2_tensor2": dict(data=2, expert=2, tensor=2),
}


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _mesh_context(axes):
    import contextlib

    from ray_tpu.parallel import MeshSpec

    if axes is None:
        return contextlib.nullcontext()
    return jax.set_mesh(MeshSpec(**axes).build())


def _ffn_case(counts, seed=0):
    """Buffers [E, B, C, D] whose occupied slots are the counts' prefixes
    (empty slots are zero rows, as the dispatch leaves them), their slots'
    gates [E, B, C] (zero in an empty slot), a cotangent that is zero where
    no pair is, as combine's is, and the weights."""
    from ray_tpu.models.mixtral import CONFIGS, _ffn_trips

    cfg = CONFIGS["mixtral-tiny"]
    E, K = FFN_EXPERTS, FFN_TOP_K
    D, F = cfg.hidden_size, cfg.intermediate_size
    C = int(4.0 * FFN_TOKENS * K / E)
    assert C == 4 * 512 and _ffn_trips(E, FFN_ROWS, C, FFN_TOKENS * K) == 30
    counts = np.asarray(counts, np.float32)  # [B, E], as expert_mask.sum(1)
    assert (counts.sum(1) == FFN_TOKENS * K).all() and counts.max() <= FFN_TOKENS
    rng = np.random.RandomState(seed)
    occupied = np.arange(C)[None, None] < counts.T[:, :, None]  # [E, B, C]
    x = rng.randn(E, FFN_ROWS, C, D).astype(np.float32) * occupied[..., None]
    g = rng.randn(E, FFN_ROWS, C, D).astype(np.float32) * occupied[..., None]
    gates = rng.rand(E, FFN_ROWS, C).astype(np.float32) * occupied
    weights = [
        (rng.randn(*shape) * 0.1).astype(np.float32)
        for shape in ((E, D, F), (E, D, F), (E, F, D))
    ]
    return x, gates, weights, g, jnp.asarray(counts), FFN_TOKENS * K


def expert_ffn_matches_the_plain_einsum(routing, mesh):
    """Values and all five gradients (x, the slots' gates, w_gate, w_up,
    w_down) against the plain einsum's rows times their gates: skipping the
    tiles past each prefix changes nothing, the gates' gradient taken
    on the other side of w_down is the one JAX takes through the rows, and
    the weights' gradients added up an expert at a time by the grouped
    matmul after the loop are the ones added up over all slots, on
    one device, on an expert-only mesh, with the rows shared out over seq,
    and with the experts' width split over a tensor axis. An expert that
    no pair reached has gradients of exact zeros."""
    from ray_tpu.models.mixtral import _swiglu, expert_ffn

    x, gates, weights, g, counts, pairs = _ffn_case(ROUTINGS[routing])

    def value_and_grads(ffn):
        def loss(x, gates, *w):
            return (ffn(x, gates, *w) * g).sum()

        return jax.jit(lambda x, gates, *w: (
            ffn(x, gates, *w),
            jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, gates, *w),
        ))(x, gates, *weights)

    with _mesh_context(FFN_MESHES[mesh]):
        want, want_grads = value_and_grads(
            lambda x, gates, *w: _swiglu(x, *w) * gates[..., None]
        )
        got, got_grads = value_and_grads(
            lambda x, gates, *w: expert_ffn(x, gates, *w, counts, pairs)
        )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(want_grads[1])).max() > 0
    for name, a, b in zip(
        ("x", "gates", "w_gate", "w_up", "w_down"), got_grads, want_grads
    ):
        scale = float(np.abs(b).max()) or 1.0
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5, err_msg=name
        )
    unreached = np.asarray(counts).sum(0) == 0
    for name, a in zip(("w_gate", "w_up", "w_down"), got_grads[2:]):
        assert not np.asarray(a)[unreached].any(), name
