"""The grouped matmul's grids and numerics, on the CPU.

What a call moves across HBM is static: Pallas fetches an operand's block
when the block's index differs from the step before, and writes an output
block back when its index moves on. So the blocks a call fetches are
counted here by walking the grid in execution order (last dimension
fastest) with the index maps the kernels are built from
(``_gmm_grid`` / ``_tgmm_grid``), for the benchmark's OLMoE cell and for
``chip_smoke.py``'s shapes, on a seeded routing. The numerics cases run the
kernels in interpret mode against ``jax.lax.ragged_dot`` in float32.
"""
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gmm as G

BF16 = 2

# (experts, (token, expert) pairs, hidden, expert width): the OLMoE cell,
# b2 x s4096 x top-8 over 64 experts; chip_smoke.py, b2 x s2048 x top-2 of 8.
OLMOE = (64, 2 * 4096 * 8, 2048, 1024)
SMOKE = (8, 2 * 2048 * 2, 1024, 3584)
# Which of a layer's calls: [rows, k] x [experts, k, n].
CALLS = {
    "olmoe-gate_up": (*OLMOE[:2], OLMOE[2], OLMOE[3]),
    "olmoe-down": (*OLMOE[:2], OLMOE[3], OLMOE[2]),
    "smoke-gate_up": (*SMOKE[:2], SMOKE[2], SMOKE[3]),
    "smoke-down": (*SMOKE[:2], SMOKE[3], SMOKE[2]),
}


def _tile_group(experts, pairs, seed=0):
    e_flat = np.random.default_rng(seed).integers(0, experts, pairs)
    _, _, tile_group, m_pad = G.aligned_group_layout(
        jnp.asarray(e_flat, jnp.int32), experts
    )
    return np.asarray(tile_group), m_pad


def _runs(tile_group):
    """Maximal stretches of consecutive tiles of one expert."""
    return 1 + int(np.count_nonzero(np.diff(tile_group)))


def _blocks_moved(grid, spec, tile_group):
    """How often the block index of `spec` changes along the grid's order:
    the fetches of an input, the write-backs of an output."""
    moved, last = 0, None
    for pos in itertools.product(*map(range, grid)):
        index = tuple(int(i) for i in spec.index_map(*pos, tile_group))
        moved += index != last
        last = index
    return moved


def _bytes_moved(grid, specs, tile_group):
    return [
        _blocks_moved(grid, spec, tile_group)
        * math.prod(spec.block_shape) * BF16
        for spec in specs
    ]


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["forward", "input_gradient"])
@pytest.mark.parametrize("call", CALLS)
def test_gmm_fetches_a_weight_block_once_for_an_experts_consecutive_tiles(
    call, transpose_rhs
):
    experts, pairs, k, n = CALLS[call]
    tile_group, m = _tile_group(experts, pairs)
    if transpose_rhs:  # cotangent [m, n] against rhs [e, k, n] -> [m, k]
        k, n = n, k
    block_n = G._gmm_block_n(k, n, BF16)
    grid, (lhs_spec, rhs_spec), out_spec = G._gmm_grid(
        m, k, n, 128, block_n, transpose_rhs
    )
    n_blocks = n // block_n
    assert grid == (n_blocks, m // 128)

    fetches = _blocks_moved(grid, rhs_spec, tile_group)
    assert fetches <= _runs(tile_group) * n_blocks

    lhs_b, rhs_b, out_b = _bytes_moved(
        grid, (lhs_spec, rhs_spec, out_spec), tile_group
    )
    once = (m * (k + n) + experts * k * n) * BF16
    assert rhs_b <= experts * k * n * BF16 * 1.15  # an empty expert's tile
    assert lhs_b + rhs_b + out_b <= 1.3 * once
    if call.startswith("olmoe"):
        # The parent moved 2.4 GB of weights alone in such a call.
        assert lhs_b + rhs_b + out_b <= 1.5e9


@pytest.mark.parametrize("call", CALLS)
def test_tgmm_reads_its_inputs_at_most_twice_and_writes_each_block_once(call):
    experts, pairs, k, n = CALLS[call]
    tile_group, m = _tile_group(experts, pairs)
    block_k, block_n = G._tgmm_blocks(k, n, BF16)
    grid, in_specs, out_spec = G._tgmm_grid(m, k, n, 128, block_k, block_n)
    assert grid == (k // block_k, n // block_n, m // 128)

    # Every expert has a tile, so each of its blocks is written, and once.
    writes = _blocks_moved(grid, out_spec, tile_group)
    assert writes == _runs(tile_group) * grid[0] * grid[1]
    assert _runs(tile_group) == experts

    lhs_b, dout_b, out_b = _bytes_moved(
        grid, (*in_specs, out_spec), tile_group
    )
    assert lhs_b <= 2 * m * k * BF16 and dout_b <= 2 * m * n * BF16
    assert out_b == experts * k * n * BF16
    if call.startswith("olmoe"):
        # The parent's (512, 512) blocks moved 1.47 GB in such a call.
        assert lhs_b + dout_b + out_b <= 1.0e9


# The capacity FFN's weight gradients (models/mixtral.py _tiles_backward), one
# chip of the Mixtral cell: four experts of 4096 x 14336, 19 trips of 512 rows.
MIXTRAL = {"mixtral-gate_up": (4096, 14336), "mixtral-down": (14336, 4096)}


@pytest.mark.parametrize("call", MIXTRAL)
def test_tgmm_over_the_capacity_ffns_trips_writes_each_experts_block_once(call):
    """The trips of a seeded routing as ``_worklist`` orders them: an
    expert's are consecutive and none is without one, so each of the 14
    (2048, 2048) blocks of each expert's gradient is written once, and the
    stacked operands are read once a block of the other side."""
    from ray_tpu.models.mixtral import _ffn_trips, _worklist

    experts, rows, C, pairs = 4, 1, 4096, 8192
    k, n = MIXTRAL[call]
    trips = _ffn_trips(experts, rows, C, pairs)
    counts = np.asarray([[2100], [0], [3000], [513]])  # an expert's pairs
    tile_group = np.asarray(
        _worklist(jnp.asarray(-(-counts // 512)), C // 512, trips)[0]
    )
    assert trips == 19 and _runs(tile_group) == experts
    m = trips * 512
    block_k, block_n = G._tgmm_blocks(k, n, BF16, 512)
    assert (block_k, block_n) == (2048, 2048)
    grid, in_specs, out_spec = G._tgmm_grid(m, k, n, 512, block_k, block_n)
    assert grid == (k // 2048, n // 2048, trips)
    assert _blocks_moved(grid, out_spec, tile_group) == experts * 14
    lhs_b, dout_b, out_b = _bytes_moved(grid, (*in_specs, out_spec), tile_group)
    assert lhs_b == grid[1] * m * k * BF16 and dout_b == grid[0] * m * n * BF16
    assert out_b == experts * k * n * BF16
    # A grid step's matmul against the bytes it fetches: on the MXU's side of
    # a v5e's 240 FLOPs a byte.
    assert 2 * block_k * block_n / ((block_k + block_n) * BF16) > 240


@pytest.mark.parametrize("k,n,block_m", [
    (2048, 1024, 128), (1024, 3584, 128), (4096, 14336, 128), (96, 40, 128),
    (4096, 14336, 512), (14336, 4096, 512),
])
def test_block_rules_keep_to_the_budget_and_divide_the_shape(k, n, block_m):
    block_n = G._gmm_block_n(k, n, BF16, block_m)
    assert n % block_n == 0 and (block_n == n or block_n % 128 == 0)
    assert (
        2 * (block_m * k + k * block_n + block_m * block_n) * BF16
        + 4 * block_m * block_n <= G._BLOCK_BUDGET
    )
    block_k, block_n = G._tgmm_blocks(k, n, BF16, block_m)
    assert k % block_k == 0 and n % block_n == 0
    assert block_k == k or block_k % 128 == 0
    assert block_n == n or block_n % 128 == 0
    assert (
        (4 + 2 * BF16) * block_k * block_n
        + 2 * block_m * (block_k + block_n) * BF16
        <= G._BLOCK_BUDGET < G._VMEM_LIMIT
    )


# ------------------------------------------------------------------ numerics


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _sizes(case):
    """Rows each expert holds, before padding to 128-row tiles."""
    if case == "an_expert_with_no_row":
        return [200, 0, 128, 77]
    if case == "an_expert_of_exactly_one_tile":
        return [128, 300, 128, 1]
    return [130, 250, 5, 128, 129, 256, 90, 384]  # eight experts


@pytest.mark.parametrize("budget", ["whole_blocks", "split_blocks"])
@pytest.mark.parametrize("case", [
    "an_expert_with_no_row", "an_expert_of_exactly_one_tile", "eight_experts",
])
def test_gmm_and_both_gradients_against_ragged_dot(
    case, budget, interpret, monkeypatch
):
    k, n = 256, 384
    if budget == "split_blocks":
        # Room for one 128-wide block of the weights and no more, so that
        # every grid has several blocks a side.
        monkeypatch.setattr(G, "_BLOCK_BUDGET", 2**19)
        assert G._gmm_block_n(k, n, 4) == 128 == G._gmm_block_n(n, k, 4)
        assert G._tgmm_blocks(k, n, 4) == (128, 128)
    sizes = np.asarray(_sizes(case))
    experts = len(sizes)
    e_flat = jnp.asarray(np.repeat(np.arange(experts), sizes), jnp.int32)
    _, dst, tile_group, m_pad = G.aligned_group_layout(e_flat, experts)
    tiles = np.bincount(np.asarray(tile_group), minlength=experts)
    assert (tiles >= np.maximum(-(-sizes // 128), 1)).all()
    if case == "an_expert_of_exactly_one_tile":
        assert tiles[0] == 1 and sizes[0] == 128

    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((sizes.sum(), k)), jnp.float32)
    # Padding rows hold zeros, as the layer's gather leaves them.
    lhs = jnp.zeros((m_pad, k), jnp.float32).at[dst].set(rows)
    rhs = jnp.asarray(rng.standard_normal((experts, k, n)) / 16, jnp.float32)
    ct = jnp.asarray(rng.standard_normal((m_pad, n)), jnp.float32)

    def oracle(lhs, rhs):
        with jax.default_matmul_precision("highest"):
            return jax.lax.ragged_dot(
                lhs, rhs, jnp.asarray(tiles * 128, jnp.int32)
            )

    out, pull = jax.vjp(lambda l, r: G.gmm(l, r, tile_group), lhs, rhs)
    want, want_pull = jax.vjp(oracle, lhs, rhs)
    for name, a, b in zip(("out", "d_lhs", "d_rhs"),
                          (out, *pull(ct)), (want, *want_pull(ct))):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)
    # An expert no row chose gets a gradient of zeros, not what the
    # output buffer held.
    d_rhs = np.asarray(pull(ct)[1])
    for e in np.flatnonzero(sizes == 0):
        assert (d_rhs[e] == 0).all()


def test_gmm_in_bfloat16_accumulates_in_float32(interpret):
    """bf16 operands, float32 accumulation, one cast at the end: within
    half a unit of bfloat16's last place of the float32 result."""
    experts, k, n = 8, 512, 256
    e_flat = jnp.asarray(
        np.random.default_rng(1).integers(0, experts, 1500), jnp.int32
    )
    _, dst, tile_group, m_pad = G.aligned_group_layout(e_flat, experts)
    rng = np.random.default_rng(2)
    lhs = jnp.zeros((m_pad, k), jnp.bfloat16).at[dst].set(
        jnp.asarray(rng.standard_normal((1500, k)), jnp.bfloat16)
    )
    rhs = jnp.asarray(rng.standard_normal((experts, k, n)) / 16, jnp.bfloat16)
    ct = jnp.asarray(rng.standard_normal((m_pad, n)), jnp.bfloat16)
    sizes = jnp.asarray(
        np.bincount(np.asarray(tile_group), minlength=experts) * 128, jnp.int32
    )

    def oracle(lhs, rhs):
        with jax.default_matmul_precision("highest"):
            return jax.lax.ragged_dot(lhs, rhs, sizes)

    f32 = jnp.float32
    out, pull = jax.vjp(lambda l, r: G.gmm(l, r, tile_group), lhs, rhs)
    want, want_pull = jax.vjp(oracle, lhs.astype(f32), rhs.astype(f32))
    for name, a, b in zip(("out", "d_lhs", "d_rhs"), (out, *pull(ct)),
                          (want, *want_pull(ct.astype(f32)))):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            a.astype(f32), b, rtol=2.0 ** -8, atol=2.0 ** -8 * np.abs(b).max(),
            err_msg=name,
        )


# ------------------------------------------- a held share's rows, back to tokens


def _pairs_summed_by_a_gather(rows, slot_of_pair, gates=None):
    """What ``pairs_summed`` replaced in ``models/mixtral.py`` (PR 70), kept
    as its reference: one XLA gather over every pair, a pair that is not here
    reading zeros by its index."""
    pairs = rows.at[slot_of_pair].get(mode="fill", fill_value=0)
    pairs = pairs.astype(jnp.float32)
    if gates is not None:
        pairs = pairs * gates[..., None]
    return pairs.sum(1).astype(rows.dtype)


def _held_pairs(s, k, share, m_pad, used, seed):
    """``slot_of_pair`` [s, k] of a routing that holds ``share`` of the pairs
    in distinct slots under ``used``, ``m_pad`` for a pair that is not here;
    token 0 has no pair here, token 1 its first alone, token 2 all k."""
    rng = np.random.default_rng(seed)
    here = rng.random((s, k)) < share
    here[0], here[1], here[2] = False, [True] + [False] * (k - 1), True
    slots = np.full((s, k), m_pad, np.int32)
    slots[here] = rng.permutation(used)[: here.sum()]
    return slots


# (tokens, top-k, width, share of the pairs here); the blocks a grid step is
# cut to: the rule's, or tiles of 16 tokens that do not divide the tokens over
# a buffer of two halves of 4 reads, which a tile of 64 or 128 pairs fills
# several times.
PAIRS_CASES = [
    (40, 4, 32, 0.25), (40, 8, 128, 1 / 32), (100, 8, 256, 0.125),
    (72, 4, 128, 1.0), (64, 8, 128, 0.0),
]


@pytest.mark.parametrize("gated", [True, False], ids=["gates", "no_gates"])
@pytest.mark.parametrize("blocks", ["the_rule's", "small"])
@pytest.mark.parametrize("s,k,d,share", PAIRS_CASES)
def test_pairs_summed_is_the_gather_it_replaced(
    s, k, d, share, blocks, gated, interpret, monkeypatch
):
    if blocks == "small":
        monkeypatch.setattr(G, "_pairs_summed_blocks", lambda k, d, size: (16, 4))
    m_pad, used = 1024, 768
    slots = _held_pairs(s, k, share, m_pad, used, seed=s + k)
    if share == 0.0:
        slots[:] = m_pad
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.standard_normal((m_pad, d)), jnp.bfloat16)
    gates = jnp.asarray(rng.random((s, k)), jnp.bfloat16) if gated else None
    got = G.pairs_summed(rows, jnp.asarray(slots), gates)
    want = _pairs_summed_by_a_gather(rows, jnp.asarray(slots), gates)
    assert got.shape == (s, d) and got.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert (got[0] == 0).all()
    alone = (slots < m_pad).sum(1) <= 1
    np.testing.assert_array_equal(got[alone], want[alone])
    # A sum of several rounds once either way; the orders of addition may
    # differ by the float32 sum's last place, one of bfloat16's at most.
    assert (np.abs(got - want) <= np.abs(want) * 2.0 ** -7).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_pairs_summed_takes_nothing_from_a_row_no_pair_names(dtype, interpret, monkeypatch):
    """Every row that no present pair names, and every row past the used
    tiles, is NaN (past the used tiles a layout is uninitialised memory,
    ``unwritten``): the result is finite and is the gather's."""
    monkeypatch.setattr(G, "_pairs_summed_blocks", lambda k, d, size: (16, 4))
    s, k, d, m_pad, used = 48, 4, 128, 512, 256
    slots = _held_pairs(s, k, 0.25, m_pad, used, seed=11)
    rng = np.random.default_rng(6)
    clean = rng.standard_normal((m_pad, d)).astype(np.float32)
    named = np.zeros(m_pad, bool)
    named[slots[slots < m_pad]] = True
    rows = jnp.asarray(np.where(named[:, None], clean, np.nan), dtype)
    gates = jnp.asarray(rng.random((s, k)), dtype)
    got = np.asarray(G.pairs_summed(rows, jnp.asarray(slots), gates), np.float32)
    assert np.isfinite(got).all()
    want = _pairs_summed_by_a_gather(
        jnp.asarray(clean, dtype), jnp.asarray(slots), gates
    )
    np.testing.assert_allclose(
        got, np.asarray(want, np.float32), rtol=2.0 ** -7, atol=1e-6
    )


@pytest.mark.parametrize("k,d", [(8, 5120), (8, 2048), (8, 4096), (4, 3584)],
                         ids=["dots3", "laguna", "solar", "xing4"])
def test_pairs_summed_blocks_keep_to_the_budget(k, d):
    tokens, reads = G._pairs_summed_blocks(k, d, BF16)
    held = (tokens + G._ROWS_A_READ) * d * 4 + tokens * d * 2 * BF16
    held += 2 * reads * G._ROWS_A_READ * d * BF16
    assert held <= G._BLOCK_BUDGET and tokens % G._A_TRIP == 0
    assert G._A_TRIP <= reads <= tokens * k // 2 and reads % G._A_TRIP == 0
