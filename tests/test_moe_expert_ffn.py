"""The expert FFN that skips the tiles past each prefix (``models/mixtral.py``
``expert_ffn``): which tiles it computes, its trips and its worklist, and
(below) its values and gradients against the plain einsum on one device and on
expert-only meshes (``tests/moe_cases.py`` has the routings and the body).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moe_cases import (  # noqa: F401 - the fixture
    ROUTINGS, _ffn_case, expert_ffn_matches_the_plain_einsum, interpret,
)


@pytest.mark.parametrize("routing", ROUTINGS)
def test_expert_ffn_computes_the_tiles_its_prefixes_reach(routing, interpret):
    """Which slots the FFN computes: rows of ones in every slot, against the
    invariant, come back non-zero from every 512-slot tile that the prefix
    of its own (expert, row) reaches and from as many others as make up the
    trips: the same number of tiles whatever the routing."""
    from ray_tpu.models.mixtral import _ffn_trips, expert_ffn

    x, gates, weights, _, counts, pairs = _ffn_case(ROUTINGS[routing])
    out = jax.jit(expert_ffn, static_argnums=6)(
        np.ones_like(x), np.ones_like(gates), *weights, counts, pairs
    )
    E, B, C, _ = x.shape
    computed = np.abs(np.asarray(out)).reshape(E, B, C // 512, 512, -1).any(
        axis=(3, 4)
    )
    reached = np.arange(C // 512) < -(-np.asarray(counts, int).T // 512)[..., None]
    assert computed[reached].all()
    assert reached.sum() <= computed.sum() == _ffn_trips(E, B, C, pairs) == 30


@pytest.mark.parametrize("shape, trips", [
    # The MoE cell's chip: four of eight experts, one row of 4,096 tokens,
    # top-2, factor 4.0. Its 8,192 pairs fill 16 tiles and can end in three
    # more (2,176 + 2,176 + 2,176 + 1,664: 5 + 5 + 5 + 4).
    ((4, 1, 4096, 8192), 19),
    ((8, 2, 4096, 8192), 2 * 23),  # the same layer on one device
    ((2, 2, 2048, 4096), 0),  # two experts can be sent every pair: all tiles
    ((8, 1, 1280, 8192), 0),  # factor 1.25: C is not whole tiles
    ((8, 1, 1536, 8192), 0),  # factor 1.5: 23 of 24 tiles can be reached
    ((4, 1, 512, 8192), 0),  # one tile a buffer
    # Pairs that are not whole tiles: 513 + 1 + 1 of them reach all four
    # trips of the bound and leave the fourth expert without one.
    ((4, 1, 1024, 515), 0),
])
def test_ffn_trips_are_the_tiles_that_can_hold_a_pair(shape, trips):
    from ray_tpu.models.mixtral import _ffn_trips

    assert _ffn_trips(*shape) == trips


def _routings(experts, rows, C, pairs, rng, n):
    """``n`` counts [experts, rows] of a device's share of each row's pairs:
    some of the experts, chosen anew each time, hold prefixes that end just
    inside a tile; the worst for the tiles reached."""
    for _ in range(n):
        counts = np.zeros((experts, rows), int)
        for row in range(rows):
            some = rng.permutation(experts)[: rng.randint(0, experts + 1)]
            left = rng.randint(0, pairs + 1)
            for e in some:
                counts[e, row] = took = min(
                    left, C, rng.randint(0, C // 512 + 1) * 512 + 1
                )
                left -= took
        yield counts


@pytest.mark.parametrize("shape", [
    (4, 1, 4096, 8192), (8, 2, 4096, 8192), (2, 2, 2048, 4096),
    (8, 1, 1280, 8192), (8, 1, 1536, 8192), (4, 1, 512, 8192),
])
def test_worklist_keeps_an_experts_trips_together_and_leaves_no_expert_out(shape):
    """What the weights' gradients need of the trips, on the shapes the
    bound is tested on: every reached tile among them and none twice, in
    (expert, row, slot) order so that an expert's trips are consecutive,
    and at least one trip in every expert, the ones no pair reaches too.
    Where the FFN is the plain einsum the trips asked for are all tiles."""
    from ray_tpu.models.mixtral import _ffn_trips, _worklist

    experts, rows, C, pairs = shape
    per = -(-C // 512)
    trips = _ffn_trips(*shape) or experts * rows * per
    worklist = jax.jit(_worklist, static_argnums=(1, 2))
    rng = np.random.RandomState(experts * rows + C)
    for counts in _routings(experts, rows, C, pairs, rng, 40):
        tiles = -(-counts // 512)
        e, b, slot = (np.asarray(i) for i in worklist(jnp.asarray(tiles), per, trips))
        flat = (e * rows + b) * per + slot // 512
        assert len(flat) == trips and (np.diff(flat) > 0).all(), (counts, flat)
        assert set(e) == set(range(experts)), (counts, e)
        reached = {
            (x * rows + y) * per + z
            for x in range(experts) for y in range(rows)
            for z in range(tiles[x, y])
        }
        assert reached <= set(flat), (counts, flat)


def test_ffn_trips_cover_the_worst_routing():
    """No routing reaches more tiles than the trips: over random splits of
    a row's pairs among a chip's experts, with prefixes made to end just
    inside a tile, the tiles reached stay within the bound, and the worst
    found meets it."""
    from ray_tpu.models.mixtral import _ffn_trips

    experts, C, pairs = 4, 4096, 8192
    bound = _ffn_trips(experts, 1, C, pairs)
    rng = np.random.RandomState(0)
    most = 0
    for _ in range(2000):
        cuts = np.sort(rng.randint(0, pairs // 128 + 1, experts - 1)) * 128
        counts = np.minimum(np.diff([0, *cuts, pairs]), C)
        most = max(most, int((-(-counts // 512)).sum()))
    assert most == bound == 19


@pytest.mark.parametrize("mesh", ["single_device", "expert2", "expert4"])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_expert_ffn_matches_the_plain_einsum(routing, mesh, interpret):
    expert_ffn_matches_the_plain_einsum(routing, mesh)
