"""A causal tile's class (interior, edge, dead) from the shapes alone, against a
brute-force mask and at the benchmark's cells: no kernel runs
(``tests/flash_cases.py`` has the tilings).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from flash_cases import TILINGS, _interpret_mode  # noqa: F401 - fixtures


# the benchmark's cells (ISSUE 46): (interior, edge, dead) a head
CELLS = [
    ((16384, 16384, 1024, 1024, True), (120, 16, 120)),  # long16k, Laguna's full layers
    ((4096, 4096, 1024, 1024, True), (6, 4, 6)),    # pretrain-4k, -mtp-4k, dropless-4k
    ((2048, 2048, 1024, 1024, True), (1, 2, 1)),    # short2k
    ((512, 512, 1024, 1024, True), (0, 1, 0)),      # sft512
    ((2048, 2048, 512, 512, True), (6, 4, 6)),      # the ring's diagonal block
    ((2048, 2048, 512, 512, False), (16, 0, 0)),    # each rotated one
]


def _brute_force(tq, tk, bq, bk, causal):
    """Every tile's class from the [tq, tk] mask itself."""
    from ray_tpu.ops.attention import _visible

    seen = np.asarray(_visible(tq, tk, None)) if causal else np.ones((tq, tk), bool)
    bq, bk = min(bq, tq), min(bk, tk)
    classes = {}
    for i in range(-(-tq // bq)):
        for j in range(-(-tk // bk)):
            tile = seen[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            whole = (j + 1) * bk <= tk  # no padded key
            classes[i, j] = ("interior" if whole and tile.all()
                             else "edge" if tile.any() else "dead")
    return classes


@pytest.mark.parametrize("tq,tk,bq,bk,causal", TILINGS)
def test_causal_tiles_match_a_brute_force_mask(tq, tk, bq, bk, causal):
    from ray_tpu.ops import attention

    classes = _brute_force(tq, tk, bq, bk, causal)
    count = lambda c: sum(v == c for v in classes.values())  # noqa: E731
    assert attention.causal_tiles(tq, tk, bq, bk, causal) == (
        count("interior"), count("edge"), count("dead"))
    # the index maps' clamps name the row's last and the column's first
    # live block: a dead step's copy is the one already there
    tile = dict(causal=causal, block_q=min(bq, tq), block_k=min(bk, tk),
                seq_q=tq, seq_k=tk)
    rows = sorted({i for i, _ in classes})
    cols = sorted({j for _, j in classes})
    for i in rows:
        live = [j for j in cols if classes[i, j] != "dead"]
        assert int(attention._last_live_key(jnp.int32(i), **tile)) == live[-1]
        assert live == cols[:len(live)]  # a prefix: the clamp skips no live tile
    for j in cols:
        live = [i for i in rows if classes[i, j] != "dead"]
        assert int(attention._first_live_row(jnp.int32(j), **tile)) == live[0]
        assert live == rows[-len(live):]


@pytest.mark.parametrize("shape,want", CELLS)
def test_causal_tiles_of_the_benchmarks_cells(shape, want):
    from ray_tpu.ops.attention import causal_tiles

    assert causal_tiles(*shape) == want
