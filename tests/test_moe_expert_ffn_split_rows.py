"""The expert FFN that skips the tiles past each prefix against the plain einsum,
values and all five gradients, where the rows are shared out over seq and
where the experts' width is split over a tensor axis (``tests/moe_cases.py``
has the routings and the body; ``tests/test_moe_expert_ffn.py`` runs it on one
device and on expert-only meshes).
"""
import pytest

from moe_cases import (  # noqa: F401 - the fixture
    ROUTINGS, expert_ffn_matches_the_plain_einsum, interpret,
)


@pytest.mark.parametrize("mesh", ["seq2_expert2", "data2_expert2_tensor2"])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_expert_ffn_matches_the_plain_einsum(routing, mesh, interpret):
    expert_ffn_matches_the_plain_einsum(routing, mesh)
