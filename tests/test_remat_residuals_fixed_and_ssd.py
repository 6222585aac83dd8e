"""What the one remat policy keeps of a fixed-decay (lightning) layer and a step-
scaled (Mamba-2) layer (``tests/remat_cases.py`` has the skeletons, the tables
and the cases' bodies; ``tests/test_remat_residuals.py`` what the policy is).
"""
import pytest

from remat_cases import (  # noqa: F401 - the fixture
    _interpret_mode, a_kda_layer_needs_each_of_its_three_names_kept,
    replay_holds_no_forward_kernel,
)


@pytest.mark.parametrize("case", ["lightning", "ssd"])
def test_replay_holds_no_forward_kernel(case):
    replay_holds_no_forward_kernel(case)


@pytest.mark.parametrize("dropped", ["lightning_o", "lightning_states", "ssd_y", "ssd_states"])
def test_a_kda_layer_needs_each_of_its_three_names_kept(dropped):
    a_kda_layer_needs_each_of_its_three_names_kept(dropped)
