"""What the one remat policy keeps of a hyper-connected layer
(``tests/remat_cases.py`` has the skeletons, the tables and the cases' bodies;
``tests/test_remat_residuals.py`` what the policy is).
"""
import pytest

from remat_cases import (  # noqa: F401 - the fixture
    _interpret_mode, replay_holds_no_forward_kernel,
)


@pytest.mark.parametrize("case", ["hyper-connections"])
def test_replay_holds_no_forward_kernel(case):
    replay_holds_no_forward_kernel(case)
