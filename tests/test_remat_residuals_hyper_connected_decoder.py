"""What the one remat policy keeps of a hyper-connected decoder layer's
projections (``tests/remat_cases.py`` has the skeletons, the tables and the
cases' bodies; ``tests/test_remat_residuals_hyper_connections.py`` the
connections' own kernels; ``tests/test_remat_residuals.py`` what the policy
is).
"""
import pytest

from remat_cases import (  # noqa: F401 - the fixture
    _interpret_mode, replay_holds_no_matmul_for_an_elementwise_consumer,
)


@pytest.mark.parametrize("case", ["hyper-connections"])
def test_replay_holds_no_matmul_for_an_elementwise_consumer(case):
    replay_holds_no_matmul_for_an_elementwise_consumer(case)
