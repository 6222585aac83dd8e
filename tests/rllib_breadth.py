"""What the five test_rllib_breadth_*.py files share. They are five
because ``--dist loadfile`` hands a file to one xdist worker, and the
learning tests of all of them together are a third of a whole run: no file
is to be over 150 s of one (``python -m tools.tier1_times``)."""
import pytest

import ray_tpu


@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()
