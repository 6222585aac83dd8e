"""What the three test_rllib_breadth_*.py files share. They are three
because ``--dist loadfile`` hands a file to one xdist worker, and the
learning tests of all of them together are a third of a whole run."""
import pytest

import ray_tpu


@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()
