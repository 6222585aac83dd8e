"""The gated delta rule of ``ray_tpu/ops/kda.py`` on the CPU: the Pallas scan
kernels in interpret mode against the recurrence, two heads a grid step and
one, the write strength over (0, 1) and (0, 2); the state they carry; the
bfloat16 matmuls' distance from float32 (``tests/test_kda_op.py`` says what
the rule is held to and names the family's files; ``tests/kda_recurrence.py``
has the recurrence and the comparison).
"""
import pytest

from kda_recurrence import compare


@pytest.mark.parametrize("heads", [2, 3], ids=["pair", "odd"])
@pytest.mark.parametrize("t,decay,beta_max", [
    (100, 0.3, 1.0), (256, 1e-3, 1.0), (192, 30.0, 1.0),
    # beta = 2 sigmoid, over (0, 2): a weak decay, where the chunk's system
    # is furthest from the identity, and a length with a padded chunk
    (256, 1e-3, 2.0), (100, 0.3, 2.0),
], ids=["100-0.3", "256-0.001", "192-30.0", "256-0.001-beta<2", "100-0.3-beta<2"])
def test_pallas_kernels_in_interpret_mode_are_the_recurrence(
        monkeypatch, t, decay, beta_max, heads):
    """The forward kernel and, under its ``custom_vjp``, the backward kernel
    that differentiates ``_head_chunk`` where it stands: two heads a grid
    step, and three heads one a step; the write strength in (0, 1) and in
    (0, 2)."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    compare(t, decay, heads, beta_max=beta_max)
