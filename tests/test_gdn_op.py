"""The scalar decay (``chunk_gdn``) of ``ray_tpu/ops/kda.py`` on the CPU: Gated
DeltaNet's road against its token-by-token recurrence, the chunked form and
the Pallas kernels interpreted, against the KDA road fed the decay broadcast
over channels, and fed by the convolution's heads-first output.

One of the six kernel families of ``ray_tpu/ops/kda.py``, a test file each
(ROADMAP C15's seams: the module's split moves one test file with each
family).

Where the road's operands lie and what it shares with KDA's is in
``tests/test_gdn_layout_op.py`` beside this file, over ``tests/gdn_cases.py``.
"""
import pytest

from gdn_cases import gdn_compare


@pytest.mark.parametrize("t,decay", [(64, 0.3), (100, 1e-3), (192, 30.0)],
                         ids=["64-0.3", "100-0.001", "192-30.0"])
def test_the_scalar_decay_chunked_form_and_its_vjp_are_the_recurrence(t, decay):
    """The XLA form (``lax.scan`` over ``_normed_gdn_chunk``), beta over (0,
    2), a weak and a strong decay, a length that is no whole number of chunks."""
    gdn_compare(t, decay)


@pytest.mark.parametrize("heads", [2, 3], ids=["pair", "odd"])
@pytest.mark.parametrize("t,decay", [(100, 0.3), (128, 1e-3), (128, 30.0)],
                         ids=["100-0.3", "128-0.001", "128-30.0"])
def test_the_scalar_decay_kernels_in_interpret_mode_are_the_recurrence(
        monkeypatch, t, decay, heads):
    """``_gdn_fwd_kernel`` and, under the ``custom_vjp``, ``_gdn_bwd_kernel``:
    forward and all seven cotangents, two heads a grid step and, where they do
    not pair off, one; heads of 24/48 lanes."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    gdn_compare(t, decay, heads)
