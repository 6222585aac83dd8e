"""What the one remat policy keeps of a sparse-attention layer
(``tests/remat_cases.py`` has the skeletons, the tables and the cases' bodies;
``tests/test_remat_residuals.py`` what the policy is).
"""
import jax
import jax.extend
import pytest

from ray_tpu.models.llama import REPLAY_KEEPS, remat_policy

from remat_cases import (  # noqa: F401 - fixtures
    LAYERS, _cfg, _interpret_mode, _run, replay_holds_no_forward_kernel,
)


@pytest.mark.parametrize("dropped", ["sparse_o", "sparse_lse"])
def test_a_sparse_layer_needs_both_of_its_kernels_names_kept(dropped):
    """As the causal kernels' o and lse: without either the replay runs
    ``_sparse_fwd_kernel`` again. (The chosen blocks are the mixer's to name,
    ``sparse_blocks`` in models/minicpm_sala.py: this skeleton chooses again.)"""
    names = [name for name in REPLAY_KEEPS if name != dropped]
    calls, _ = _run("sparse", jax.checkpoint_policies.save_only_these_names(*names))
    assert calls["_sparse_fwd_kernel"] == 2 * LAYERS
    kept, _ = _run("sparse", remat_policy(_cfg(remat_prevent_cse=True)))
    assert kept["_sparse_fwd_kernel"] == LAYERS
    assert kept["_bwd_dkv_sparse_kernel"] == kept["_bwd_dq_sparse_kernel"] == LAYERS


@pytest.mark.parametrize("case", ["sparse"])
def test_replay_holds_no_forward_kernel(case):
    replay_holds_no_forward_kernel(case)
