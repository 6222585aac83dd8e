"""The hyper-connections' kernels compile ahead of time for a v5e chip, with no
chip (``tests/aot_v5e.py`` has how; ``tests/test_kernels_aot_v5e.py`` the
flash kernels).
"""
import jax.numpy as jnp
import pytest

from aot_v5e import _compile_for, topo, v5e  # noqa: F401 - fixtures


# One of Xing4's hyper-connections at the benchmark's real size: four streams
# of b1 x s4096 tokens at the published 3584 channels.
HC_STREAMS = ((4, 1, 4096, 3584), jnp.bfloat16)
HC_ONE = ((1, 4096, 3584), jnp.bfloat16)
HC_MAPS = {k: ((k, 1, 4096), jnp.float32) for k in (4, 24)}


def hc_entries():
    from ray_tpu.models import hyper_connections as hcs

    phi = ((4 * 3584, 24), jnp.bfloat16)
    return {
        "_hc_pre_fwd_kernel": (
            lambda x, phi, alpha, b: hcs._pre_fwd(x, phi, alpha, b, 1e-6),
            HC_STREAMS, phi, ((), jnp.float32), ((4,), jnp.float32)),
        "_hc_post_fwd_kernel": (
            hcs._post_fwd, HC_STREAMS, HC_ONE, HC_MAPS[4], ((4, 4, 1, 4096), jnp.float32)),
        "_hc_post_bwd_kernel": (
            hcs._post_bwd, HC_STREAMS, HC_STREAMS, HC_ONE, HC_MAPS[4],
            ((4, 4, 1, 4096), jnp.float32)),
        "_hc_pre_sums_kernel": (hcs._pre_sums, HC_ONE, HC_STREAMS),
        "_hc_pre_bwd_kernel": (
            hcs._pre_bwd, HC_STREAMS, HC_STREAMS, HC_ONE, HC_MAPS[4],
            ((1, 4096), jnp.float32), ((48, 1, 4096), jnp.bfloat16),
            ((4, 3584, 24), jnp.bfloat16)),
    }


@pytest.mark.parametrize("kernel", [
    "_hc_pre_fwd_kernel", "_hc_post_fwd_kernel", "_hc_post_bwd_kernel", "_hc_pre_sums_kernel", "_hc_pre_bwd_kernel"])
def test_a_hyper_connections_kernel_compiles_for_v5e(v5e, kernel):
    fn, *args = hc_entries()[kernel]
    text = _compile_for(v5e, fn, *args)
    # the streams' cotangent is written over the one that came down to it
    if kernel == "_hc_pre_bwd_kernel":
        assert "output_to_operand_aliasing={{}: (0, {})}" in text
