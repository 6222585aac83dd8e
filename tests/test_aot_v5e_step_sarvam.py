"""The sarvam cell's train step at its real size, lowered ahead of time for a v5e
chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import pytest

from aot_v5e import _lowered_step, topo, v5e  # noqa: F401 - fixtures


@pytest.fixture(scope="module")
def sarvams_step(v5e):
    return _lowered_step(v5e, "sarvam-105b-l5.pretrain-4k")


def test_sarvams_step_takes_q_and_k_to_the_flash_kernels_by_the_latent_kernels(sarvams_step):
    """Five layers: a forward body and the replay's copy of it behind each
    forward entry, one behind each backward entry; q's and k's pass a layer
    forward, replayed and backward. Nothing of the XLA road is left: no
    [., 64, 4096, 192] array is concatenated (``_rope``'s two and k's
    assembly were 30 in the parent's text), the shared key part is broadcast
    to no [1, 4096, 64, 64], and no ``_rope`` product stands in float32."""
    import re

    from benchmarks.lib import checks

    _, text = sarvams_step
    bodies = checks.count_pallas_kernels(text, (
        "_latent_q_kernel", "_latent_k_kernel", "_latent_q_back_kernel",
        "_latent_k_back_kernel"))
    assert bodies == {"_latent_q_kernel": 2, "_latent_k_kernel": 2,
                      "_latent_q_back_kernel": 1, "_latent_k_back_kernel": 1}
    calls = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text)) for entry in (
        "_latent_q_forward", "_latent_k_forward", "_latent_q_backward", "_latent_k_backward")}
    assert calls == {"_latent_q_forward": 10, "_latent_k_forward": 10,
                     "_latent_q_backward": 5, "_latent_k_backward": 5}
    assert not re.findall(r"stablehlo\.concatenate.*(1x64x4096x192|4096x64x192)x", text)
    assert "tensor<1x4096x64x64xbf16>" not in text
    assert "tensor<1x64x4096x32xf32>" not in text
