"""The rotary kernel and its pass back compile ahead of time for a v5e chip, with
no chip (``tests/aot_v5e.py`` has how; ``tests/test_kernels_aot_v5e.py`` the
flash kernels).
"""
import jax.numpy as jnp
import pytest

from ray_tpu.ops import rotary

from aot_v5e import _compile_for, topo, v5e  # noqa: F401 - fixtures


# q's and k's rotation as one pass (``ops/rotary.py``), heads first in and
# out, and the pass back, which is the same kernel against the tables with
# the sines' sign turned: the Laguna cell's sliding layer (b1 x s16384, q at
# 64 heads and k at 8, the whole head), its full layer (q at 48, the leading
# half under YaRN's amplitude: two lane rotations against three tables) and
# the MiniCPM-SALA cell's Lightning layer (q and k at 32), at the blocks
# ``rotate`` gives them.
@pytest.mark.parametrize("heads,half,leading,amplitude", [
    (64, 64, True, 1.0), (8, 64, True, 1.0), (48, 32, True, 1.4158883),
    (32, 64, False, 1.0),
], ids=["laguna_swa_q", "laguna_swa_k", "laguna_attn_q", "lightning_q_and_k"])
def test_rotary_kernel_and_its_pass_back_compile_for_v5e(v5e, heads, half, leading, amplitude):
    from benchmarks.lib import trace

    shape = (1, heads, 16384, 128)
    turn = rotary._Turn(leading, amplitude, *rotary._blocks(shape), False)
    assert turn[2:4] == (rotary.ROWS, min(heads, rotary.HEADS))
    for back in (False, True):
        text = _compile_for(
            v5e, lambda x, p, f: rotary._turned(x, p, f, turn, back),  # noqa: B023
            (shape, jnp.bfloat16), ((1, 16384), jnp.int32), ((half,), jnp.float32))
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert [trace.kernel_name(line) for line in calls] == ["_rotary_kernel"]
        # the whole of x in and out as it lies: no copy, no transposition
        assert " copy(" not in text and " transpose(" not in text
