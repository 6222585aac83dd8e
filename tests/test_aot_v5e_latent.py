"""Latent attention's q and k kernels compile ahead of time for a v5e chip, with
no chip (``tests/aot_v5e.py`` has how; ``tests/test_kernels_aot_v5e.py`` the
flash kernels).
"""
import jax.numpy as jnp
import pytest

from ray_tpu.ops import rotary

from aot_v5e import _compile_for, topo, v5e  # noqa: F401 - fixtures


# Latent attention's q and k from the projections to the flash kernels in one
# pass each and the two passes back (``ops/rotary.py`` ``latent_qkv``) at the
# cells' real sizes (b1 x s4096): sarvam's 64 heads under the per-head norm and
# the rotation, Xing4's 32 under the rotation alone, and the norm alone (what
# ``benchmarks/tools/wrong_sarvam.py``'s program without the rotation runs); q [1, H, 4096, 128 | 64],
# kv [1, H, 4096, 128 | 128], the shared key part [1, 4096, 64]. Each kernel
# reads its own name as a profile's reader names it.
@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("heads,eps,turns", [(64, 1e-6, True), (32, None, True), (64, 1e-6, False)],
                         ids=["sarvam", "xing4", "the_norm_alone"])
def test_latent_kernels_compile_for_v5e(v5e, heads, eps, turns, direction):
    from benchmarks.lib import trace

    t = 4096
    q, kv, v = ((1, heads, t, lanes) for lanes in (192, 256, 128))
    fuse = rotary._Fuse(eps, turns, *rotary._blocks(q), False)
    assert fuse[2:4] == (rotary.ROWS, rotary.HEADS)
    bf16 = jnp.bfloat16
    # positions and, where the layer turns, the table (None for a layer that does not)
    table = [((1, t), jnp.int32), ((32,), jnp.float32)][:1 + turns]
    weight = [((192,), jnp.float32)] if eps else []
    shared = ((1, t, 64), bf16)
    if direction == "forward":
        entries = {"_latent_q_kernel": (rotary._latent_q_forward, [(q, bf16)], []),
                   "_latent_k_kernel": (rotary._latent_k_forward, [(kv, bf16), shared], [])}
    else:  # cotangents, then what the norm's transpose reads again
        entries = {
            "_latent_q_back_kernel": (rotary._latent_q_backward, [(q, bf16)], [(q, bf16)]),
            "_latent_k_back_kernel": (rotary._latent_k_backward, [(q, bf16), (v, bf16)],
                                      [(kv, bf16), shared])}
    for kernel, (entry, arrays, read_again) in entries.items():
        # an entry takes None for what a layer without the norm has not
        norms = [*read_again, *weight] if eps else [None] * (len(read_again) + 1)
        given = [a for a in norms if a is not None]

        def call(*a, entry=entry, n=len(arrays), norms=norms, given=given):
            last = a[n + len(given):] if turns else (a[-1], None)
            return entry(*a[:n], *(a[n:n + len(given)] or norms), *last, fuse)

        text = _compile_for(v5e, call, *arrays, *given, *table)
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert [trace.kernel_name(line) for line in calls] == [kernel]
