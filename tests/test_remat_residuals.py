"""What the one remat policy keeps, by its two rules.

Under ``nothing_saveable`` a layer's replay runs each custom-vjp forward rule
whole: the kernel that wrote o and lse (or o and the per-chunk states) runs a
second time only to hand its own backward kernels what it had written once.
And it runs every projection again: a SwiGLU's gate_proj and up_proj for the
element-wise pass after them, o_proj and down_proj for the add or the norm
after the sublayer. Behind the barrier the replaying cells run
(``prevent_cse=True``) ``models.llama.remat_policy`` keeps the values
``REPLAY_KEEPS`` names; the forward rules tag the kernels' and
``models/llama.py`` the matmuls'. Here, on the CPU with the kernels
interpreted, through two ``nn.remat`` layers behind that barrier: the
gradient's jaxpr holds each forward kernel and each of those matmuls once a
layer where ``nothing_saveable`` holds it twice, a layer's replay is handed
the names a backward reads and no other, and loss and gradients are the same
to the bit.

This file holds softmax attention's layers (the causal, windowed and 192/128
kernels; the pre-norm and norm-after decoder) and what the barrier and the
rotation change. ``tests/remat_cases.py`` has the skeletons, the tables and
the cases' bodies; the other mixer families run them in
``tests/test_remat_residuals_*.py`` and the policy's own cases are in
``tests/test_remat_policy.py``.
"""
import pytest
from remat_jaxpr import forward_matmuls, kernel_calls

from ray_tpu.models import llama
from ray_tpu.models.llama import remat_policy

from remat_cases import (  # noqa: F401 - fixtures
    LAYERS, MATMUL_CASES, NOTHING, _KERNEL, _PRODUCTS, _cfg, _decoder,
    _gradient, _handed_to_the_replays, _interpret_mode, _same_to_the_bit,
    replay_holds_no_forward_kernel,
    replay_holds_no_matmul_for_an_elementwise_consumer,
)


@pytest.mark.parametrize("word", ["nothing", "kernels"])
def test_behind_the_barrier_either_word_is_the_one_policy(word):
    """The words a configuration may carry where a replay is executed give the
    one policy object (JAX caches a jitted kernel entry's partial evaluation
    by its identity): no replay runs a projection or the forward kernel, each
    is handed the kernel's names, the sublayer's output and the SwiGLU's two
    products; loss and gradients to the bit. Without the barrier either word
    saves nothing."""
    layer, shape, _, _ = MATMUL_CASES["pre-norm"]
    policy = remat_policy(_cfg(remat_policy=word, remat_prevent_cse=True))
    assert policy is llama._KEEP
    assert remat_policy(_cfg(remat_policy=word)) is NOTHING
    jaxpr, kept = _gradient(layer, shape, policy)
    dots = forward_matmuls(jaxpr)
    assert dots["mlp/gate_proj"] == dots["mlp/up_proj"] == LAYERS
    assert dots["attn/o_proj"] == dots["mlp/down_proj"] == LAYERS
    assert kernel_calls(jaxpr)["_fwd_kernel"] == LAYERS
    assert _handed_to_the_replays(jaxpr) == [
        sorted(_KERNEL | _PRODUCTS | {"mixer_out"})] * LAYERS
    _, bare = _gradient(layer, shape, NOTHING)
    _same_to_the_bit(kept, bare)


def test_the_replay_keeps_no_residual_for_the_rotation():
    """At 128 lanes a head q and k turn through ``ops/rotary.py``'s kernel,
    whose pass back keeps positions and the table's frequencies: the replay
    turns q and k again, as it makes every operand of the kernels again, and
    is handed the names a layer that turns through XLA is handed."""
    layer, shape, _, names = MATMUL_CASES["pre-norm"]
    wide = _decoder(head_dim=128)
    policy = remat_policy(_cfg(remat_prevent_cse=True))
    kept_jaxpr, kept = _gradient(wide, shape, policy)
    # q and k, forward, replayed and backward
    assert kernel_calls(kept_jaxpr)["_rotary_kernel"] == LAYERS * 6
    assert kernel_calls(_gradient(layer, shape, policy)[0])["_rotary_kernel"] == 0
    assert _handed_to_the_replays(kept_jaxpr) == [sorted(names)] * LAYERS
    bare_jaxpr, bare = _gradient(wide, shape, NOTHING)
    assert kernel_calls(bare_jaxpr)["_rotary_kernel"] == LAYERS * 6
    _same_to_the_bit(kept, bare)


@pytest.mark.parametrize("case", ["pre-norm", "norm-after"])
def test_without_the_barrier_the_names_are_inert(case):
    """A configuration that executes no replay (``remat_prevent_cse`` false)
    takes ``nothing_saveable``: its gradient holds every projection forward
    and in the replay, as before the names, and no replay is handed one."""
    layer, shape, matmuls, _ = MATMUL_CASES[case]
    policy = remat_policy(layer[1]["cfg"])
    assert policy is NOTHING
    jaxpr, _ = _gradient(layer, shape, policy, barrier=False)
    dots = forward_matmuls(jaxpr)
    for name, (_, replayed) in matmuls.items():
        assert dots[name] == LAYERS * replayed, dots
    assert _handed_to_the_replays(jaxpr) == [[]] * LAYERS


@pytest.mark.parametrize("case", ["causal-128", "window", "mla-192-128"])
def test_replay_holds_no_forward_kernel(case):
    replay_holds_no_forward_kernel(case)


@pytest.mark.parametrize("case", ["pre-norm", "norm-after"])
def test_replay_holds_no_matmul_for_an_elementwise_consumer(case):
    replay_holds_no_matmul_for_an_elementwise_consumer(case)
