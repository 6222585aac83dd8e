"""What the ahead-of-time tests share (``tests/test_kernels_aot_v5e.py`` and the
``tests/test_aot_v5e_*.py`` beside it): libtpu's description of a ``v5e:2x2``
topology, one of its devices as a sharding, a function compiled for it and a
benchmark cell's train step lowered for it, with no chip. A plain module: a
piece imports the fixtures and helpers it reads by name, and a new kernel
family or a new cell's step brings a file of its own.
"""
import jax
from jax.sharding import SingleDeviceSharding
import pytest

from ray_tpu.ops.attention import _backward_call, _causal_mask, _forward_call


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - libtpu absent or too old
        pytest.skip(f"libtpu gives no v5e topology here: {e}")


@pytest.fixture(scope="module")
def v5e(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_for(sharding, fn, *args):
    """args are (shape, dtype) pairs; returns the optimized HLO text."""
    specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in args]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "custom-call" in text and "tpu_custom_call" in text
    return text


def _causal_fwd(block):
    """The one forward call under the causal mask at ``block`` x ``block``,
    the default scale."""
    return lambda q, k, v: _forward_call(
        _causal_mask(q, k, v, True, block, block), q, k, v, q.shape[2] ** -0.5)


def _causal_bwd(block):
    return lambda q, k, v, o, lse, do: _backward_call(
        _causal_mask(q, k, v, True, block, block), q, k, v, o, lse, do,
        q.shape[2] ** -0.5)


def _lowered_step(v5e, name):
    """(the cell ``name``, its step's StableHLO as lowered for a v5e chip)."""
    cell, lowered = _lower_step(v5e, name)
    return cell, lowered.as_text()


def _lower_step(v5e, name):
    """(the cell ``name``, its step lowered for a v5e chip)."""
    import importlib

    import numpy as np

    from benchmarks.lib import cells
    from benchmarks.loops.train_lm import make_loss_fn, make_optimizer
    from ray_tpu import train

    attention = importlib.import_module("ray_tpu.ops.attention")
    cell = cells.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    model = cells.resolve(config["program"]["model"])(cells.program_config(config))
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), tree)

    tx = make_optimizer(traffic)
    batch = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"]), np.int32, sharding=v5e)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "_on_tpu", lambda: True)
        lowered = train.make_train_step(make_loss_fn(traffic, model), tx).lower(
            placed(shapes), placed(jax.eval_shape(tx.init, shapes)), batch, batch
        )
    return cell, lowered


# (bodies, call sites) of ``_rotary_kernel`` in a cell's lowered step: a body
# a jitted entry (``ops/rotary.py`` ``_turned``: a shape, a part of a head, a
# direction, and a replay's copy of a forward one), a call for q and for k of
# every layer that turns heads of 128 lanes, forward, replayed where the cell
# replays, and backward. The Laguna cell: sliding q and k, full q and k. The
# MiniCPM-SALA cell: q and k are one shape, and its sparse layer turns
# nothing. The Mistral cells' lowered text holds the replay's calls too; no
# barrier stands there, XLA merges them with the forward's, and a trace
# counts 16 a step. sarvam's rotated part is 64 lanes of a 192-wide head:
# ``models/mla.py`` turns it inside ``latent_qkv``'s kernels, not this one.
ROTARY_STEPS = {
    "laguna-xs2-33b-a3b-l8.longctx-16k": (12, 48),
    "minicpm-sala-9b-l4.long16k": (3, 18),
    "mistral-7b-l4.short2k": (6, 24),
    "sarvam-105b-l5.pretrain-4k": (0, 0),
}


def _turns(text):
    from benchmarks.lib import checks

    bodies = checks.count_pallas_kernels(text, ("_rotary_kernel",))["_rotary_kernel"]
    return bodies, text.count("call @_turned")


# The Granite cell's kernels at the benchmark's real size (b1 x s8192): the
# Mamba-2 scan at 64 heads of 64 over a state of 128, u as [1, 8192, 64 x 64]
# (what the kernels take of ``chunk_ssd``'s [1, 8192, 64, 64]; nothing is
# transposed), B and C one [1, 8192, 128] pair, the steps as rows [1, 8, 8,
# 8192]; and at an odd number of groups. Each reads its own name as a
# profile's reader names it.
def _kernels(text):
    from benchmarks.lib import trace

    return [trace.kernel_name(line) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]
