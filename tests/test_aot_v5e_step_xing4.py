"""The Xing4 cell's train step at its real size, lowered ahead of time for a v5e
chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import jax
import pytest

from aot_v5e import topo, v5e  # noqa: F401 - fixtures


def test_xing4s_step_calls_each_hyper_connection_kernel_once_a_connection(v5e):
    """Two layers and the module's, at the rehearsal's widths (128 channels)
    over 256 tokens, which tile: six hyper-connections, each a read and a
    write forward and the three backward kernels; no replay holds a read or
    a write, the remat policy keeps what they wrote (models/llama.py
    REPLAY_KEEPS). A backward kernel's body stands once in the text,
    behind its jitted entry; a forward one's twice, a layer's first
    connection's and its second's, which remat's partial evaluation tells
    apart because the second's streams are a kept value; with one policy
    object for every ``_through`` the module's layer shares them
    (``models.llama._KEEP``). Every call
    is under /hc/pre/ or /hc/post/, where the benchmark's model.hc_share and
    model.hc_roofline look for it."""
    import importlib
    import re

    import numpy as np

    from benchmarks.lib import cells, checks
    from benchmarks.loops.train_lm import make_loss_fn, make_optimizer
    from ray_tpu import train

    attention = importlib.import_module("ray_tpu.ops.attention")
    cell = cells.load_cell("xing4-29b-a4b-l5.pretrain-mtp-4k")
    config, traffic = cell["config"], {**cell["traffic"], "seq": 256}
    config = {**config, **config["rehearsal"], "num_hidden_layers": 2}
    model = cells.resolve(config["program"]["model"])(cells.program_config(config))
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), tree)

    tx = make_optimizer(traffic)
    batch = jax.ShapeDtypeStruct((1, traffic["seq"]), np.int32, sharding=v5e)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "_on_tpu", lambda: True)
        text = train.make_train_step(make_loss_fn(traffic, model), tx).lower(
            placed(shapes), placed(jax.eval_shape(tx.init, shapes)), batch, batch
        ).as_text(debug_info=True)
    connections = 2 * (config["num_hidden_layers"] + config["num_nextn_predict_layers"])
    # entry: (kernel, bodies, calls, scope)
    entries = {"_pre_fwd": ("_hc_pre_fwd_kernel", 2, connections, "/hc/pre/"),
               "_post_fwd": ("_hc_post_fwd_kernel", 2, connections, "/hc/post/"),
               "_post_bwd": ("_hc_post_bwd_kernel", 1, connections, "/hc/post/"),
               "_pre_sums": ("_hc_pre_sums_kernel", 1, connections, "/hc/pre/"),
               "_pre_bwd": ("_hc_pre_bwd_kernel", 1, connections, "/hc/pre/")}
    bodies = checks.count_pallas_kernels(text, [k for k, *_ in entries.values()])
    assert bodies == {k: n for k, n, *_ in entries.values()}
    locations = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    for entry, (_, _, calls, scope) in entries.items():
        sites = re.findall(rf"call @{entry}(?:_\d+)?\(.*loc\((#loc\d+)\)$", text, re.M)
        assert len(sites) == calls, (entry, len(sites))
        for site in sites:
            assert scope in locations[site], (entry, locations[site])
