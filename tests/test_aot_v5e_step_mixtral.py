"""The Mixtral cell's train step at its real size on its mesh, lowered ahead of
time for a v5e chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import jax
import pytest

from aot_v5e import topo  # noqa: F401 - fixtures


def test_mixtrals_step_takes_its_weight_gradients_from_the_grouped_matmul(topo):
    """The Mixtral cell's step at its real size on seq=2 x expert=2 (the
    rehearsal size above runs the plain einsum: `_ffn_trips` is 0 there),
    lowered and not compiled: each layer's backward ends in three
    `_tgmm_kernel` calls over the five stacks its loop filled, and no
    float32 array of a chip's four expert matrices is left for a loop to
    carry."""
    import importlib

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.lib import cells, checks
    from benchmarks.loops.train_lm import make_loss_fn, make_optimizer
    from ray_tpu import train
    from ray_tpu.parallel import MeshSpec, logical_sharding
    from ray_tpu.parallel.mesh import spec_for_param

    cell = cells.load_cell("mixtral-8x7b-l2.ep2seq2-4k")
    config, traffic = cell["config"], cell["traffic"]
    mesh = MeshSpec(**traffic["mesh"]).build(topo.devices[: cell["chips"]])
    cfg = cells.program_config(config)
    model_cls = cells.resolve(config["program"]["model"])
    shapes = jax.eval_shape(
        model_cls(cfg).init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))

    def placed(path, leaf):
        keys = tuple(getattr(p, "key", getattr(p, "name", getattr(p, "idx", "")))
                     for p in path)
        keys = keys[keys.index("params"):] if "params" in keys else keys
        spec = spec_for_param(keys, leaf.shape) if leaf.ndim else PartitionSpec()
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec))

    tx = make_optimizer(traffic)
    batch = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"]), np.int32,
        sharding=logical_sharding(mesh, ("batch", "seq")))
    with pytest.MonkeyPatch.context() as patch, jax.set_mesh(mesh):
        patch.setattr(
            importlib.import_module("ray_tpu.ops.attention"), "_on_tpu", lambda: True)
        model = model_cls(cfg, mesh=mesh)
        text = train.make_train_step(make_loss_fn(traffic, model), tx).lower(
            jax.tree_util.tree_map_with_path(placed, shapes),
            jax.tree_util.tree_map_with_path(placed, jax.eval_shape(tx.init, shapes)),
            batch, batch,
        ).as_text()
    layers, width = config["num_hidden_layers"], config["intermediate_size"]
    counts = checks.count_pallas_kernels(text, ("_tgmm_kernel", "_unwritten_kernel"))
    assert counts == {"_tgmm_kernel": 3 * layers, "_unwritten_kernel": 5 * layers}
    # Its mesh splits the sequence: q and k at 128 lanes a head turn by _rope.
    assert "_rotary_kernel" not in text and "_turned" not in text
    hidden = config["hidden_size"]
    assert f"tensor<{19 * 512}x{width}xbf16>" in text  # a stack of 19 trips
    for shape in (f"4x{hidden}x{width}", f"4x{width}x{hidden}"):
        assert f"tensor<{shape}xbf16>" in text and f"tensor<{shape}xf32>" not in text
