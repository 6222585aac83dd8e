"""One trace a kernel: every Pallas call site stands behind one jitted entry
that JAX inlines as it traces (``ops/attention.py`` ``kernel_entry``), so a
step whose layers are one shape runs a kernel's Python once and not once a
layer, and its text holds every call where it was.

Read by the entries' own counter (``util/tracing.py`` ``entry_counts``): a
family of kernels a case, three same-shaped layers under ``jax.grad`` with the
remat on; the lowered step of a small Granite for a described v5e (the
kernels' names a layer, under the layer's scopes); and what must never share a
trace: the interpreter's switch, a differing static, a patched constant. The
loops are traced and never run, under ``jax.set_mesh`` as a train step is: with
no mesh at all JAX evaluates a remat's equations under an explicit empty mesh,
which is another trace context than none, and a forward entry is then traced
once for the remat's trace and once for the forward rule's."""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gmm as G
from ray_tpu.ops import kda
from ray_tpu.util import tracing

attention = importlib.import_module("ray_tpu.ops.attention")
F32 = jnp.float32


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


@pytest.fixture
def fresh_traces():
    """An entry keeps what it traced for the life of the process: a case
    that counts traces starts from none."""
    jax.clear_caches()


def draw(seed, *shape, dtype=jnp.bfloat16):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape) * 0.1, dtype)


# A family: ``layer(t)`` gives (the first layer's input, the layer as a
# function of it) at a length t, every other operand a constant of the layer;
# ``traces`` is what three layers of one length trace, {entry: its distinct
# (statics, shapes)}: a scan's forward entry two, without the states (the
# function as the remat traces it) and with them (its forward rule), its
# backward entry one; the flash entries one each (both of the forward's calls
# say the same); the grouped matmul two, as the weights are stored and
# transposed, and its transposed form one.


def ssd(t, h=8, p=32, n=32):
    rest = (jax.nn.softplus(draw(1, 1, t, h, dtype=F32)), draw(2, h, dtype=F32),
            draw(3, 1, t, n), draw(4, 1, t, n), draw(5, h, dtype=F32))
    return draw(0, 1, t, h, p), lambda u: kda.chunk_ssd(u, *rest[:2], *rest[2:])


def delta_rule(t, h=2, dk=32, dv=32):
    q, k = draw(1, 1, t, h, dk, dtype=F32), draw(2, 1, t, h, dk, dtype=F32)
    g = -jax.nn.softplus(draw(3, 1, t, h, dk, dtype=F32))
    beta = jax.nn.sigmoid(draw(4, 1, t, h, dtype=F32))
    gate, weight = draw(5, 1, t, h, dv), jnp.ones((dv,), F32)
    return draw(0, 1, t, h, dv), lambda v: kda.chunk_kda(
        q, k, v, g, beta, gate, weight, scale=dk ** -0.5, rms_eps=1e-6)


def gdn(t, h=2, dk=32, dv=32):
    qk = draw(1, 1, 2, h, t, dk, dtype=F32)
    g = -jax.nn.softplus(draw(3, 1, t, h, dtype=F32))
    beta = jax.nn.sigmoid(draw(4, 1, t, h, dtype=F32))
    gate, weight = draw(5, 1, t, h, dv), jnp.ones((dv,), F32)
    return draw(0, 1, t, h, dv), lambda v: kda.chunk_gdn(
        qk, v, g, beta, gate, weight, scale=dk ** -0.5, rms_eps=1e-6)


def lightning(t, h=2, dk=32, dv=32):
    q, k = draw(1, 1, t, h, dk), draw(2, 1, t, h, dk)
    gate, weight = draw(5, 1, t, h, dv), jnp.ones((dv,), F32)
    slopes = jnp.asarray([0.1, 0.2], F32)
    return draw(0, 1, t, h, dv), lambda v: kda.chunk_lightning(
        q, k, v, gate, weight, slopes, scale=dk ** -0.5, rms_eps=1e-6)


def flash(**kind):
    def layer(t, h=2, d=32):
        return draw(0, 1, h, t, d), lambda x: attention.flash_attention(x, x, x, **kind)

    return layer


def bitmap(t, h=2, d=32, block_size=16):  # 256-key tiles: 512 and 256 rows pad apart
    blocks = jnp.tril(jnp.ones((t, t // block_size), bool), 0)[None, None]
    blocks = jnp.broadcast_to(blocks, (1, h, t, t // block_size))
    return draw(0, 1, h, t, d), lambda x: attention.flash_attention(
        x, x, x, blocks=blocks, block_size=block_size)


def grouped(bounded):
    def layer(t, experts=2, k=128):
        rhs = draw(1, experts, k, k, dtype=F32)
        tile_group = jnp.asarray(np.arange(t // 128) * experts // (t // 128), jnp.int32)
        used = jnp.asarray([t // 128 - 1], jnp.int32) if bounded else None
        return draw(0, t, k, dtype=F32), lambda lhs: G.gmm(
            lhs, rhs, tile_group, 128, used)

    return layer


FAMILIES = {
    "ssd": (ssd, {"kda._ssd_forward_pallas": 2, "kda._ssd_backward_pallas": 1}),
    "kda": (delta_rule, {"kda._forward_pallas": 2, "kda._backward_pallas": 1}),
    "gdn": (gdn, {"kda._gdn_forward_pallas": 2, "kda._gdn_backward_pallas": 1}),
    "lightning": (lightning, {"kda._lightning_forward_pallas": 2,
                              "kda._lightning_backward_pallas": 1}),
    "flash-causal": (flash(), {"attention._block_fwd": 1, "attention._block_bwd": 1}),
    "flash-windowed": (flash(window=128),
                       {"attention._block_fwd": 1, "attention._block_bwd": 1}),
    "flash-bitmap": (bitmap, {"attention._sparse_fwd": 1, "attention._sparse_bwd": 1}),
    "gmm": (grouped(False), {"gmm._gmm_pallas": 2, "gmm._tgmm_pallas": 1}),
    "tgmm": (grouped(True), {"gmm._gmm_pallas": 2, "gmm._tgmm_pallas": 1}),
}
T, T_ODD = 512, 256


def one_device_mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))


def counted(family, layers: int, odd: bool = False) -> dict:
    """{entry: [calls, traces]} of tracing the gradient of ``layers`` layers of
    one length, each under a remat of its own, and then an ``odd`` one of
    another length on the first rows."""
    layer, _ = FAMILIES[family]
    x0, same = layer(T)
    rows = 0 if family.endswith("gmm") else x0.shape.index(T)
    _, other = layer(T_ODD)

    def loss(x):
        # Made under the mesh, as a layer's input is in a step: an array from
        # outside it says "no mesh" in its type, which is another type.
        x = x * 1
        for i in range(layers):
            with tracing.scope(f"{tracing.LAYER}{i}"):
                # A function of its own a layer, as a model's modules are: a
                # remat keeps the jaxpr of a function it has seen.
                x = jax.checkpoint(lambda x, layer=same: layer(x))(x)
        if odd:
            x = jax.checkpoint(other)(jax.lax.slice_in_dim(x, 0, T_ODD, axis=rows))
        # Times a constant: the sum's own cotangent is a weakly typed one,
        # which no layer inside a model is handed.
        return (x.astype(F32) * jnp.arange(x.shape[-1], dtype=F32)).sum()

    before = tracing.entry_counts()
    with jax.set_mesh(one_device_mesh()):
        jax.make_jaxpr(jax.grad(loss))(x0)
    return tracing.entry_counts(before)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_three_layers_of_one_shape_trace_a_kernel_as_often_as_one(family, fresh_traces):
    traces = FAMILIES[family][1]
    one = counted(family, 1)
    assert {k: v[1] for k, v in one.items()} == traces
    jax.clear_caches()
    three = counted(family, 3)
    assert {k: v[1] for k, v in three.items()} == traces
    for entry, (calls, _) in three.items():
        assert calls == 3 * one[entry][0] >= 3, entry
    # A fourth layer of another shape: one more trace a (statics), nothing of
    # the three again.
    four = counted(family, 3, odd=True)
    assert {k: v[1] for k, v in four.items()} == traces
    assert {k: v[0] for k, v in four.items()} == {
        k: calls + one[k][0] for k, (calls, _) in three.items()}
    # And nothing at all the second time round.
    assert {k: v[1] for k, v in counted(family, 3, odd=True).items()} == dict.fromkeys(traces, 0)


# ------------------------------------------------- the text holds what it held

GRANITE = {"hidden_size": 256, "intermediate_size": 512, "shared_intermediate_size": 512,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
           "vocab_size": 512, "mamba_n_heads": 8, "mamba_d_head": 64,
           "mamba_d_state": 128, "mamba_expand": 2, "num_hidden_layers": 4,
           "layer_types": ["mamba", "mamba", "attention", "mamba"]}


@pytest.fixture(scope="module")
def granite_text():
    """(the train step of a Granite of three Mamba layers around an attention
    layer, at sizes the kernels tile, lowered for the TPU as
    benchmarks/rehearse.py lowers a cell's, the backend's probe stood in for;
    the entries' counts of that lowering). Lowered, not compiled: Mosaic's
    lowering is jaxlib's and needs no TPU library."""
    from benchmarks.lib import cells
    from benchmarks.loops.train_lm import make_loss_fn, make_optimizer
    from ray_tpu import train

    cell = cells.load_cell("granite-4-h-micro-l10.pretrain-8k")
    config, traffic = {**cell["config"], **GRANITE}, {**cell["traffic"], "seq": 512}
    model = cells.resolve(config["program"]["model"])(cells.program_config(config))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    tx = make_optimizer(traffic)
    batch = jax.ShapeDtypeStruct((1, traffic["seq"]), np.int32)
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as patch:
        patch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
        patch.setattr(attention, "_on_tpu", lambda: True)
        before = tracing.entry_counts()
        with jax.set_mesh(one_device_mesh()):
            text = train.make_train_step(make_loss_fn(traffic, model), tx).trace(
                shapes, jax.eval_shape(tx.init, shapes), batch, batch
            ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
        return text, tracing.entry_counts(before)


def test_the_lowered_step_holds_a_kernel_a_layer_under_the_layers_scopes(granite_text):
    from benchmarks.lib import checks

    text, counts = granite_text
    kernels = ("_ssd_fwd_kernel", "_ssd_bwd_kernel", "_fwd_kernel", "_bwd_dkv_kernel",
               "_bwd_dq_kernel")
    assert checks.count_pallas_kernels(text, kernels) == {
        "_ssd_fwd_kernel": 3, "_ssd_bwd_kernel": 3, "_fwd_kernel": 1,
        "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1}
    # Nine calls of the scan's entries, three traces: the mechanism's share.
    assert counts["kda._ssd_forward_pallas"] == [6, 2]
    assert counts["kda._ssd_backward_pallas"] == [3, 1]
    assert counts["attention._block_fwd"] == [2, 1]
    # Every call under its own layer's name stack, which a device trace's
    # per-layer readers attribute its time by.
    locations = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    under = {}
    for line in text.splitlines():
        kernel = re.search(r'kernel_name = "(\w+)"', line)
        if kernel and kernel.group(1) in kernels:
            site = locations[re.search(r"loc\((#loc\d+)\)$", line).group(1)]
            under.setdefault(kernel.group(1), []).append(site)
    mamba = [f"/{tracing.LAYER}{i}/{tracing.MAMBA}/" for i in (0, 1, 3)]
    for kernel, backward in (("_ssd_fwd_kernel", False), ("_ssd_bwd_kernel", True)):
        for scope in mamba:
            assert sum(scope in site for site in under[kernel]) == 1, (kernel, scope)
        assert all(("transpose(" in site) == backward for site in under[kernel])
    for kernel in ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel"):
        assert f"/{tracing.LAYER}2/{tracing.ATTN}/" in under[kernel][0]


# ------------------------------------------------------------- no stale trace


def traced(fn, *args):
    """(the jaxpr of a call, how many entries it traced). ``make_jaxpr``
    keeps the jaxpr of a function it has seen: a new one a call."""
    before = tracing.entry_counts()
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    return jaxpr, sum(t for _, t in tracing.entry_counts(before).values())


def pallas_calls(jaxpr) -> list:
    return [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]


def test_the_interpreters_switch_is_part_of_a_trace(monkeypatch, fresh_traces):
    q = draw(0, 2, 256, 32)
    block = lambda: attention._block_fwd(q, q, q, True, 0.25, 128, 128)  # noqa: E731
    lhs, rhs = draw(1, 256, 128, dtype=F32), draw(2, 2, 128, 128, dtype=F32)
    tile_group = jnp.asarray([0, 1], jnp.int32)
    rows = lambda: G._gmm_pallas(lhs, rhs, tile_group, 128)  # noqa: E731

    on = [traced(f) for f in (block, rows)]
    assert [n for _, n in on] == [1, 1]
    assert all(p.params["interpret"] for j, _ in on for p in pallas_calls(j))
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    off = [traced(f) for f in (block, rows)]
    assert [n for _, n in off] == [1, 1]  # traced anew, not the last one's
    assert not pallas_calls(off[0][0])  # on the CPU, no interpreter: XLA's road
    assert [p.params["interpret"] for p in pallas_calls(off[1][0])] == [False]
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)  # the probe stood in for
    probe = traced(block)
    assert probe[1] == 1 and len(pallas_calls(probe[0])) == 1
    monkeypatch.undo()
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    assert [traced(f)[1] for f in (block, rows)] == [0, 0]  # each is kept


def scan_statics():
    """{case: (entry's call at a value of the static, two values)}."""
    t, h, dk = 128, 2, 32
    q, g = draw(1, 1, t, h * dk, dtype=F32), -jnp.ones((1, t, h * dk), F32)
    v, beta = draw(2, 1, t, h * dk), jnp.full((1, h, t, 1), 0.5, F32)
    weight = jnp.ones((1, dk), F32)
    x = draw(3, 2, 256, 32)
    return {
        "states": (lambda s: kda._forward_pallas(
            q, q, v, g, beta, v, weight, h, (0.25, 1e-6, 1e-6), s), (False, True)),
        "norm": (lambda eps: kda._forward_pallas(
            q, q, v, g, beta, v, weight, h, (0.25, 1e-6, eps), False), (1e-6, 1e-5)),
        "window": (lambda w: attention._block_fwd(x, x, x, True, 0.25, 128, 128, w),
                   (None, 128)),
        "transpose_rhs": (lambda tr: G._gmm_pallas(
            draw(4, 256, 128, dtype=F32), draw(5, 2, 128, 128, dtype=F32),
            jnp.asarray([0, 1], jnp.int32), 128, transpose_rhs=tr), (False, True)),
    }


@pytest.mark.parametrize("static", ["states", "norm", "window", "transpose_rhs"])
def test_a_differing_static_is_another_trace(static, fresh_traces):
    call, (first, second) = scan_statics()[static]
    a, b = traced(lambda: call(first)), traced(lambda: call(second))
    assert (a[1], b[1]) == (1, 1)
    assert str(a[0]) != str(b[0])
    again = traced(lambda: call(first))
    assert again[1] == 0 and str(again[0]) == str(a[0])


def test_a_patched_constant_a_body_reads_is_another_trace(monkeypatch, fresh_traces):
    """What the kernels' tests patch between two calls of one shape
    (``tests/test_kda_op.py`` and its five siblings, ``tests/test_gmm_kernel.py``,
    ``tests/test_flash_interpret.py`` and the ``tests/test_flash_tile*.py``) is in the entries' keys."""
    call = scan_statics()["states"][0]
    lhs, rhs = draw(1, 256, 128, dtype=F32), draw(2, 2, 128, 256, dtype=F32)
    rows = lambda: G._gmm_pallas(lhs, rhs, jnp.asarray([0, 1], jnp.int32), 128)  # noqa: E731
    x = draw(3, 2, 256, 32)
    block = lambda: attention._block_fwd(x, x, x, True, 0.25, 128, 128)  # noqa: E731
    pairs, whole, tiles = traced(lambda: call(True)), traced(rows), traced(block)
    monkeypatch.setattr(kda, "_PAIR", 1)
    monkeypatch.setattr(G, "_BLOCK_BUDGET", 2 ** 18)
    real = attention._tile_class

    def no_interior(*args, **tile):
        live, interior = real(*args, **tile)
        return live, interior & False

    monkeypatch.setattr(attention, "_tile_class", no_interior)
    alone, split, masked = traced(lambda: call(True)), traced(rows), traced(block)
    assert (pairs[1], whole[1], tiles[1], alone[1], split[1], masked[1]) == (1,) * 6
    grid = lambda j: pallas_calls(j)[0].params["grid_mapping"].grid  # noqa: E731
    assert grid(pairs[0])[2] == 1 and grid(alone[0])[2] == 2  # heads a step: 2, 1
    assert grid(whole[0])[0] == 1 and grid(split[0])[0] == 2  # column blocks
    assert str(tiles[0]) != str(masked[0])
