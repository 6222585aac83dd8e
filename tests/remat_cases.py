"""What the tests of the one remat policy share
(``tests/test_remat_residuals.py`` and ``tests/test_remat_residuals_*.py``, a
file a mixer family, and ``tests/test_remat_policy.py``): the skeleton layers
around each kernel, two ``nn.remat`` layers of one behind the barrier, the
gradient's jaxpr and values (made once for a layer, shape and policy), the
case tables ``CASES`` and ``MATMUL_CASES``, and the bodies of the three cases
that run over them, which each family's file runs over its own names.
``tests/remat_jaxpr.py`` counts kernels and matmuls in a jaxpr. A new mixer
adds its skeleton and its rows here and brings a file of its own.
"""
import functools

import flax.linen as nn
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from remat_jaxpr import forward_matmuls, kernel_calls

from ray_tpu.models import hyper_connections
from ray_tpu.models.llama import (
    MLP, REPLAY_KEEPS, Attention, DecoderLayer, LlamaConfig, remat_policy,
)
from ray_tpu.models.mixtral import MixtralConfig, MoELayer
from ray_tpu.ops.attention import flash_attention, select_blocks
from ray_tpu.ops.kda import chunk_gdn, chunk_kda, chunk_lightning, chunk_ssd
from ray_tpu.util import tracing


LAYERS = 2
T = 256
NOTHING = jax.checkpoint_policies.nothing_saveable


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _cfg(**kwargs):
    return LlamaConfig(
        vocab_size=64, hidden_size=64, intermediate_size=128, num_layers=LAYERS,
        num_heads=2, num_kv_heads=2, max_seq_len=T, **kwargs,
    )


class _Attention(nn.Module):
    """A mixer's skeleton around ``flash_attention``: projections XLA
    computes on both sides of the kernels."""
    heads: int
    kv_heads: int
    d: int
    d_v: int
    window: object = None

    @nn.compact
    def __call__(self, x):
        b, t, c = x.shape

        def heads(name, n, width):
            y = nn.Dense(n * width, use_bias=False, name=name)(x)
            return y.reshape(b, t, n, width).transpose(0, 2, 1, 3)

        o = flash_attention(
            heads("q", self.heads, self.d), heads("k", self.kv_heads, self.d),
            heads("v", self.kv_heads, self.d_v), window=self.window,
            block_q=128, block_k=128,
        )
        o = o.transpose(0, 2, 1, 3).reshape(b, t, -1)
        return x + nn.Dense(c, use_bias=False, name="o")(o)


class _KDA(nn.Module):
    heads: int = 2
    dk: int = 64
    dv: int = 64

    @nn.compact
    def __call__(self, x):
        b, t, c = x.shape

        def heads(name, width, dtype=jnp.float32):
            y = nn.Dense(self.heads * width, use_bias=False, name=name)(x)
            return y.reshape(b, t, self.heads, width).astype(dtype)

        g = -jax.nn.softplus(heads("g", self.dk))
        beta = jax.nn.sigmoid(nn.Dense(self.heads, use_bias=False, name="beta")(x))
        weight = self.param("norm", nn.initializers.ones, (self.dv,))
        o = chunk_kda(
            heads("q", self.dk), heads("k", self.dk), heads("v", self.dv, x.dtype),
            g, beta, heads("gate", self.dv, x.dtype), weight,
            scale=self.dk ** -0.5, rms_eps=1e-6,
        )
        return x + nn.Dense(c, use_bias=False, name="o")(o.reshape(b, t, -1))


class _GDN(nn.Module):
    """``_KDA`` with one decay a head and token and value heads twice as wide
    as the key heads, through ``chunk_gdn``."""
    heads: int = 2
    dk: int = 32
    dv: int = 64

    @nn.compact
    def __call__(self, x):
        b, t, c = x.shape

        def heads(name, width, dtype=jnp.float32):
            y = nn.Dense(self.heads * width, use_bias=False, name=name)(x)
            return y.reshape(b, t, self.heads, width).astype(dtype)

        g = -jax.nn.softplus(nn.Dense(self.heads, use_bias=False, name="g")(x))
        beta = 2.0 * jax.nn.sigmoid(nn.Dense(self.heads, use_bias=False, name="beta")(x))
        weight = self.param("norm", nn.initializers.ones, (self.dv,))
        heads_first = lambda y: y.transpose(0, 2, 1, 3)  # noqa: E731
        o = chunk_gdn(
            jnp.stack([heads_first(heads("q", self.dk)), heads_first(heads("k", self.dk))], 1),
            heads("v", self.dv, x.dtype),
            g, beta, heads("gate", self.dv, x.dtype), weight,
            scale=self.dk ** -0.5, rms_eps=1e-6,
        )
        return x + nn.Dense(c, use_bias=False, name="o")(o.reshape(b, t, -1))


class _Lightning(nn.Module):
    """``_KDA`` without a write strength or a learned decay, through
    ``chunk_lightning``: one slope a head."""
    heads: int = 2
    d: int = 64

    @nn.compact
    def __call__(self, x):
        b, t, c = x.shape

        def heads(name):
            y = nn.Dense(self.heads * self.d, use_bias=False, name=name)(x)
            return y.reshape(b, t, self.heads, self.d)

        weight = self.param("norm", nn.initializers.ones, (self.d,))
        o = chunk_lightning(
            heads("q"), heads("k"), heads("v"), heads("gate"), weight,
            jnp.asarray([0.5, 0.01], jnp.float32), scale=self.d ** -0.5, rms_eps=1e-6,
        )
        return x + nn.Dense(c, use_bias=False, name="o")(o.reshape(b, t, -1))


class _SSD(nn.Module):
    """A Mamba-2 scan between projections, through ``chunk_ssd``: a step a
    head and token, B and C one pair for every head, a skip a head."""
    heads: int = 2
    p: int = 16
    n: int = 16

    @nn.compact
    def __call__(self, x):
        b, t, c = x.shape
        proj = lambda name, width: nn.Dense(width, use_bias=False, name=name)(x)  # noqa: E731
        y = chunk_ssd(
            proj("u", self.heads * self.p).reshape(b, t, self.heads, self.p),
            jax.nn.softplus(proj("dt", self.heads)),
            self.param("A_log", nn.initializers.zeros, (self.heads,)),
            proj("B", self.n), proj("C", self.n),
            self.param("D", nn.initializers.ones, (self.heads,)),
        )
        return x + nn.Dense(c, use_bias=False, name="o")(y.reshape(b, t, -1))


class _Sparse(nn.Module):
    """``_Attention`` over the blocks ``select_blocks`` chooses, K and V at
    half of q's heads."""
    heads: int = 4
    kv_heads: int = 2
    d: int = 32

    @nn.compact
    def __call__(self, x):
        b, t, c = x.shape

        def heads(name, n):
            y = nn.Dense(n * self.d, use_bias=False, name=name)(x)
            return y.reshape(b, t, n, self.d).transpose(0, 2, 1, 3)

        q, k, v = heads("q", self.heads), heads("k", self.kv_heads), heads("v", self.kv_heads)
        blocks = select_blocks(q, k, block_size=16, topk=4, window=32,
                               init_blocks=1, kernel_size=8, kernel_stride=4)
        o = flash_attention(q, k, v, blocks=blocks, block_size=16)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, -1)
        return x + nn.Dense(c, use_bias=False, name="o")(o)


class _HyperConnected(nn.Module):
    """A layer's two rounds of read, sublayer, write on [n, B, T, C] streams,
    as ``models.llama._hyper_connected`` makes them."""

    @nn.compact
    def __call__(self, x):
        for name in ("mixer", "ffn"):
            u, x, maps = hyper_connections.HyperConnection(
                hyper_connections.HyperConnections(), 1e-6,
                nn.initializers.normal(0.02), name=f"{name}_hc",
            )(x, streams=True)
            y = nn.Dense(x.shape[-1], use_bias=False, name=name)(u)
            x = hyper_connections.write_streams(x, y, *maps)
        return x


class _Decoder(nn.Module):
    """``models.llama.DecoderLayer`` as ``_through`` binds it, around softmax
    attention and the FFN ``ffn`` names."""
    cfg: LlamaConfig
    ffn: str = tracing.MLP

    @nn.compact
    def __call__(self, x):
        positions = jnp.broadcast_to(jnp.arange(x.shape[-2]), x.shape[-3:-1])
        ffn = {tracing.MLP: MLP, tracing.MOE: MoELayer}[self.ffn]
        return DecoderLayer(
            self.cfg, (tracing.ATTN, Attention), (self.ffn, ffn), name="layer",
        )(x, positions)


class _Stack(nn.Module):
    layer: object  # (the layer's nn.Module class, its fields)
    policy: object
    barrier: bool = True

    @nn.compact
    def __call__(self, x):
        cls, fields = self.layer
        layer_cls = nn.remat(cls, prevent_cse=self.barrier, policy=self.policy)
        for i in range(LAYERS):
            x = layer_cls(**fields, name=f"layers_{i}")(x)
        return jnp.sum(x.astype(jnp.float32) ** 2)


CASES = {
    # name: (layer, x's shape, {a forward kernel: calls a layer in the
    # gradient under the policy, and under nothing_saveable})
    "causal-128": ((_Attention, dict(heads=2, kv_heads=2, d=128, d_v=128)),
                   (1, T, 64), {"_fwd_kernel": (1, 2)}),
    "window": ((_Attention, dict(heads=4, kv_heads=2, d=128, d_v=128, window=128)),
               (1, T, 64), {"_fwd_window_kernel": (1, 2)}),
    "mla-192-128": ((_Attention, dict(heads=2, kv_heads=2, d=192, d_v=128)),
                    (1, T, 64), {"_fwd_kernel": (1, 2)}),
    "kda": ((_KDA, {}), (1, T, 64), {"_kda_fwd_kernel": (1, 2)}),
    "gdn": ((_GDN, {}), (1, T, 64), {"_gdn_fwd_kernel": (1, 2)}),
    "lightning": ((_Lightning, {}), (1, T, 64), {"_lightning_fwd_kernel": (1, 2)}),
    "sparse": ((_Sparse, {}), (1, T, 64), {"_sparse_fwd_kernel": (1, 2)}),
    "ssd": ((_SSD, {}), (1, T, 64), {"_ssd_fwd_kernel": (1, 2)}),
    # A layer's second write is its output, which no replay makes.
    "hyper-connections": ((_HyperConnected, {}), (4, 1, T, 128), {
        "_hc_pre_fwd_kernel": (2, 4), "_hc_post_fwd_kernel": (2, 3)}),
}


def _gradient(layer, shape, policy, barrier=True):
    """The jaxpr of loss and gradients through ``_Stack``, and their values:
    made once for a (layer, shape, policy, barrier), which several cases read
    (a case's kept and bare gradients are another case's too) and none writes."""
    cls, fields = layer
    return _gradient_of(cls, tuple(fields.items()), shape, policy, barrier)


@functools.lru_cache(maxsize=None)
def _gradient_of(cls, fields, shape, policy, barrier):
    layer = (cls, dict(fields))
    model = _Stack(layer, policy, barrier)
    x = jnp.asarray(np.random.RandomState(0).randn(*shape), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)
    grad = jax.value_and_grad(lambda p, x: model.apply(p, x), argnums=(0, 1))
    return jax.make_jaxpr(grad)(params, x).jaxpr, jax.jit(grad)(params, x)


def _run(case, policy):
    layer, shape, _ = CASES[case]
    jaxpr, values = _gradient(layer, shape, policy)
    return kernel_calls(jaxpr), values


def _same_to_the_bit(kept, bare):
    for a, b in zip(jax.tree_util.tree_leaves(kept), jax.tree_util.tree_leaves(bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def replay_holds_no_forward_kernel(case):
    forward = CASES[case][2]
    kept_calls, kept = _run(case, remat_policy(_cfg(remat_prevent_cse=True)))
    bare_calls, bare = _run(case, NOTHING)
    for kernel, (once, replayed) in forward.items():
        assert kept_calls[kernel] == LAYERS * once, kept_calls
        assert bare_calls[kernel] == LAYERS * replayed, bare_calls
    # Nothing but the forward kernels left the replay.
    for kernel in set(bare_calls) - set(forward):
        assert kept_calls[kernel] == bare_calls[kernel], (kept_calls, bare_calls)
    _same_to_the_bit(kept, bare)


def a_kda_layer_needs_each_of_its_three_names_kept(dropped):
    """o, the per-chunk states and the chunks' inverses leave the forward
    kernel together (KDA's, and the scalar-decay kernel's under names of its
    own; the fixed-decay kernel's o and states and the Mamba-2 kernel's y and
    states, which have no inverse): a
    policy that lacks any one of them runs the forward kernel in the replay to
    remake it, whatever else it holds."""
    case = dropped.partition("_")[0]
    fwd, bwd = f"_{case}_fwd_kernel", f"_{case}_bwd_kernel"
    names = [name for name in REPLAY_KEEPS if name != dropped]
    calls, _ = _run(case, jax.checkpoint_policies.save_only_these_names(*names))
    assert calls[fwd] == 2 * LAYERS and calls[bwd] == LAYERS
    kept, _ = _run(case, remat_policy(_cfg(remat_prevent_cse=True)))
    assert kept[fwd] == kept[bwd] == LAYERS


def _decoder(ffn=tracing.MLP, config=LlamaConfig, **fields):
    cfg = config(
        vocab_size=64, hidden_size=128, intermediate_size=256, num_layers=LAYERS,
        num_heads=2, num_kv_heads=2, max_seq_len=T, **fields,
    )
    return _Decoder, dict(cfg=cfg, ffn=ffn)


_KERNEL = {"flash_o", "flash_lse"}
_PRODUCTS = {"mlp_gate", "mlp_up"}
_SHARED = dict(
    config=MixtralConfig, num_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=128, num_shared_experts=1,
)
MATMUL_CASES = {
    # name: (layer, x's shape, {a projection: its forward matmuls a layer in
    # the gradient under the policy, and under nothing_saveable}, the names a
    # layer's replay is handed)
    #
    # Nothing reads a pre-norm layer's FFN output but the add after it: no
    # replay makes it under either policy, and it is not kept.
    "pre-norm": (_decoder(), (1, T, 128), {
        "mlp/gate_proj": (1, 2), "mlp/up_proj": (1, 2), "attn/o_proj": (1, 2),
        "mlp/down_proj": (1, 1),
    }, _KERNEL | _PRODUCTS | {"mixer_out"}),
    # The norm after a sublayer reads the sublayer's output in its backward.
    "norm-after": (_decoder(norm_after=True), (1, T, 128), {
        "mlp/gate_proj": (1, 2), "mlp/up_proj": (1, 2), "attn/o_proj": (1, 2),
        "mlp/down_proj": (1, 2),
    }, _KERNEL | _PRODUCTS | {"mixer_out", "ffn_out"}),
    # A write's backward reads what the sublayer gave it (the gradient of the
    # map it is spread by). A layer's second write is its output.
    "hyper-connections": (
        _decoder(hyper_connections=hyper_connections.HyperConnections()),
        (4, 1, T, 128), {
            "mlp/gate_proj": (1, 2), "mlp/up_proj": (1, 2),
            "attn/o_proj": (1, 2), "mlp/down_proj": (1, 2),
        }, _KERNEL | _PRODUCTS | {"mixer_out", "ffn_out", "hc_read", "hc_maps",
                                  "hc_write"}),
    # ``MLP(..., name="shared")`` inside the expert layer (models/mixtral.py).
    "shared-expert": (_decoder(tracing.MOE, **_SHARED), (1, T, 128), {
        "shared/gate_proj": (1, 2), "shared/up_proj": (1, 2),
        "attn/o_proj": (1, 2), "shared/down_proj": (1, 1),
    }, _KERNEL | _PRODUCTS | {"mixer_out"}),
}


def _handed_to_the_replays(jaxpr):
    """The names of the kept values each layer's replay takes: the operands of
    the gradient's ``remat2`` equations that a ``checkpoint_name`` made. JAX
    passes a kept float through ``reduce_precision`` on its way, and a kept
    operand of a jitted function (``nn.silu``) through that function's known
    half, which returns it as it came."""
    named = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            named[eqn.outvars[0]] = eqn.params["name"]
        elif eqn.primitive.name == "reduce_precision" and eqn.invars[0] in named:
            named[eqn.outvars[0]] = named[eqn.invars[0]]
        elif eqn.primitive.name == "jit":
            inner = eqn.params["jaxpr"].jaxpr
            came = dict(zip(inner.invars, eqn.invars))
            for out, inner_out in zip(eqn.outvars, inner.outvars):
                if came.get(inner_out) in named:
                    named[out] = named[came[inner_out]]
    return [
        sorted({named[v] for v in eqn.invars
                if isinstance(v, jax.extend.core.Var) and v in named})
        for eqn in jaxpr.eqns if eqn.primitive.name == "remat2"
    ]


def replay_holds_no_matmul_for_an_elementwise_consumer(case):
    layer, shape, matmuls, names = MATMUL_CASES[case]
    kept_jaxpr, kept = _gradient(layer, shape, remat_policy(_cfg(remat_prevent_cse=True)))
    bare_jaxpr, bare = _gradient(layer, shape, NOTHING)
    kept_dots = forward_matmuls(kept_jaxpr)
    bare_dots = forward_matmuls(bare_jaxpr)
    for name, (once, replayed) in matmuls.items():
        assert kept_dots[name] == LAYERS * once, kept_dots
        assert bare_dots[name] == LAYERS * replayed, bare_dots
    # Nothing else left the replay: the kernels' operands are made again.
    for name in set(bare_dots) - set(matmuls):
        assert kept_dots[name] == bare_dots[name], (name, kept_dots, bare_dots)
    assert _handed_to_the_replays(kept_jaxpr) == [sorted(names)] * LAYERS
    # (A hyper-connected layer's input is the write before it, whatever the
    # policy.)
    assert all(set(layer) <= {"hc_write"}
               for layer in _handed_to_the_replays(bare_jaxpr))
    _same_to_the_bit(kept, bare)
