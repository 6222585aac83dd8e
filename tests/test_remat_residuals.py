"""What the one remat policy keeps of the Pallas forward kernels.

Under ``nothing_saveable`` a layer's replay runs each custom-vjp forward rule
whole: the kernel that wrote o and lse (or o and the per-chunk states) runs a
second time only to hand its own backward kernels what it had written once.
Behind the barrier the replaying cells run (``prevent_cse=True``)
``models.llama.remat_policy`` keeps the values ``KERNEL_RESIDUALS`` names, and
the forward rules tag them. Here, on the CPU with the kernels interpreted,
through two ``nn.remat`` layers behind that barrier: the gradient's jaxpr
holds each forward kernel once a layer where ``nothing_saveable`` holds it
twice, and loss and gradients are the same to the bit.
"""
import ast
import pathlib

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu.models import hyper_connections
from ray_tpu.models.llama import KERNEL_RESIDUALS, LlamaConfig, remat_policy
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.kda import chunk_gdn, chunk_kda

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
        o = chunk_gdn(
            heads("q", self.dk), heads("k", self.dk), heads("v", self.dv, x.dtype),
            g, beta, heads("gate", self.dv, x.dtype), weight,
            scale=self.dk ** -0.5, rms_eps=1e-6,
        )
        return x + nn.Dense(c, use_bias=False, name="o")(o.reshape(b, t, -1))


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


class _Stack(nn.Module):
    layer: object  # (the layer's nn.Module class, its fields)
    policy: object

    @nn.compact
    def __call__(self, x):
        cls, fields = self.layer
        layer_cls = nn.remat(cls, prevent_cse=True, policy=self.policy)
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
    # A layer's second write is its output, which no replay makes.
    "hyper-connections": ((_HyperConnected, {}), (4, 1, T, 128), {
        "_hc_pre_fwd_kernel": (2, 4), "_hc_post_fwd_kernel": (2, 3)}),
}


def _kernel_calls(jaxpr, counts):
    """Every ``pallas_call`` of ``jaxpr`` and of the jaxprs its equations
    hold, by the kernel's name."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["jaxpr"].debug_info.func_name
            counts[name] = counts.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, counts)
    return counts


def _run(case, policy):
    layer, shape, _ = CASES[case]
    model = _Stack(layer, policy)
    x = jnp.asarray(np.random.RandomState(0).randn(*shape), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)
    grad = jax.value_and_grad(lambda p, x: model.apply(p, x), argnums=(0, 1))
    calls = _kernel_calls(jax.make_jaxpr(grad)(params, x).jaxpr, {})
    return calls, jax.jit(grad)(params, x)


@pytest.mark.parametrize("case", list(CASES))
def test_replay_holds_no_forward_kernel(case):
    forward = CASES[case][2]
    kept_calls, kept = _run(case, remat_policy(_cfg(remat_prevent_cse=True)))
    bare_calls, bare = _run(case, NOTHING)
    for kernel, (once, replayed) in forward.items():
        assert kept_calls[kernel] == LAYERS * once, kept_calls
        assert bare_calls[kernel] == LAYERS * replayed, bare_calls
    # Nothing but the forward kernels left the replay.
    for kernel in set(bare_calls) - set(forward):
        assert kept_calls[kernel] == bare_calls[kernel], (kept_calls, bare_calls)
    for a, b in zip(jax.tree_util.tree_leaves(kept), jax.tree_util.tree_leaves(bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dropped", [
    "kda_o", "kda_states", "kda_t", "gdn_o", "gdn_states", "gdn_t"])
def test_a_kda_layer_needs_each_of_its_three_names_kept(dropped):
    """o, the per-chunk states and the chunks' inverses leave the forward
    kernel together (KDA's, and the scalar-decay kernel's under names of its
    own): a policy that lacks any one of them runs the forward kernel in the
    replay to remake it, whatever else it holds."""
    case = dropped.partition("_")[0]
    fwd, bwd = f"_{case}_fwd_kernel", f"_{case}_bwd_kernel"
    names = [name for name in KERNEL_RESIDUALS if name != dropped]
    calls, _ = _run(case, jax.checkpoint_policies.save_only_these_names(*names))
    assert calls[fwd] == 2 * LAYERS and calls[bwd] == LAYERS
    kept, _ = _run(case, remat_policy(_cfg(remat_prevent_cse=True)))
    assert kept[fwd] == kept[bwd] == LAYERS


def test_the_other_policies_are_what_they_were():
    """``"dots"`` as ever; and without the barrier no replay is executed, so
    the names would cost memory and delete nothing (``remat_policy``)."""
    for barrier in (False, True):
        assert remat_policy(_cfg(remat_policy="dots", remat_prevent_cse=barrier)) is (
            jax.checkpoint_policies.checkpoint_dots)
    assert remat_policy(_cfg()) is NOTHING
    assert remat_policy(_cfg(remat_prevent_cse=True)) is not NOTHING


def _tagged_names():
    """The literal names ``checkpoint_name`` is called with anywhere in the
    package."""
    names = []
    for path in pathlib.Path(ray_tpu.__file__).parent.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "id", getattr(node.func, "attr", None))
                    == "checkpoint_name"):
                assert isinstance(node.args[1], ast.Constant), (path, node.lineno)
                names.append(node.args[1].value)
    return names


def test_every_name_is_tagged_and_every_tag_is_kept():
    tagged = _tagged_names()
    assert len(tagged) == len(set(tagged)), tagged  # a name has one site
    assert set(tagged) == set(KERNEL_RESIDUALS)
    assert len(KERNEL_RESIDUALS) == len(set(KERNEL_RESIDUALS))
