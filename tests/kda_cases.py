"""What the test files of ``ray_tpu/ops/kda.py``'s six kernel families share
(``tests/test_kda_op.py``, ``test_gdn_op.py``, ``test_lightning_op.py``,
``test_ssd_op.py``, ``test_conv_silu_op.py``, ``test_conv_silu_bias_op.py``):
the batch, heads and widths, the norm and gate after a scan, a jaxpr's
``pallas_call``s, and the convolution's reference, inputs, gradients and
cases. A plain module: a piece imports what it reads by name.
"""
import jax
import jax.numpy as jnp

from ray_tpu.models.llama import RMSNorm
from ray_tpu.ops import kda


B, H, DK, DV = 2, 2, 32, 16
SCALE, RMS_EPS = DK ** -0.5, 1e-5
NAMES = "q k v g beta gate weight".split()


def gated_norm(o, gate, weight):
    """The mixer's way out of the recurrence before the kernels took it:
    ``RMSNorm`` over a head's channels, then the output gate."""
    normed = RMSNorm(RMS_EPS).apply({"params": {"scale": weight}}, o)
    return normed * jax.nn.sigmoid(gate)


def pallas_calls(jaxpr, found):
    """Every pallas_call equation in a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            pallas_calls(sub, found)
    return found


def pallas_outputs(jaxpr):
    """Number of outputs of every pallas_call in a jaxpr, nested ones too."""
    return [len(eqn.outvars) for eqn in pallas_calls(jaxpr, [])]


def heads_first(y, d):
    """``tokens_first`` undone (d None: nothing)."""
    return y if d is None else y.reshape(*y.shape[:2], -1, d).transpose(0, 2, 1, 3)


def conv_reference(x, w, dtype, heads=None):
    return heads_first(jax.nn.silu(kda.short_conv(x, w)).astype(dtype), heads)


def conv_inputs(batch, t, channels, dtype, seed=0, heads=None):
    """A projection, a filter and a cotangent of the output's dtype and
    layout."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    dy = jax.random.normal(keys[2], (batch, t, channels), jnp.float32).astype(dtype)
    return (jax.random.normal(keys[0], (batch, t, channels), jnp.float32),
            jax.random.uniform(keys[1], (4, channels), jnp.float32, -0.5, 0.5),
            heads_first(dy, heads))


def conv_and_gradients(fn, x, w, dy, **layout):
    y, vjp = jax.vjp(lambda x, w: fn(x, w, dy.dtype, **layout), x, w)
    return (y, *vjp(dy))


# (batch, tokens, channels, the output's dtype, the lanes of a head where the
# output lies heads first, the kernels' blocks or None): three blocks of 512
# rows and two of 128 lanes, every block eight tiles of 64 rows, so the halo
# crosses tile and block edges both ways; one tile of 16 rows, most of it the
# filter's reach from t < 0; two batch rows, over which and over whose blocks
# the filter's gradient adds up; v's rounding to bfloat16 (its cotangent
# arrives in bfloat16, 16 rows a sublane tile); and shapes that do not tile,
# in tokens and in channels. Heads first: Olmo-Hybrid's key heads, four of 96
# lanes to a block of 384 (a head's lanes begin inside a vreg), two blocks of
# rows and two of lanes; its value heads, two of 192 to a block, bfloat16 out
# and back, two batch rows of three blocks; heads of whole vregs; and 192
# channels, where no whole vregs are whole heads of 96.
CONV_CASES = {
    "three-blocks": (1, 1536, 256, jnp.float32, None, (512, 256, 64, True, 0)),
    "one-tile": (1, 16, 128, jnp.float32, None, (16, 128, 16, True, 0)),
    "batch-of-2": (2, 256, 128, jnp.float32, None, (256, 128, 64, True, 0)),
    "bfloat16-out": (2, 192, 128, jnp.bfloat16, None, (64, 128, 64, True, 0)),
    "tokens-do-not-tile": (2, 100, 128, jnp.float32, None, None),
    "lanes-do-not-tile": (2, 64, 96, jnp.bfloat16, None, None),
    "heads-of-96": (1, 1024, 768, jnp.float32, 96, (512, 384, 64, True, 96)),
    "heads-of-192-bfloat16": (2, 192, 384, jnp.bfloat16, 192, (64, 384, 64, True, 192)),
    "heads-of-128": (1, 256, 256, jnp.float32, 128, (256, 256, 64, True, 128)),
    "heads-do-not-tile": (2, 64, 192, jnp.float32, 96, None),
}


def conv_calls_text(t, channels, dtype, biased):
    """What ``conv_silu`` and its gradient trace to at a shape, kernels and
    all: each ``pallas_call``'s kernel as a jaxpr, its grid, and every
    operand's and result's block, index map and array."""
    x = jax.ShapeDtypeStruct((1, t, channels), jnp.float32)
    w = jax.ShapeDtypeStruct((4, channels), jnp.float32)
    b = [jax.ShapeDtypeStruct((channels,), jnp.float32)] * biased
    dy = jax.ShapeDtypeStruct((1, t, channels), dtype)
    both = jax.make_jaxpr(lambda x, w, dy, *b: jax.vjp(
        lambda x, w, *b: kda.conv_silu(x, w, dtype, *b), x, w, *b)[1](dy))(x, w, dy, *b)
    text = []
    for eqn in pallas_calls(both.jaxpr, []):
        mapping = eqn.params["grid_mapping"]
        text += [str(eqn.params["jaxpr"]), str(mapping.grid)]
        text += [f"{m.block_shape} {m.index_map_jaxpr} {m.array_aval}"
                 for m in mapping.block_mappings]
    return "\n".join(text)


# Read by this same code at the parent of the PR that gave ``conv_silu`` its
# ``heads`` (commit 2399a98), at the widths of the cells that call it without:
# Kimi-Linear's q and k (b1 x s16384, 32 heads of 128, float32 out) and its v
# (bfloat16 out and back), Solar-Open2's (b1 x s4096, 64 heads of 128) and
# Granite's biased pass over x, B and C (b1 x s8192, 4,352 channels, bfloat16).
CONV_BEFORE = {
    "kimi-linear-q-and-k": ((16384, 4096, jnp.float32, 0), "61ec1ae74855cfa9"),
    "kimi-linear-v": ((16384, 4096, jnp.bfloat16, 0), "53a9bfa1e55431ea"),
    "solar-open2-q-and-k": ((4096, 8192, jnp.float32, 0), "937a2f889da98451"),
    "granite-xbc": ((8192, 4352, jnp.bfloat16, 1), "88c48436988f48c2"),
}
