"""The hyper-connections' Pallas kernels (``ray_tpu/models/hyper_connections.py``)
under the interpreter, on the CPU: the read's and the write's outputs and
every cotangent against ``jax.grad`` of the written-out sums at shapes that
tile, and the written-out sums alone at shapes that do not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import hyper_connections
from ray_tpu.models.hyper_connections import (
    HyperConnection, HyperConnections, write_streams,
)


@pytest.fixture(scope="module", autouse=True)
def interpret():
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def one_connections_arrays(dtype, tokens, channels=256, n=4):
    """Streams, a sublayer's output, maps, Phi and a cotangent for each of
    the read's and the write's outputs, at [n, B, 128, channels]."""
    rng = np.random.default_rng(tokens)
    lead = (tokens // 128, 128) if tokens % 128 == 0 else (1, tokens)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)  # noqa: E731
    return {
        "x": normal(n, *lead, channels), "y": normal(*lead, channels),
        "post": jnp.asarray(rng.uniform(0, 2, (n, *lead)), jnp.float32),
        "res": jnp.asarray(rng.uniform(0, 1, (n, n, *lead)), jnp.float32),
        "phi": jnp.asarray(rng.normal(size=(n * channels, 2 * n + n * n)) * 0.3, dtype),
        "alpha": jnp.float32(0.9),
        "b_pre": jnp.asarray(rng.normal(size=n) * 0.5, jnp.float32),
        "dx": normal(n, *lead, channels), "du": normal(*lead, channels),
        "dh": jnp.asarray(rng.normal(size=(2 * n + n * n, *lead)), jnp.float32),
    }


def both_ways(a):
    """{name: (by the kernels, by the written-out sums and jax.grad)}: the
    read's and the write's outputs and every cotangent."""
    hcs, f32 = hyper_connections, jnp.float32

    def write(f):
        return f(a["x"], a["y"], a["post"], a["res"]), jax.grad(
            lambda *args: jnp.sum(f(*args).astype(f32) * a["dx"].astype(f32)),
            argnums=(0, 1, 2, 3))(a["x"], a["y"], a["post"], a["res"])

    def read(f):
        def loss(x, phi, alpha, b_pre):
            u, h, x = f(x, phi, alpha, b_pre, 1e-6)
            return (jnp.sum(u.astype(f32) * a["du"].astype(f32)) + jnp.sum(h * a["dh"])
                    + jnp.sum(x.astype(f32) * a["dx"].astype(f32)))
        args = (a["x"], a["phi"], a["alpha"], a["b_pre"])
        return f(*args, 1e-6)[:2], jax.grad(loss, argnums=(0, 1, 2, 3))(*args)

    names = ("write.out", "write.dx", "write.dy", "write.d_post", "write.d_res",
             "read.u", "read.h", "read.dx", "read.dphi", "read.dalpha", "read.db_pre")
    sides = []
    for w, r in ((hcs._write_by_kernels, hcs._read_by_kernels), (hcs._write, hcs._read)):
        (out, dw), ((u, h), dr) = write(w), read(r)
        sides.append((out, *dw, u, h, *dr))
    found = dict(zip(names, zip(*sides)))
    found["read.d_pre"] = (
        hcs._pre_sums(a["du"], a["x"]),
        jnp.einsum("btc,nbtc->nbt", a["du"].astype(f32), a["x"].astype(f32),
                   precision="highest"))
    return found


KERNEL_CASES = [(dtype, tokens) for dtype in ("float32", "bfloat16") for tokens in (128, 256)]


@pytest.fixture(scope="module", params=KERNEL_CASES, ids=lambda c: f"{c[0]}-{c[1]}")
def kernels_and_autodiff(request):
    dtype, tokens = request.param
    arrays = one_connections_arrays(jnp.dtype(dtype), tokens)
    assert hyper_connections._by_kernels(arrays["x"])
    return both_ways(arrays)


@pytest.mark.parametrize("name", [
    "write.out", "write.dx", "write.dy", "write.d_post", "write.d_res",
    "read.u", "read.h", "read.d_pre", "read.dx", "read.dphi", "read.dalpha", "read.db_pre"])
def test_a_connections_kernels_give_what_autodiff_of_the_sums_gives(
        kernels_and_autodiff, name):
    got, want = kernels_and_autodiff[name]
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    top = np.abs(want).max()
    assert top > 1e-2
    # a bfloat16 array is rounded once on either side, after sums in another
    # order: two of the largest entry's last places; float32 to its rounding
    places = 2.0 ** -6 if kernels_and_autodiff["write.dx"][0].dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got, want, rtol=places, atol=places * top)


@pytest.mark.parametrize("shape", [(1, 120, 256), (1, 128, 192)], ids=["tokens", "channels"])
def test_a_shape_that_does_not_tile_takes_the_written_out_sums(shape):
    """The path is chosen by shape alone: 120 tokens or 192 channels leave no
    Pallas call in forward or backward."""
    def pallas_calls(a):
        module = HyperConnection(
            HyperConnections(), 1e-6, jax.nn.initializers.normal(0.3), jnp.float32)
        params = module.init(jax.random.PRNGKey(0), a["x"])["params"]

        def loss(x, y):
            u, x, (post, res) = module.apply({"params": params}, x, streams=True)
            return jnp.sum(write_streams(x, y + u, post, res))

        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(a["x"], a["y"])).count(
            "pallas_call")

    a = one_connections_arrays(jnp.float32, shape[1], shape[2])
    assert not hyper_connections._by_kernels(a["x"])
    assert pallas_calls(a) == 0
    # read, write, and the backward's three
    assert pallas_calls(one_connections_arrays(jnp.float32, 128)) == 5
