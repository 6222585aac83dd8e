"""Mesh/sharding layer + ring attention vs dense oracle on the 8-device
CPU mesh (the reference tests multi-node on one box the same way —
cluster_utils; here virtual XLA devices stand in for chips)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import attention_reference, flash_attention
from ray_tpu.parallel import MeshSpec, logical_sharding
from ray_tpu.parallel.mesh import logical_to_spec


def test_mesh_spec_build():
    spec = MeshSpec(data=2, seq=2, tensor=2)
    mesh = spec.build()
    assert mesh.shape == {"data": 2, "fsdp": 1, "seq": 2, "tensor": 2, "expert": 1}


def test_mesh_spec_too_many_devices():
    with pytest.raises(ValueError):
        MeshSpec(data=16).build()


def test_logical_to_spec():
    assert logical_to_spec(("batch", "seq", "embed")) == P(("data", "fsdp"), "seq", "fsdp") or True
    # embed after batch: fsdp already used by batch -> embed replicates
    spec = logical_to_spec(("batch", "seq", "embed"))
    assert spec[0] == ("data", "fsdp")
    assert spec[1] == "seq"
    assert spec[2] is None  # fsdp consumed by batch


def test_logical_sharding_placement():
    mesh = MeshSpec(data=4, tensor=2).build()
    x = jnp.zeros((8, 16))
    sharded = jax.device_put(x, logical_sharding(mesh, ("batch", "mlp")))
    assert sharded.sharding.spec[1] == "tensor"


def test_flash_matches_reference_cpu():
    # On CPU flash_attention falls back to the reference path; exercise the
    # dispatch and GQA handling.
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 8, 64, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 64, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 64, 32))
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def ringed(mesh, q, k, v, causal):
    """``flash_attention`` under the ambient ``mesh``, which has to take the
    ring: a neighbour exchange is in what it traces."""
    with jax.set_mesh(mesh):
        attend = functools.partial(flash_attention, causal=causal)
        assert "ppermute" in str(jax.make_jaxpr(attend)(q, k, v))
        return attend(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = MeshSpec(seq=4).build()
    b, h, t, d = 2, 4, 128, 16
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, t, d), jnp.float32)
        for i in range(3)
    )
    out = ringed(mesh, q, k, v, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_grads_match_dense():
    mesh = MeshSpec(seq=4).build()
    b, h, t, d = 1, 2, 64, 8
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, t, d), jnp.float32)
        for i in range(3)
    )

    def loss_ring(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    with jax.set_mesh(mesh):
        grad = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2))).lower(q, k, v).compile()
        assert "collective-permute" in grad.as_text()
        g_ring = grad(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5)


def test_ring_attention_gqa():
    mesh = MeshSpec(seq=2).build()
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 64, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 64, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 64, 16))
    out = ringed(mesh, q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_pallas_interpret_matches_reference():
    # Run the actual pallas kernel in interpreter mode on CPU.
    from ray_tpu.ops import attention as A

    q = jax.random.normal(jax.random.PRNGKey(0), (4, 96, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (4, 96, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (4, 96, 32), jnp.float32)
    import jax.experimental.pallas as pl  # noqa: F401
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        o, lse = A._forward_call(
            A._causal_mask(q, k, v, True, 32, 32), q, k, v, 0.25
        )
    # Treat the leading dim as heads of a single batch element.
    ref = attention_reference(q[None], k[None], v[None], causal=True, sm_scale=0.25)[0]
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)


def test_flash_pallas_backward_matches_reference_grads():
    """dq/dk/dv from the pallas backward kernels vs autodiff through the
    XLA reference (interpret mode on CPU; the same kernels run compiled
    on the chip)."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import attention as A

    q = jax.random.normal(jax.random.PRNGKey(3), (2, 96, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(4), (2, 96, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(5), (2, 96, 32), jnp.float32)
    do = jax.random.normal(jax.random.PRNGKey(6), (2, 96, 32), jnp.float32)

    def ref_out(q, k, v):
        o = attention_reference(
            q[None], k[None], v[None], causal=True, sm_scale=0.25
        )[0]
        return jnp.sum(o * do)

    dq_ref, dk_ref, dv_ref = jax.grad(ref_out, argnums=(0, 1, 2))(q, k, v)

    with pltpu.force_tpu_interpret_mode():
        mask = A._causal_mask(q, k, v, True, 32, 32)
        o, lse = A._forward_call(mask, q, k, v, 0.25)
        dq, dk, dv = A._backward_call(mask, q, k, v, o, lse, do, 0.25)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_ref), atol=2e-4)


def test_latent_attention_rings_where_the_mesh_splits_the_sequence():
    """``MLAMixer`` takes the road the dense mixer takes: under seq=2 it
    rings and agrees with one device where v's heads are as wide as q's,
    and says so where they are not (the ring's blocks are one width)."""
    from ray_tpu.models.mla import MLAConfig, MLAMixer

    widths = dict(
        hidden_size=64, num_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, mla_rope=True, dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    mesh = MeshSpec(seq=2).build()

    mixer = MLAMixer(MLAConfig(v_head_dim=16, **widths))
    params = mixer.init(jax.random.PRNGKey(1), x, positions)
    alone = mixer.apply(params, x, positions)
    with jax.set_mesh(mesh):
        assert "ppermute" in str(jax.make_jaxpr(mixer.apply)(params, x, positions))
        split = mixer.apply(params, x, positions)
    np.testing.assert_allclose(np.asarray(split), np.asarray(alone), atol=2e-5)

    narrow = MLAMixer(MLAConfig(v_head_dim=8, **widths))
    params = narrow.init(jax.random.PRNGKey(1), x, positions)
    with jax.set_mesh(mesh), pytest.raises(ValueError, match="splits the sequence"):
        narrow.apply(params, x, positions)
