"""Every Pallas kernel compiles for a v5e chip ahead of time, with no chip:
libtpu describes the topology and runs the real Mosaic and XLA:TPU
compilers. Catches a kernel the chip's compiler rejects before a chip run
is spent on it. Shapes are the ones chip_smoke.py and the benchmark run."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.attention import _flash_bwd_pallas, _flash_fwd_pallas
from ray_tpu.ops.gmm import gmm


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - libtpu absent or too old
        pytest.skip(f"libtpu gives no v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_for(sharding, fn, *args):
    """args are (shape, dtype) pairs; returns the optimized HLO text."""
    specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in args]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "custom-call" in text and "tpu_custom_call" in text
    return text


# (batch*heads, seq, head_dim, block): llama-1b b2 x s2048 as the model
# runs it; mixtral-small's head_dim; ring attention's 512 blocks.
FLASH_SHAPES = [(32, 2048, 128, 1024), (32, 2048, 64, 1024), (32, 2048, 128, 512)]


@pytest.mark.parametrize("bh,t,d,block", FLASH_SHAPES)
def test_flash_fwd_compiles_for_v5e(v5e, bh, t, d, block):
    qkv = ((bh, t, d), jnp.bfloat16)
    _compile_for(
        v5e,
        lambda q, k, v: _flash_fwd_pallas(
            q, k, v, causal=True, sm_scale=d**-0.5, block_q=block, block_k=block
        ),
        qkv, qkv, qkv,
    )


@pytest.mark.parametrize("bh,t,d,block", FLASH_SHAPES)
def test_flash_bwd_compiles_for_v5e(v5e, bh, t, d, block):
    qkv = ((bh, t, d), jnp.bfloat16)
    _compile_for(
        v5e,
        lambda q, k, v, o, lse, do: _flash_bwd_pallas(
            q, k, v, o, lse, do, causal=True, sm_scale=d**-0.5,
            block_q=block, block_k=block,
        ),
        qkv, qkv, qkv, qkv, ((bh, t), jnp.float32), qkv,
    )


# mixtral-small: b2 x s2048 tokens x top-2 pairs padded to 128-row tiles
# per expert, hidden 1024 <-> expert width 3584 (w_gate/w_up and w_down).
# OLMoE-1B-7B (the benchmark's dropless-4k cell): b2 x s4096 x top-8 pairs
# over 64 experts, hidden 2048 <-> expert width 1024.
@pytest.mark.parametrize("m,experts,k,n", [
    (2 * 2048 * 2 + 8 * 128, 8, 1024, 3584),
    (2 * 2048 * 2 + 8 * 128, 8, 3584, 1024),
    (2 * 4096 * 8 + 64 * 128, 64, 2048, 1024),
    (2 * 4096 * 8 + 64 * 128, 64, 1024, 2048),
])
def test_gmm_and_its_gradient_compile_for_v5e(v5e, m, experts, k, n):
    operands = (
        ((m, k), jnp.bfloat16), ((experts, k, n), jnp.bfloat16),
        ((m // 128,), jnp.int32),
    )
    _compile_for(v5e, gmm, *operands)
    # dlhs (the same kernel on transposed weights) and drhs (_tgmm).
    _compile_for(
        v5e,
        lambda lhs, rhs, tg: jax.grad(
            lambda lhs, rhs: gmm(lhs, rhs, tg).astype(jnp.float32).sum(),
            argnums=(0, 1),
        )(lhs, rhs),
        *operands,
    )
