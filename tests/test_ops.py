"""Pallas kernel tests. Numerics run in interpret mode on the CPU; that the
same kernel reaches the TPU compiler is checked without a chip, by lowering
for the TPU platform and by compiling ahead of time for a v5e topology
(libtpu compiles without devices). The compiled kernel's numerics on the
chip are chip_smoke.py's ``kernel`` phase."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import llama
from brpc_tpu.ops import flash_attention


def _inputs(key, b=2, t=128, hq=4, hkv=2, d=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, hq, d), dtype)
    k = jax.random.normal(kk, (b, t, hkv, d), dtype)
    v = jax.random.normal(kv, (b, t, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = _inputs(jax.random.PRNGKey(0))
    want = llama.attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_uneven_blocks():
    q, k, v = _inputs(jax.random.PRNGKey(1), t=64)
    want = llama.attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, block_q=16, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# The shapes of the numeric tests above, then Llama-3-8B's head geometry.
_TPU_SHAPES = [
    dict(t=128, block_q=32, block_k=32),
    dict(t=64, block_q=16, block_k=64),
    dict(t=64, block_q=32, block_k=32, dtype=jnp.bfloat16),
    dict(b=1, t=2048, hq=32, hkv=8, d=128, dtype=jnp.bfloat16),
]


def _abstract_inputs(sharding=None, b=2, t=128, hq=4, hkv=2, d=32,
                     dtype=jnp.float32, **blocks):
    q = jax.ShapeDtypeStruct((b, t, hq, d), dtype, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, t, hkv, d), dtype, sharding=sharding)
    return (q, kv, kv), blocks


@pytest.mark.parametrize("shape", _TPU_SHAPES)
def test_flash_lowers_for_tpu(shape):
    """interpret=False must get past Pallas' TPU block-shape rules (the
    last two block dimensions divisible by 8 and 128, or whole) and become
    a Mosaic custom call."""
    args, blocks = _abstract_inputs(**shape)
    lowered = flash_attention.trace(*args, interpret=False, **blocks).lower(
        lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies

    # Compiling ahead of time opens no device, so this process need not be
    # libtpu's only one on the host: left alone, libtpu takes
    # /tmp/libtpu_lockfile while it loads and a concurrent loader (another
    # test run) aborts on it.
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    return topo.devices[0]


@pytest.mark.parametrize("shape", _TPU_SHAPES)
def test_flash_compiles_for_v5e(v5e_device, shape):
    """XLA:TPU and Mosaic accept the kernel for the chip the fabric runs
    on."""
    args, blocks = _abstract_inputs(
        jax.sharding.SingleDeviceSharding(v5e_device), **shape)
    compiled = flash_attention.trace(*args, interpret=False,
                                     **blocks).lower().compile()
    assert "custom-call" in compiled.as_text()


def test_flash_bf16():
    q, k, v = _inputs(jax.random.PRNGKey(2), t=64, dtype=jnp.bfloat16)
    want = llama.attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2)
