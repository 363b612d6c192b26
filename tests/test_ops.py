"""Pallas kernel tests. Numerics run in interpret mode on the CPU; that the
same kernels reach the TPU compiler is checked without a chip, by lowering
for the TPU platform and by compiling ahead of time for a v5e topology
(libtpu compiles without devices). The compiled kernels' numerics on the
chip are chip_smoke.py's ``kernel`` phase. Last, the choice
``llama.attention`` makes between the kernels and the dense form."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from brpc_tpu import obs
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


# -- the backward pass ------------------------------------------------------

def _weighted(attn, w):
    """A scalar of attention whose cotangent is not constant."""
    return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)


@pytest.mark.parametrize("blocks", [(32, 32), (16, 64)],
                         ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("group", [4, 1])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
def test_flash_grad_matches_dense(dtype, tol, group, causal, blocks):
    """dQ, dK, dV of the kernels against autodiff of the dense form. The
    bf16 tolerance is relative to each gradient's largest element: both
    sides round p and dS to 8 mantissa bits, at different places."""
    q, k, v = _inputs(jax.random.PRNGKey(3), b=1, hq=4, hkv=4 // group,
                      dtype=dtype)
    w = jax.random.normal(jax.random.PRNGKey(4), (1, 128, 4 * 32))
    got = jax.grad(_weighted(functools.partial(
        flash_attention, causal=causal, block_q=blocks[0], block_k=blocks[1],
        interpret=True), w), (0, 1, 2))(q, k, v)
    want = jax.grad(_weighted(functools.partial(
        llama.dense_attention, causal=causal), w), (0, 1, 2))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        assert g.dtype == r.dtype == dtype and g.shape == r.shape, name
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.max(np.abs(g - r)) <= tol * np.max(np.abs(r)), name


def _grad_of_sum(**blocks):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=False, **blocks)
                       .astype(jnp.float32))
    return jax.jit(jax.grad(loss, (0, 1, 2)))


@pytest.mark.parametrize("shape", _TPU_SHAPES)
def test_flash_backward_lowers_for_tpu(shape):
    """Forward, dQ and dK/dV kernels: three Mosaic custom calls."""
    args, blocks = _abstract_inputs(**shape)
    lowered = _grad_of_sum(**blocks).trace(*args).lower(
        lowering_platforms=("tpu",))
    assert lowered.as_text().count("stablehlo.custom_call @tpu_custom_call") \
        == 3


@pytest.mark.parametrize("shape", _TPU_SHAPES)
def test_flash_backward_compiles_for_v5e(v5e_device, shape):
    args, blocks = _abstract_inputs(
        jax.sharding.SingleDeviceSharding(v5e_device), **shape)
    compiled = _grad_of_sum(**blocks).trace(*args).lower().compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


# -- the choice llama.attention makes ---------------------------------------

# Kernel-eligible and small: 4 heads of 128 over 2 KV heads, bf16, and a
# sequence (three tiles of 128) that is no weight's dimension, so that an
# array with two trailing dimensions of T can only be a score matrix.
_T = 384
_ELIGIBLE = llama.LlamaConfig(vocab_size=1024, hidden=512, n_layers=2,
                              n_heads=4, n_kv_heads=2, head_dim=128,
                              intermediate=1024)


def _abstract_step(cfg, sharding=None):
    optimizer = optax.adamw(1e-4)
    params = jax.eval_shape(lambda k: llama.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    state = (params, jax.eval_shape(optimizer.init, params),
             jax.ShapeDtypeStruct((1, _T), jnp.int32))
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        state)
    return jax.jit(llama.make_train_step(cfg, optimizer)).trace(*state)


@pytest.fixture
def lowerings():
    """Reads (kernel, dense) lowerings counted since the test began."""
    obs.set_enabled(True)       # other modules' tests leave it off
    names = ("attn_kernel_lowerings", "attn_dense_lowerings")
    before = [obs.counter(n).get_value() for n in names]
    return lambda: tuple(obs.counter(n).get_value() - b
                         for n, b in zip(names, before))


def test_train_step_takes_the_kernel_on_tpu(v5e_device, lowerings):
    text = _abstract_step(
        _ELIGIBLE, jax.sharding.SingleDeviceSharding(v5e_device)
    ).lower().compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    assert re.findall(rf"\w+\[[\d,]*{_T},{_T}\]", text) == []
    assert lowerings() == (1, 0)


def test_train_step_stays_dense_on_cpu(lowerings):
    text = _abstract_step(_ELIGIBLE).lower(
        lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in text
    assert re.search(rf"tensor<[\dx]*{_T}x{_T}xf32>", text)
    assert lowerings() == (0, 1)


@pytest.mark.parametrize("change", [dict(dtype=jnp.float32),
                                    dict(head_dim=32)],
                         ids=["float32", "head_dim32"])
def test_ineligible_operands_stay_dense_on_tpu(change, lowerings):
    text = _abstract_step(dataclasses.replace(_ELIGIBLE, **change)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text
    assert lowerings() == (0, 1)


def test_one_step_through_the_kernel_matches_dense():
    """Loss and every gradient leaf's norm of the small model, the kernels
    (interpreted) against the dense form, to what bf16 allows: the two round
    the scores and the probabilities at different places."""
    params = llama.init_params(jax.random.PRNGKey(5), _ELIGIBLE)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, _T), 0,
                                _ELIGIBLE.vocab_size)
    grad = jax.jit(jax.value_and_grad(llama.loss_fn), static_argnums=(2, 3))
    loss, grads = grad(params, tokens, _ELIGIBLE,
                       functools.partial(flash_attention, interpret=True))
    want_loss, want = grad(params, tokens, _ELIGIBLE, llama.dense_attention)
    assert abs(float(loss) - float(want_loss)) <= 2e-3 * float(want_loss)
    norms = jax.tree_util.tree_map(
        lambda g, w: (float(jnp.linalg.norm(g)), float(jnp.linalg.norm(w))),
        grads, want)
    for path, (g, w) in jax.tree_util.tree_leaves_with_path(
            norms, is_leaf=lambda x: isinstance(x, tuple)):
        assert abs(g - w) <= 2e-2 * w, (jax.tree_util.keystr(path), g, w)
