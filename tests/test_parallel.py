"""Collective/parallelism tests on the 8-device CPU mesh (SURVEY §4: the
loopback-multi-node pattern — virtual devices stand in for chips; the
driver separately dry-runs the real multi-chip path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from brpc_tpu.ops.flash_attention import attention
from brpc_tpu.parallel import (
    CollectiveChannel,
    make_mesh,
    pipeline_apply,
    ring_attention,
    ulysses_attention,
)


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh({"dp": 8})


def test_all_reduce(mesh8):
    chan = CollectiveChannel(mesh8, "dp")
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    out = jax.jit(chan.all_reduce)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).sum(0))


def test_all_gather_identity(mesh8):
    chan = CollectiveChannel(mesh8, "dp")
    x = jnp.arange(32, dtype=jnp.float32).reshape(16, 2)
    out = jax.jit(chan.all_gather)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_reduce_scatter_then_gather(mesh8):
    chan = CollectiveChannel(mesh8, "dp")
    x = jnp.arange(64, dtype=jnp.float32).reshape(16, 4)
    rs = jax.jit(chan.reduce_scatter)(x)
    # replicated input summed 8x, scattered: gathering returns 8*x
    back = jax.jit(chan.all_gather)(rs)
    np.testing.assert_allclose(np.asarray(back), 8 * np.asarray(x))


def test_shift_ring(mesh8):
    chan = CollectiveChannel(mesh8, "dp")
    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    out = jax.jit(lambda a: chan.shift(a, 1))(x)
    # device i's value moves to device i+1 (ring)
    np.testing.assert_allclose(
        np.asarray(out).ravel(), np.roll(np.arange(8), 1)
    )


def test_map_reduce(mesh8):
    chan = CollectiveChannel(mesh8, "dp")
    x = jnp.ones((8, 4), jnp.float32)
    out = jax.jit(
        lambda a: chan.map_reduce(lambda s: jnp.sum(s * 2), a)
    )(x)
    assert float(out) == 64.0


def _attn_inputs(key, b=2, t=64, hq=4, hkv=2, d=8):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, hq, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, hkv, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = make_mesh({"sp": 8})
    q, k, v = _attn_inputs(jax.random.PRNGKey(0))
    want = attention(q, k, v, causal=causal)
    got = jax.jit(
        lambda q, k, v: ring_attention(
            q, k, v, mesh=mesh, axis="sp", causal=causal
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_dense(causal):
    mesh = make_mesh({"sp": 2})
    q, k, v = _attn_inputs(jax.random.PRNGKey(1))
    want = attention(q, k, v, causal=causal)
    got = jax.jit(
        lambda q, k, v: ulysses_attention(
            q, k, v, mesh=mesh, axis="sp", causal=causal
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_grads():
    mesh = make_mesh({"sp": 4})
    q, k, v = _attn_inputs(jax.random.PRNGKey(2), t=32)

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_attention(q, k, v, mesh=mesh, axis="sp") ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v) ** 2)

    # dq, dk and dv: the running max is held constant in the backward pass,
    # which must leave all three exact.
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-3)


def test_pipeline_matches_sequential():
    mesh = make_mesh({"pp": 4})
    n_stages, width = 4, 16
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (n_stages, width, width), jnp.float32) * 0.3

    def stage_fn(params, x):
        return jnp.tanh(x @ params)

    x = jax.random.normal(jax.random.PRNGKey(4), (16, width), jnp.float32)
    want = x
    for s in range(n_stages):
        want = stage_fn(w[s], want)
    got = jax.jit(
        lambda w, x: pipeline_apply(
            stage_fn, w, x, mesh=mesh, axis="pp", microbatches=8
        )
    )(w, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
