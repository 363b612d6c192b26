"""ops/ssd.py (the Mamba-2 scan in chunked SSD form), the convolution's bias
(ops/causal_conv.py) and attention at heads of 64 (ops/flash_attention.py),
on the CPU: the plain form and the kernels through the Pallas interpreter
against plain definitions — the scan against its token-by-token recurrence,
the convolution against its shifted adds, attention against
``dense_attention``. Shapes are the smallest that cross what the kernels
tile: two chunks of 128, two runs of two 64-wide heads in 128 lanes, one
B / C of 128 shared by all heads; the plain form at narrower heads runs in
tests/test_mamba.py. Their compilation for a v5e at the cells'
sizes is tests/test_ops.py's.

Tolerances. In float32 the chunked form and the recurrence do the same
arithmetic in another order: 1e-4 of each array's norm (read: 2e-6 to 5e-5,
the largest the decays' gradient at a strong decay). In bf16, the dtype the
cell runs, the kernels round x, B, C, y and the products' operands where the
recurrence is float32 throughout: 2e-2 (read: 1.2e-3 to 3e-3).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from brpc_tpu.ops import causal_conv, ssd

fa = importlib.import_module("brpc_tpu.ops.flash_attention")


def recurrence(xbc, dt, a, head_dim):
    """The definition, float32, a position at a time: per head from S = 0,
    S_t = exp(a_t) S_{t-1} + dt_t B_t x_t^T, y_t = S_t^T C_t."""
    b_, t, w = xbc.shape
    h, p = dt.shape[-1], head_dim
    n = (w - h * p) // 2
    xbc = xbc.astype(jnp.float32)
    x = xbc[..., :h * p].reshape(b_, t, h, p)
    bb, cc = xbc[..., h * p:h * p + n], xbc[..., h * p + n:]

    def step(s, xs):
        x_t, b_t, c_t, dt_t, a_t = xs
        s = (jnp.exp(a_t)[..., None, None] * s + dt_t[..., None, None]
             * b_t[:, None, :, None] * x_t[:, :, None, :])
        return s, jnp.einsum("bhnp,bn->bhp", s, c_t, precision="highest")

    mv = lambda z: jnp.moveaxis(z, 1, 0)      # noqa: E731
    _, y = lax.scan(step, jnp.zeros((b_, h, n, p)),
                    (mv(x), mv(bb), mv(cc), mv(dt), mv(a)))
    return mv(y).reshape(b_, t, h * p)


def _inputs(t=256, heads=4, head_dim=64, state=128, batch=1, decay=16.0,
            dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    xbc = jax.random.normal(k[0], (batch, t, heads * head_dim + 2 * state))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, t, heads)) - 1.0)
    a = dt * -jax.random.uniform(k[2], (heads,), minval=1.0, maxval=decay)
    dy = jax.random.normal(k[3], (batch, t, heads * head_dim))
    return xbc.astype(dtype), dt, a, dy


def _value_and_grads(fn, xbc, dt, a, dy):
    with jax.default_matmul_precision("highest"):
        y, vjp = jax.vjp(fn, xbc, dt, a)
        return (y.astype(jnp.float32), *(
            g.astype(jnp.float32) for g in vjp(dy.astype(y.dtype))))


def _gap(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


CASES = {
    # two chunks of 128, two runs of two heads of 64, N = 128
    "two_chunks_two_runs": dict(),
    # a decay of up to 200 a unit step, two sequences: whole chunks decayed
    # to nothing
    "strong_decay": dict(decay=200.0, batch=2),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    """(inputs, head width, the recurrence's y and gradients): the
    definition computed once a case."""
    spec = CASES[request.param]
    inputs = _inputs(**spec)
    head_dim = spec.get("head_dim", 64)
    want = _value_and_grads(
        lambda *z: recurrence(*z, head_dim), *inputs)
    return request.param, inputs, head_dim, want


def test_the_plain_form_is_the_recurrence(case):
    _, inputs, head_dim, want = case
    got = _value_and_grads(
        lambda *z: ssd.ssd(*z, head_dim=head_dim), *inputs)
    for name, g, w in zip(("y", "dxbc", "ddt", "da"), got, want):
        assert _gap(g, w) <= 1e-4, name


def test_the_kernels_interpreted_are_the_recurrence(case):
    _, inputs, head_dim, want = case
    assert ssd.kernels_take(inputs[0].shape, inputs[1].shape[-1], head_dim,
                            jnp.bfloat16)
    got = _value_and_grads(
        lambda *z: ssd.ssd(*z, head_dim=head_dim, interpret=True), *inputs)
    for what, g, w in zip(("y", "dxbc", "ddt", "da"), got, want):
        assert _gap(g, w) <= 1e-4, what


@pytest.mark.parametrize("case", ["two_chunks_two_runs"], indirect=True)
def test_the_kernels_in_bf16_against_the_recurrence(case):
    """The kernels' casts: bf16 operands, float32 states and sums."""
    _, (xbc, dt, a, dy), _, want = case
    got = _value_and_grads(
        lambda *z: ssd.ssd(*z, head_dim=64, interpret=True),
        xbc.astype(jnp.bfloat16), dt, a, dy)
    for name, g, w in zip(("y", "dxbc", "ddt", "da"), got, want):
        assert _gap(g, w) <= 2e-2, name


def test_what_the_kernels_take():
    bf16, f32 = jnp.bfloat16, jnp.float32
    cell = (1, 4096, 64 * 64 + 2 * 128)
    assert ssd.kernels_take(cell, 64, 64, bf16)
    assert not ssd.kernels_take(cell, 64, 64, f32)
    assert not ssd.kernels_take((1, 4000, cell[2]), 64, 64, bf16)
    assert not ssd.kernels_take((1, 4096, 64 * 64 + 2 * 64), 64, 64, bf16)
    assert not ssd.kernels_take((1, 4096, 3 * 64 + 256), 3, 64, bf16)
    assert ssd.kernels_take((1, 4096, 32 * 128 + 256), 32, 128, bf16)
    # heads of 64 two a run; narrower heads that do not fill whole tiles
    # one a run (only the plain form takes them)
    assert ssd.run_width(64, 64) == 2 and ssd.run_width(8, 16) == 8
    assert ssd.run_width(4, 16) == 1 and ssd.run_width(3, 64) == 1
    assert ssd.chunk_for(4096) == ssd.CHUNK == 128 and ssd.chunk_for(64) == 64


# -- the convolution's bias ---------------------------------------------------

def _conv_inputs():
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(k[0], (1, 256, 384)).astype(jnp.bfloat16)
    taps = jax.random.normal(k[1], (4, 256)) * 0.5
    bias = jax.random.normal(k[2], (256,)) * 0.5
    dy = jax.random.normal(k[3], (1, 256, 256))
    return x, taps, bias, dy


def test_conv_silu_with_a_bias_interpreted_is_the_plain_form():
    x, taps, bias, dy = _conv_inputs()

    def grads(fn):
        y, vjp = jax.vjp(fn, x, taps, bias)
        return (y.astype(jnp.float32),
                *(g.astype(jnp.float32) for g in vjp(dy.astype(y.dtype))))

    got = grads(lambda *z: causal_conv.conv_silu(*z, interpret=True))
    want = grads(lambda x, w, b: jax.nn.silu(causal_conv.causal_conv(
        x[..., :256], w) + b).astype(x.dtype))
    # y and dx are bf16, rounded once from float32 sums taken in another
    # order (a flip of the last bit here and there); dtaps and dbias float32
    for name, g, w, tol in zip(("y", "dx", "dtaps", "dbias"), got, want,
                               (5e-4, 5e-4, 1e-5, 1e-5)):
        assert _gap(g, w) <= tol, name


def test_conv_silu_without_a_bias_traces_as_it_did():
    """``bias=None`` is the kernels as a Gated DeltaNet layer runs them:
    the same jaxpr as leaving it out, three operands forward, six backward,
    one row a tap of the taps' sums."""
    x, taps, _, _ = _conv_inputs()

    def program(*bias):
        return jax.make_jaxpr(jax.grad(
            lambda x, w: causal_conv.conv_silu(x, w, *bias).astype(
                jnp.float32).sum(), argnums=(0, 1)))(x, taps)

    jaxpr = program()
    assert str(jaxpr) == str(program(None))
    kernels = [(eqn.params["name"], len(eqn.invars),
                [v.aval.shape for v in eqn.outvars])
               for eqn in _equations(jaxpr.jaxpr)
               if eqn.primitive.name == "pallas_call"]
    assert sorted(kernels) == [
        ("conv_silu_bwd", 6, [(1, 256, 256), (1, 4, 256)]),
        ("conv_silu_fwd", 3, [(1, 256, 256)])]


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


# -- attention at heads of 64 -------------------------------------------------

def test_attention_kernels_take_heads_of_64_and_agree_with_dense():
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(k[0], (1, 256, 4, 64)).astype(jnp.bfloat16)
    kk = jax.random.normal(k[1], (1, 256, 1, 64)).astype(jnp.bfloat16)
    v = jax.random.normal(k[2], (1, 256, 1, 64)).astype(jnp.bfloat16)
    do = jax.random.normal(k[3], (1, 256, 4 * 64)).astype(jnp.bfloat16)
    assert fa.supported((1, 4096, 32, 64), (1, 4096, 8, 64), jnp.bfloat16)
    assert fa.heads_together(4, 4096, 64, 64, jnp.bfloat16)

    def grads(fn, *operands):
        o, vjp = jax.vjp(fn, *operands)
        return (o.astype(jnp.float32),
                *(g.astype(jnp.float32) for g in vjp(do.astype(o.dtype))))

    got = grads(lambda *z: fa.flash_attention(
        *z, interpret=True, block_q=128, block_k=128), q, kk, v)
    want = grads(fa.dense_attention,
                 *(z.astype(jnp.float32) for z in (q, kk, v)))
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-2 * float(
            jnp.max(jnp.abs(w))), name
    assert np.isfinite(np.asarray(got[0])).all()
