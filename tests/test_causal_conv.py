"""The short convolution and its silu (ops/causal_conv.py): the two Pallas
kernels through the interpreter against the plain form they replace — value
and the gradients of x and the taps — and against the formulas written out in
float64; what crosses from one block of positions to the next, in both
directions; the zeros before the first position and after the last; the
operands the kernels do not take, which run the plain form to the numbers the
layer had; and which form a program lowered for a platform holds. That Mosaic
and XLA:TPU take the kernels at the benchmark cell's size is compiled in
tests/test_ops.py, beside the other kernels (one file loads libtpu)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu import obs
from brpc_tpu.ops import causal_conv
from brpc_tpu.ops.causal_conv import conv_silu

K = 4


def _inputs(b=2, t=256, c=256, w=None, dtype=jnp.bfloat16, seed=0):
    """x [b, t, w] (w past the c convolved channels where given), taps
    [K, c] float32, a cotangent of the result."""
    kx, kt, kd = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kx, (b, t, w or c), dtype),
            jax.random.normal(kt, (K, c), jnp.float32) * 0.5,
            jax.random.normal(kd, (b, t, c), dtype))


def _before_the_kernels(x, taps):
    """What ``hybrid.gated_delta_net`` ran under ``gdn.conv`` before the
    kernels, written out again."""
    k, t = taps.shape[0], x.shape[1]
    x = x[..., :taps.shape[1]]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    return jax.nn.silu(sum(padded[:, j:j + t] * taps[j].astype(jnp.float32)
                           for j in range(k))).astype(x.dtype)


def _float64(x, taps, dy):
    """y, dx and dtaps by the formulas, in numpy float64."""
    x, taps, dy = (np.asarray(a, np.float64) for a in (x, taps, dy))
    k, c = taps.shape
    t = x.shape[1]
    x = x[..., :c]
    padded = np.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    pre = sum(padded[:, j:j + t] * taps[j] for j in range(k))
    sig = 1.0 / (1.0 + np.exp(-pre))
    dpre = dy * sig * (1.0 + pre * (1.0 - sig))
    ahead = np.pad(dpre, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(taps[j] * ahead[:, k - 1 - j:k - 1 - j + t] for j in range(k))
    dtaps = np.stack([np.sum(dpre * padded[:, j:j + t], axis=(0, 1))
                      for j in range(k)])
    return pre * sig, dx, dtaps


def _ulps(a, b):
    """How many bf16 values apart, elementwise (neither near a change of
    sign here: the larger of the two readings is what is held to one)."""
    bits = lambda v: np.asarray(v).view(np.int16).astype(np.int32)  # noqa: E731
    return np.abs(bits(a) - bits(b))


def _grads(f, x, taps, dy):
    y, vjp = jax.vjp(f, x, taps)
    return (y, *vjp(dy))


def _interpreted(x, taps):
    return conv_silu(x, taps, interpret=True)


# 256 positions are one block; 384 are three of 128 (the largest block that
# divides), the last case in an x wider than the convolved channels (the
# layer's q | k | v | z)
CASES = {"one_block": dict(), "three_blocks": dict(t=384),
         "three_blocks_wider_x": dict(t=384, w=384)}
BLOCK = 128              # of the cases of 384 positions


@pytest.fixture(scope="module", params=sorted(CASES))
def both(request):
    """(the kernels' y, dx, dtaps; the plain form's; the inputs)."""
    x, taps, dy = _inputs(**CASES[request.param])
    return (_grads(_interpreted, x, taps, dy),
            _grads(causal_conv._plain, x, taps, dy), (x, taps, dy))


def test_forward_is_the_plain_forms_to_a_bf16_ulp(both):
    """One bf16 value apart at most, and rarely (the CPU's compiler fuses a
    multiply and an add where the interpreter does not); a pre-activation
    that cancels to nearly nothing is held by its size, not its bits."""
    (y, _, _), (y_plain, _, _), _ = both
    assert y.dtype == jnp.bfloat16 and y.shape == y_plain.shape
    apart = _ulps(y, y_plain)
    big = np.abs(np.asarray(y_plain, np.float32)) > 1e-3
    assert apart[big].max() <= 1
    assert (apart > 0).mean() < 0.01
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_plain, np.float32),
                               rtol=2 ** -7, atol=1e-5)


def test_gradient_of_x_is_the_plain_forms(both):
    """Rounded once to bf16 from float32 sums in another order: a value
    apart at most, and where it is x's cotangent past the convolved
    channels, zero."""
    (_, dx, _), (_, dx_plain, _), (x, taps, _) = both
    assert dx.dtype == x.dtype and dx.shape == x.shape
    c = taps.shape[1]
    assert not np.asarray(dx[..., c:], np.float32).any()
    big = np.abs(np.asarray(dx_plain, np.float32)) > 1e-3
    assert _ulps(dx, dx_plain)[big].max() <= 1
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(dx_plain, np.float32),
                               rtol=2 ** -7, atol=1e-5)


def test_gradient_of_the_taps_is_a_float32_sum(both):
    (_, _, dw), (_, _, dw_plain), _ = both
    assert dw.dtype == jnp.float32
    scale = np.abs(np.asarray(dw_plain)).max()
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_plain),
                               rtol=0, atol=1e-5 * scale)


def test_value_and_gradients_follow_the_formulas_through_silu(both):
    """Against float64 numpy: y and dx inside bf16's rounding, dtaps — a
    float32 sum of float32 dpre, never rounded to bf16 — to 1e-5."""
    (y, dx, dw), _, (x, taps, dy) = both
    y64, dx64, dw64 = _float64(x, taps, dy)
    c = taps.shape[1]
    np.testing.assert_allclose(np.asarray(y, np.float64), y64, rtol=2 ** -8,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(dx[..., :c], np.float64), dx64,
                               rtol=2 ** -8, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw, np.float64), dw64, rtol=0,
                               atol=1e-5 * np.abs(dw64).max())


@pytest.mark.parametrize("block", [1, 2], ids=["first", "second"])
def test_a_spike_in_a_blocks_last_row_reaches_the_next_three(block):
    """x is 1 at the last position t of a block: y_t is silu(taps[3]) and
    the next block's rows 0, 1, 2 silu(taps[2]), silu(taps[1]),
    silu(taps[0]); nothing anywhere else."""
    _, taps, _ = _inputs()
    at = block * BLOCK - 1
    x = jnp.zeros((1, 384, 256), jnp.bfloat16).at[0, at].set(1)
    y = np.asarray(_interpreted(x, taps), np.float32)
    want = np.zeros_like(y)
    for j in range(K):
        want[0, at + (K - 1 - j)] = np.asarray(
            jax.nn.silu(taps[j]).astype(jnp.bfloat16), np.float32)
    np.testing.assert_array_equal(y, want)


@pytest.mark.parametrize("block", [1, 2], ids=["second", "third"])
def test_a_cotangent_in_a_blocks_first_row_reaches_the_three_before(block):
    """x = 0, so silu'(pre) = 1/2 everywhere; dy is 1 at a block's first
    position t: dx_{t-3+j} = taps[j] / 2, the three rows before the block
    and its own first, and nothing anywhere else."""
    _, taps, _ = _inputs()
    at = block * BLOCK
    x = jnp.zeros((1, 384, 256), jnp.bfloat16)
    dy = jnp.zeros((1, 384, 256), jnp.bfloat16).at[0, at].set(1)
    _, dx, dw = _grads(_interpreted, x, taps, dy)
    want = np.zeros(x.shape, np.float32)
    for j in range(K):
        want[0, at - (K - 1) + j] = np.asarray(
            (taps[j] / 2).astype(jnp.bfloat16), np.float32)
    np.testing.assert_array_equal(np.asarray(dx, np.float32), want)
    assert not np.asarray(dw).any()


@pytest.mark.parametrize("t", [256, 384])
def test_the_first_block_sees_zeros_before_position_0(t):
    """The tile before the first block is clamped onto the block's own
    first rows; were it not taken as zeros, y_0 would hold x_13..x_15."""
    x, taps, _ = _inputs(b=1, t=t)
    y = _interpreted(x, taps)
    x32 = x.astype(jnp.float32)
    for t in range(K - 1):
        pre = sum(taps[K - 1 - s] * x32[0, t - s] for s in range(t + 1))
        assert _ulps(y[0, t], jax.nn.silu(pre).astype(jnp.bfloat16)).max() \
            <= 1, t


@pytest.mark.parametrize("t", [256, 384])
def test_the_last_block_sees_no_cotangent_after_the_end(t):
    """The tile after the last block is clamped onto the block's own last
    rows; dx of the last position is taps[3] dpre_T-1 alone."""
    x, taps, dy = _inputs(b=1, t=t)
    _, dx, _ = _grads(_interpreted, x, taps, dy)
    _, dx64, _ = _float64(x, taps, dy)
    np.testing.assert_allclose(np.asarray(dx[0, -K:], np.float64),
                               dx64[0, -K:], rtol=2 ** -8, atol=1e-6)


NOT_TAKEN = {
    "float32_x": dict(dtype=jnp.float32),
    "three_channels": dict(c=3, t=16),
    "no_whole_block_of_positions": dict(t=200),
}


@pytest.mark.parametrize("case", sorted(NOT_TAKEN))
def test_operands_the_kernels_do_not_take_run_the_plain_form(case):
    """To the bit what the layer gave before the kernels, value and both
    gradients; and forcing the kernels on them is refused."""
    x, taps, dy = _inputs(**NOT_TAKEN[case])
    assert not causal_conv.kernels_take(x.shape, taps.shape, x.dtype)
    jaxpr = str(jax.make_jaxpr(conv_silu)(x, taps))
    assert "pallas_call" not in jaxpr and "platform_index" not in jaxpr
    for got, want in zip(_grads(conv_silu, x, taps, dy),
                         _grads(_before_the_kernels, x, taps, dy)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    with pytest.raises(ValueError, match="do not take"):
        conv_silu(x, taps, interpret=True)


def test_kernels_take_bf16_whole_lanes_and_whole_blocks():
    take = causal_conv.kernels_take
    bf16 = jnp.bfloat16
    assert take((1, 8192, 12288), (4, 8192), bf16)        # the cell's
    assert take((2, 256, 256), (4, 256), bf16)
    assert take((2, 256, 256), (8, 256), bf16)
    assert not take((2, 256, 256), (9, 256), bf16)        # K past a tile
    assert not take((1, 8192, 12288), (4, 8192), jnp.float32)
    assert not take((1, 8192, 8192), (4, 8200), bf16)     # no whole lanes
    assert not take((1, 8192, 4096), (4, 8192), bf16)     # taps wider than x
    assert not take((1, 8200, 12288), (4, 8192), bf16)    # no whole block


def _lowered(dtype, platform, c=256):
    x, taps, _ = (jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for a in _inputs(b=1, c=c, w=c + 128, dtype=dtype))
    # the value too: the gradient alone needs no y, and no forward kernel
    grad = jax.jit(jax.value_and_grad(
        lambda x, t: jnp.sum(conv_silu(x, t).astype(jnp.float32)),
        argnums=(0, 1)))
    return grad.trace(x, taps).lower(lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("dtype,platform,c,kernels", [
    (jnp.bfloat16, "tpu", 256, True), (jnp.bfloat16, "cpu", 256, False),
    (jnp.float32, "tpu", 256, False), (jnp.bfloat16, "tpu", 192, False)])
def test_program_holds_the_kernels_only_on_tpu_at_shapes_they_take(
        dtype, platform, c, kernels):
    """One traced function for every platform; the choice is settled when
    the program is lowered, and ``conv_lowerings`` counts the programs that
    kept the kernels (once: the forward and the backward choice count the
    same x, which a module lowers once)."""
    obs.set_enabled(True)
    before = obs.counter("conv_lowerings").get_value()
    text = _lowered(dtype, platform, c)
    assert obs.counter("conv_lowerings").get_value() - before == int(kernels)
    assert ("tpu_custom_call" in text) is kernels
    if kernels:
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
        assert sorted(set(re.findall(r"conv_silu_\w+", text))) == [
            "conv_silu_bwd", "conv_silu_fwd"]
        assert "gdn_" not in text


def test_on_the_cpu_the_eligible_shapes_give_the_plain_forms_numbers():
    """The default branch of the lowered choice: value and gradients equal
    what the layer gave before the kernels."""
    x, taps, dy = _inputs(w=384)
    for got, want in zip(_grads(conv_silu, x, taps, dy),
                         _grads(_before_the_kernels, x, taps, dy)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
