"""The short causal convolution of a Gated DeltaNet or Mamba-2 layer and the
silu on it, forward and backward, as one Pallas TPU kernel each
(models/hybrid.py, the scope ``gdn.conv``; models/mamba.py, ``ssm.conv``).

``conv_silu(x, taps, bias)`` is ``silu(causal_conv(x[..., :C], taps) +
bias)`` in x's dtype: a depthwise convolution of K taps along the positions,
the last tap on the current position, ``y_t = silu(sum_j taps[j]
x_{t-K+1+j} + bias)``, over the first C channels of x (C is the taps'
width: the caller hands the layer's whole projection, q | k | v | z or xBC
| z, and the kernels reach q | k | v or xBC by block index, so the slice is
never copied and nothing is padded in HBM). The bias is optional (a Gated
DeltaNet layer has none, a Mamba-2 layer's has one); without it the
kernels, their operands and the traced program are what they were before
there was one.

Written in ``jax.numpy`` the float32 products, their sum, the silu and the
transposes of each are whole float32 [B, T, C] passes through HBM. The
kernels move the channels once: a grid step takes a block of positions by a
block of channels in the dtype it arrives in, casts it to float32 in VMEM a
run of rows at a time, and writes bf16. What a run needs of its neighbours
— the K - 1 rows before it, and in the backward pass the K - 1 after —
comes as one 16-row tile (a bf16 tile's rows) each way: from the block
itself inside it, and at a block's edge from a second block spec on the same
array that names the tile before (after) the block, taken as zeros at the
first (last) block. No state passes from grid step to grid step of the
forward kernel, so its whole grid is parallel. A shift along the positions
is a sublane rotation of the widened run (``pltpu.roll``) whose wrapped rows
fall in the tile that is cut off.

- ``conv_silu_fwd``: x -> y. bf16 in, float32 taps, bias, products, sums
  and silu, bf16 out.
- ``conv_silu_bwd``: x, dy -> dx, dtaps. It recomputes the pre-activation of
  its rows and of the tile after them, ``dpre = dy silu'(pre)`` in float32
  (never rounded), ``dx_t = sum_j taps[j] dpre_{t+K-1-j}`` rounded once to
  x's dtype, and ``dtaps[j] = sum_t dpre_t x_{t-K+1+j}`` summed in float32
  into an output block that stays in VMEM across the positions (the grid's
  last, sequential axis), a row of partial sums a batch row; with a bias,
  ``dbias = sum_t dpre_t`` is one more row of that block. The residuals
  are x and the taps: no float32 [T, C] array is saved or leaves a kernel.

The names do not start with ``gdn_``: the benchmark's ``gdn_step_share`` and
``gdn_roofline`` take every op named ``gdn_*`` for the recurrence's kernels
(ops/gated_delta.py) and set their time against the recurrence's work alone.

Which form runs is chosen as every kernel of ``ops/`` is (``lowered.choose``):
by the operands while tracing (``kernels_take``: bf16, channels of
whole 128-lane tiles, positions of whole blocks, K at most 8), by the
platform when lowered (``lax.platform_dependent``, the plain form as
``default``). ``conv_lowerings`` counts the programs lowered with the
kernels. The plain form is ``causal_conv`` and ``jax.nn.silu`` under JAX's
own transposes: what the layer ran before the kernels, kept here so there is
one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from brpc_tpu.ops.lowered import choose, compiler_params

_HALO = 16               # rows of a bf16 tile: a run's neighbours, each way
_LANES = 128
_MAX_TAPS = 8
# rows a trip of the kernels' loops widens to float32 at once
_FWD_ROWS, _BWD_ROWS = 256, 128


def causal_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """Depthwise causal convolution along T, in float32. x: [B, T, C];
    taps: [K, C], the last tap on the current position: y_t = sum_j taps[j]
    x_{t-K+1+j}."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    return sum(padded[:, j:j + t] * taps[j].astype(jnp.float32)
               for j in range(k))


def _plain(x, taps, *bias):
    pre = causal_conv(x[..., :taps.shape[1]], taps)
    if bias:
        pre = pre + bias[0].astype(jnp.float32)
    return jax.nn.silu(pre).astype(x.dtype)


# -- the kernels --------------------------------------------------------------

def _block(t: int) -> int:
    """Positions a grid step takes: 1,024 where that divides, 0 where
    nothing does."""
    return next((b for b in (1024, 512, 256, 128) if t % b == 0), 0)


def kernels_take(x_shape, taps_shape, dtype) -> bool:
    """Whether the compiled kernels take these operands: bf16, the
    convolved channels whole 128-lane tiles, positions of whole blocks, no
    more taps than a tile of neighbours holds."""
    k, c = taps_shape
    return (jnp.dtype(dtype) == jnp.bfloat16 and c % _LANES == 0
            and c <= x_shape[2] and 1 <= k <= _MAX_TAPS
            and _block(x_shape[1]) > 0)


def _shifted(wide, k: int):
    """wide: float32 [_HALO + n, lanes], the rows from ``_HALO`` before a
    run on -> the K operands of its convolution, [n, lanes] each, the j-th
    x_{t-K+1+j}: sublane rotations whose wrapped rows are cut off."""
    return [(pltpu.roll(wide, k - 1 - j, 0) if j < k - 1 else wide)[_HALO:]
            for j in range(k)]


def _weighted(w, operands):
    """sum_j w[j] operands[j], summed in j's order as the plain form does."""
    acc = w[0] * operands[0]
    for wj, xj in zip(w[1:], operands[1:]):
        acc = acc + wj * xj
    return acc


def _tile(ref, at, lanes, edge, beyond):
    """The float32 16-row tile of the block ``ref`` at row ``at``, or where
    ``beyond`` (the tile lies outside the block) the neighbour's, ``edge``."""
    at = pl.multiple_of(jnp.clip(at, 0, ref.shape[1] - _HALO), _HALO)
    inside = ref[0, pl.ds(at, _HALO), lanes].astype(jnp.float32)
    return jnp.where(beyond, edge, inside)


def _edge(ref, lanes, absent):
    """A neighbouring block's tile, zeros where there is no such block."""
    return jnp.where(absent, 0.0, ref[0, :, lanes].astype(jnp.float32))


def _fwd_kernel(x_ref, before_ref, w_ref, *refs, rows: int):
    """``refs``: the bias where there is one, then y."""
    *bias_ref, y_ref = refs
    k, (block, width) = w_ref.shape[0], x_ref.shape[1:]
    for lane in range(0, width, _LANES):
        lanes = pl.ds(lane, _LANES)
        w = [w_ref[j:j + 1, lanes] for j in range(k)]
        bias = [r[0:1, lanes] for r in bias_ref]
        before = _edge(before_ref, lanes, pl.program_id(2) == 0)

        def run(i, carry):
            at = pl.multiple_of(i * rows, rows)
            wide = jnp.concatenate([
                _tile(x_ref, at - _HALO, lanes, before, i == 0),
                x_ref[0, pl.ds(at, rows), lanes].astype(jnp.float32)], axis=0)
            pre = _weighted(w, _shifted(wide, k))
            for b in bias:
                pre = pre + b
            y_ref[0, pl.ds(at, rows), lanes] = jax.nn.silu(pre).astype(
                y_ref.dtype)
            return carry

        lax.fori_loop(0, block // rows, run, 0)


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                *refs, rows: int):
    """``refs``: the bias where there is one, then dx and the block of the
    taps' sums, a row a tap and with a bias a row for it."""
    *bias_ref, dx_ref, dw_ref = refs
    k, (block, width) = w_ref.shape[0], x_ref.shape[1:]
    n = block // rows
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for lane in range(0, width, _LANES):
        lanes = pl.ds(lane, _LANES)
        w = [w_ref[j:j + 1, lanes] for j in range(k)]
        bias = [r[0:1, lanes] for r in bias_ref]
        before = _edge(before_ref, lanes, first)
        after = _edge(after_ref, lanes, last)
        dy_after = _edge(dy_after_ref, lanes, last)

        def run(i, sums):
            at = pl.multiple_of(i * rows, rows)
            # the run's rows and the tile after them: dx of the run's last
            # K - 1 rows takes dpre of the first K - 1 after it
            wide = jnp.concatenate([
                _tile(x_ref, at - _HALO, lanes, before, i == 0),
                x_ref[0, pl.ds(at, rows), lanes].astype(jnp.float32),
                _tile(x_ref, at + rows, lanes, after, i == n - 1)], axis=0)
            dy = jnp.concatenate([
                dy_ref[0, pl.ds(at, rows), lanes].astype(jnp.float32),
                _tile(dy_ref, at + rows, lanes, dy_after, i == n - 1)],
                axis=0)
            operands = _shifted(wide, k)
            pre = _weighted(w, operands)
            for b in bias:
                pre = pre + b
            sig = jax.nn.sigmoid(pre)
            dpre = dy * (sig * (1.0 + pre * (1.0 - sig)))
            ahead = [(pltpu.roll(dpre, rows + _HALO - (k - 1 - j), 0)
                      if j < k - 1 else dpre)[:rows] for j in range(k)]
            dx_ref[0, pl.ds(at, rows), lanes] = _weighted(w, ahead).astype(
                dx_ref.dtype)
            return tuple(
                s + jnp.sum(dpre[:rows] * xj[:rows], axis=0, keepdims=True)
                for s, xj in zip(sums, operands)) + tuple(
                s + jnp.sum(dpre[:rows], axis=0, keepdims=True)
                for s in sums[k:])

        sums = lax.fori_loop(
            0, n, run,
            (jnp.zeros((1, _LANES), jnp.float32),) * (k + len(bias)))
        for j in range(k + len(bias)):
            dw_ref[0, j:j + 1, lanes] += sums[j]


def _specs(block: int, t: int, width: int):
    """Block specs by grid (batch, block of channels, block of positions):
    the block itself and the 16-row tile before and after it, clamped into
    the array (the kernels take an absent neighbour as zeros)."""
    tiles = block // _HALO
    here = pl.BlockSpec((1, block, width), lambda b, c, i: (b, i, c))
    before = pl.BlockSpec(
        (1, _HALO, width),
        lambda b, c, i: (b, jnp.maximum(i * tiles - 1, 0), c))
    after = pl.BlockSpec(
        (1, _HALO, width),
        lambda b, c, i: (b, jnp.minimum((i + 1) * tiles, t // _HALO - 1), c))
    return here, before, after


def _width(c: int) -> int:
    """Channels a grid step takes: whole 128-lane tiles, 512 (a row of a
    block then moves as 1 KiB) where that divides."""
    return next(w for w in (512, 256, _LANES) if c % w == 0)


def _rows_spec(rows: int, width: int):
    """The taps (and the bias) as float32 rows, by block of channels."""
    return pl.BlockSpec((rows, width), lambda b, c, i: (0, c))


def _fwd_kernels(x, taps, *bias, interpret: bool):
    (b, t, _), (k, c) = x.shape, taps.shape
    block, width = _block(t), _width(c)
    here, before, _ = _specs(block, t, width)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rows=min(block, _FWD_ROWS)),
        name="conv_silu_fwd", interpret=interpret,
        grid=(b, c // width, t // block),
        in_specs=[here, before, _rows_spec(k, width),
                  *(_rows_spec(1, width) for _ in bias)],
        out_specs=here,
        out_shape=jax.ShapeDtypeStruct((b, t, c), x.dtype),
        compiler_params=compiler_params(("parallel",) * 3),
    )(x, x, taps.astype(jnp.float32),
      *(v.astype(jnp.float32).reshape(1, c) for v in bias))


def _bwd_kernels(x, taps, dy, *bias, interpret: bool):
    """-> (dx [B, T, C] in x's dtype, dtaps float32 [K, C], and with a bias
    dbias float32 [C])."""
    (b, t, _), (k, c) = x.shape, taps.shape
    block, width = _block(t), _width(c)
    here, before, after = _specs(block, t, width)
    sums = k + len(bias)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=min(block, _BWD_ROWS)),
        name="conv_silu_bwd", interpret=interpret,
        grid=(b, c // width, t // block),
        in_specs=[here, before, after, here, after, _rows_spec(k, width),
                  *(_rows_spec(1, width) for _ in bias)],
        out_specs=[here,
                   pl.BlockSpec((1, sums, width), lambda b, c, i: (b, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((b, t, c), x.dtype),
                   jax.ShapeDtypeStruct((b, sums, c), jnp.float32)],
        compiler_params=compiler_params(("parallel", "parallel",
                                         "arbitrary")),
    )(x, x, x, dy, dy, taps.astype(jnp.float32),
      *(v.astype(jnp.float32).reshape(1, c) for v in bias))
    dw = dw.sum(0)
    return (dx, dw) if not bias else (dx, dw[:k], dw[k])


# -- kernel or plain form, and the VJP ----------------------------------------

def _bwd_plain(x, taps, dy, *bias):
    grads = jax.vjp(_plain, x[..., :taps.shape[1]], taps, *bias)[1](dy)
    return grads[0], *(g.astype(jnp.float32) for g in grads[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_silu(x, taps, interpret, *bias):
    # only operands ``kernels_take`` come here
    return choose(_fwd_kernels, _plain, True, "conv_lowerings", interpret, x,
                  taps, *bias)


def _conv_silu_fwd(x, taps, interpret, *bias):
    return _conv_silu(x, taps, interpret, *bias), (x, taps, *bias)


def _conv_silu_bwd(interpret, residuals, dy):
    x, taps, *bias = residuals
    dx, dw, *db = choose(_bwd_kernels, _bwd_plain, True, "conv_lowerings",
                         interpret, x, taps, dy, *bias)
    # the channels past the convolved ones took no part
    dx = jnp.pad(dx, ((0, 0), (0, 0), (0, x.shape[2] - dx.shape[2])))
    return (dx, dw.astype(taps.dtype),
            *(d.astype(b.dtype) for d, b in zip(db, bias)))


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(x: jax.Array, taps: jax.Array, bias: jax.Array | None = None,
              *, interpret: bool | None = None) -> jax.Array:
    """x: [B, T, W]; taps: [K, C], C at most W; bias: [C] or None -> silu of
    the causal convolution of x's first C channels plus the bias, [B, T, C]
    in x's dtype, differentiable in all three. ``interpret`` True / False
    forces the kernels through the Pallas interpreter or the compiler
    (tests)."""
    bias = () if bias is None else (bias,)
    if kernels_take(x.shape, taps.shape, x.dtype):
        return _conv_silu(x, taps, interpret, *bias)
    if interpret is not None:
        raise ValueError(f"the kernels do not take x {x.dtype}{x.shape}, "
                         f"taps {taps.shape}")
    return _plain(x, taps, *bias)
