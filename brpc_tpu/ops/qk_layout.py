"""q and k from their projections to the attention kernels in one pass:
Pallas TPU kernels, forward and backward (models/windowed.py, the scopes
``swa.rope`` and ``full.rope``).

A layer's projections leave q and k token-major, ``[B, T, H·D]``; the
attention kernels (ops/flash_attention.py) want them head-major, ``[B, H, T,
D]``, and on the way a layer norms each head (RMSNorm with a weight of D,
where the model has q/k norms) and turns it by its rope. Written in
``jax.numpy`` the norm, the rope and the transposes are float32 passes over
``[B, T, H, D]`` through HBM, each again in the recomputation and transposed
in the backward pass. ``forward`` reads q and k once in the dtype they
arrive in and writes them once, head-major, with the norm and the rotation
done in float32 in registers on the way; the backward kernel takes dq and dk
head-major from the attention kernels' backward, turns them back (the
rotation by the negated angle), applies the norm's backward from the
token-major input it kept, and writes token-major gradients.

- One call a pass serves q and k together: the grid's last axis runs over
  the groups of ``nh + nkv`` heads, q's first and then k's. A block index
  that does not change costs no copy, so q's blocks rest while k's move and
  the other way round: every block is read once and written once.
- The rotation is ``x · C + partner(x) · S`` over whole 128-lane rows:
  ``partner`` swaps the two halves of the leading ``rot`` lanes (lane
  rotations), and ``C`` / ``S`` are ``[B, T, D]`` float32 tables that
  ``rotation_tables`` makes from the positions and the ``rot / 2`` inverse
  frequencies with the XLA ops ``llama.rope`` runs — ``cos · scale`` twice
  then ones, ``-sin · scale``, ``sin · scale`` then zeros — so the lanes
  past ``rot`` pass through and no table is a constant of the program.
- The norm is ``x · rsqrt(mean x² + eps) · w`` in float32, never rounded
  before the rotation (``llama.rms_norm`` rounds to the compute dtype twice
  on the way: this is the same arithmetic with fewer roundings). Its
  backward sums the weights' gradients in float32 over every position, a
  row of partial sums a batch row.

``forward`` and ``backward`` are the two kernels and nothing else: the
caller owns the VJP and the choice between them and its plain form
(models/windowed.py: ``qk_head_major``), and ``supported`` tells it which
operands the compiled kernels take. ``interpret=True`` runs the same kernels
through the Pallas interpreter (CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_VMEM_LIMIT = 64 << 20
_ROWS = 256              # rows a trip of the kernels' loops widens to float32


def _block(t: int) -> int:
    """Positions a grid step takes: 512 where that divides, 0 where nothing
    does."""
    return next((b for b in (512, 256, 128) if t % b == 0), 0)


def _heads_a_step(nh: int, nkv: int) -> int:
    """Heads a grid step takes, of q or of k: as many as divide both."""
    return next(g for g in (8, 4, 2, 1) if nh % g == 0 and nkv % g == 0)


def supported(q_shape, k_shape, dtype, head_dim: int, rot: int) -> bool:
    """Whether the compiled kernels take q ``[B, T, nh·D]`` and k ``[B, T,
    nkv·D]``: bf16, heads of whole 128-lane rows, an even number of turned
    lanes that a head holds, positions of whole blocks."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and head_dim % _LANES == 0
            and rot % 2 == 0 and 0 < rot <= head_dim
            and q_shape[2] % head_dim == 0 and k_shape[2] % head_dim == 0
            and _block(q_shape[1]) > 0)


def rotation_tables(positions: jax.Array, inv_freq, head_dim: int,
                    scale: float):
    """positions ``[B, T]``, the ``rot / 2`` inverse frequencies -> (C, S),
    float32 ``[B, T, head_dim]``: the rotation of halves ``[x1 cos - x2 sin,
    x1 sin + x2 cos, rest]`` (``llama.rope``'s convention, cos and sin times
    ``scale``) is ``x · C + partner(x) · S``."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    rest = jnp.zeros((*angles.shape[:-1], head_dim - 2 * angles.shape[-1]),
                     jnp.float32)
    return (jnp.concatenate([cos, cos, rest + 1.0], axis=-1),
            jnp.concatenate([-sin, sin, rest], axis=-1))


def _turn(x, c, s, half: int):
    """x: float32 [rows, D] -> ``x · c + partner(x) · s``; ``partner`` swaps
    the halves of the leading ``2 · half`` lanes (what it brings to the lanes
    past them meets a zero of ``s``)."""
    d = x.shape[1]
    partner = pltpu.roll(x, half, 1)
    if 2 * half < d:
        lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
        partner = jnp.where(lane < half, pltpu.roll(x, d - half, 1), partner)
    return x * c + partner * s


def _heads_of_runs(block: int, group: int, d: int, tables, body):
    """``body(rows, lanes, g, c, s)`` for each run of ``_ROWS`` rows of a
    block and each of its ``group`` heads: the rows' slice, the head's lanes
    in a token-major block, the head's index in a head-major one, and the
    run's rows of the two ``tables``. Both loops are ``fori_loop``s, so a
    kernel's body is traced for one head whatever the group."""
    rows_a_run = min(block, _ROWS)

    def run(i, carry):
        rows = pl.ds(pl.multiple_of(i * rows_a_run, rows_a_run), rows_a_run)
        c, s = (ref[0, rows, :] for ref in tables)

        def head(g, carry):
            body(rows, pl.ds(pl.multiple_of(g * d, d), d), g, c, s)
            return carry

        return lax.fori_loop(0, group, head, carry)

    lax.fori_loop(0, block // rows_a_run, run, 0)


def _fwd_kernel(*refs, n_q: int, half: int, eps: float, norm: bool):
    q_ref, k_ref, c_ref, s_ref = refs[:4]
    w_ref = refs[4] if norm else None
    qo_ref, ko_ref = refs[-2:]
    group, block, d = qo_ref.shape[1:]

    def move(x_ref, o_ref, which):
        def body(rows, lanes, g, c, s):
            x = x_ref[0, rows, lanes].astype(jnp.float32)
            if norm:
                x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps) * w_ref[which:which + 1, :]
            o_ref[0, g, rows, :] = _turn(x, c, s, half).astype(o_ref.dtype)
        _heads_of_runs(block, group, d, (c_ref, s_ref), body)

    j = pl.program_id(2)
    pl.when(j < n_q)(lambda: move(q_ref, qo_ref, 0))
    pl.when(j >= n_q)(lambda: move(k_ref, ko_ref, 1))


def _bwd_kernel(*refs, n_q: int, half: int, eps: float, norm: bool):
    dq_ref, dk_ref, c_ref, s_ref = refs[:4]
    q_ref, k_ref, w_ref = refs[4:7] if norm else (None,) * 3
    dqo_ref, dko_ref = refs[-3:-1] if norm else refs[-2:]
    dw_ref = refs[-1] if norm else None
    group, block, d = dq_ref.shape[1:]
    j = pl.program_id(2)

    if norm:
        @pl.when((pl.program_id(1) == 0) & (j == 0))
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

    def move(dy_ref, x_ref, o_ref, which):
        def body(rows, lanes, g, c, s):
            # the rotation's transpose: by the negated angle
            dx = _turn(dy_ref[0, g, rows, :].astype(jnp.float32), c, -s, half)
            if norm:
                x = x_ref[0, rows, lanes].astype(jnp.float32)
                r = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
                normed = x * r
                dw_ref[0, which:which + 1, :] += jnp.sum(
                    dx * normed, axis=0, keepdims=True)
                dx = dx * w_ref[which:which + 1, :]
                dx = r * (dx - normed * jnp.mean(dx * normed, axis=-1,
                                                 keepdims=True))
            o_ref[0, rows, lanes] = dx.astype(o_ref.dtype)
        _heads_of_runs(block, group, d, (c_ref, s_ref), body)

    pl.when(j < n_q)(lambda: move(dq_ref, q_ref, dqo_ref, 0))
    pl.when(j >= n_q)(lambda: move(dk_ref, k_ref, dko_ref, 1))


def _specs(block: int, group: int, d: int, n_q: int):
    """Block specs by grid (batch, block of positions, group of heads, q's
    groups first): q's and k's token-major blocks, their head-major blocks,
    a table's block. Past its own groups an array's index stands still."""
    def q_at(j):
        return jnp.minimum(j, n_q - 1)

    def k_at(j):
        return jnp.maximum(j - n_q, 0)

    tokens = [pl.BlockSpec((1, block, group * d),
                           lambda b, i, j, at=at: (b, i, at(j)))
              for at in (q_at, k_at)]
    heads = [pl.BlockSpec((1, group, block, d),
                          lambda b, i, j, at=at: (b, at(j), i, 0))
             for at in (q_at, k_at)]
    table = pl.BlockSpec((1, block, d), lambda b, i, j: (b, i, 0))
    return tokens, heads, table


def _call(kernel, name, interpret, sequential_blocks: bool, **kwargs):
    return pl.pallas_call(
        kernel, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if sequential_blocks else "parallel",
                "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        **kwargs)


@functools.partial(jax.jit, static_argnames=("half", "eps", "interpret"))
def forward(q, k, cos, sin, weights, half: int, eps: float,
            interpret: bool = False):
    """q ``[B, T, nh·D]``, k ``[B, T, nkv·D]`` (``supported``), the tables
    of ``rotation_tables`` for the leading ``2 · half`` lanes of a head, and
    ``weights``: float32 ``[2, D]``, the q norm's and the k norm's, or None
    where the heads are not normed -> (q ``[B, nh, T, D]``, k ``[B, nkv, T,
    D]``), each head normed and turned."""
    (b, t, q_width), d = q.shape, cos.shape[2]
    nh, nkv = q_width // d, k.shape[2] // d
    group, block = _heads_a_step(nh, nkv), _block(t)
    n_q, norm = nh // group, weights is not None
    tokens, heads, table = _specs(block, group, d, n_q)
    whole = [pl.BlockSpec((2, d), lambda b, i, j: (0, 0))] if norm else []
    return _call(
        functools.partial(_fwd_kernel, n_q=n_q, half=half, eps=eps,
                          norm=norm),
        "qk_layout_fwd", interpret, sequential_blocks=False,
        grid=(b, t // block, (nh + nkv) // group),
        in_specs=[*tokens, table, table, *whole],
        out_specs=heads,
        out_shape=[jax.ShapeDtypeStruct((b, nh, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b, nkv, t, d), k.dtype)],
    )(q, k, cos, sin, *([weights] if norm else []))


@functools.partial(jax.jit, static_argnames=("half", "eps", "interpret"))
def backward(dq, dk, q, k, cos, sin, weights, half: int, eps: float,
             interpret: bool = False):
    """``forward``'s transpose: the cotangents of its results, head-major,
    and its operands (q and k are read with ``weights`` alone: the
    rotation's transpose needs no operand of the forward pass, the norm's
    needs its input) -> (dq ``[B, T, nh·D]``, dk ``[B, T, nkv·D]`` in the
    cotangents' dtype, the float32 ``[2, D]`` gradient of ``weights`` or
    None)."""
    (b, nh, t, d), nkv = dq.shape, dk.shape[1]
    group, block = _heads_a_step(nh, nkv), _block(t)
    n_q, norm = nh // group, weights is not None
    tokens, heads, table = _specs(block, group, d, n_q)
    whole = pl.BlockSpec((2, d), lambda b, i, j: (0, 0))
    out = _call(
        functools.partial(_bwd_kernel, n_q=n_q, half=half, eps=eps,
                          norm=norm),
        "qk_layout_bwd", interpret, sequential_blocks=norm,
        grid=(b, t // block, (nh + nkv) // group),
        in_specs=[*heads, table, table, *([*tokens, whole] if norm else [])],
        out_specs=[*tokens, *([pl.BlockSpec((1, 2, d),
                                            lambda b, i, j: (b, 0, 0))]
                              if norm else [])],
        out_shape=[jax.ShapeDtypeStruct((b, t, nh * d), dq.dtype),
                   jax.ShapeDtypeStruct((b, t, nkv * d), dk.dtype),
                   *([jax.ShapeDtypeStruct((b, 2, d), jnp.float32)]
                     if norm else [])],
    )(dq, dk, cos, sin, *([q, k, weights] if norm else []))
    return (*out[:2], out[2].sum(0) if norm else None)
