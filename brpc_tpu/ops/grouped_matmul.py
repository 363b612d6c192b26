"""Grouped matrix product over the experts a chip holds — the routed half of
a sparse expert layer (models/deepseek.py), forward and backward, with no
padding to a capacity and no dropped row.

The (token, expert) assignments that fall on held experts are laid out by
expert: ``group_layout`` sorts them and gives every expert a run of whole
row tiles (at least one, so that an expert nobody chose still gets a zero
gradient), ``dispatch`` gathers the tokens' rows into that layout,
``grouped_matmul`` multiplies every tile by its own expert's matrix, and
``combine`` gathers each token's rows back and sums them by weight. The
buffers' static bound is every assignment landing here plus one tile of
slack per expert; the work follows the rows present: the kernels' grid runs
over the bound, but a step past the last tile in use maps to the block the
step before it held (no DMA) and does nothing.

Three Pallas TPU kernels, named for the device trace: ``moe_gmm_fwd``
(tile × its expert's [K,N]), ``moe_gmm_dlhs`` (dOut tile × the matrix
transposed) and ``moe_gmm_drhs`` (tileᵀ × dOut tile, accumulated in float32
over an expert's consecutive tiles). K and N are whole in VMEM (an expert's
matrix is 3 MB in bf16 at 2,048 × 768). Everything else is a gather: the
transposes of ``dispatch`` and ``combine`` are written as gathers through
the inverse map, since a scatter-add of rows serialises on a TPU. When
lowered for another platform, or at shapes the kernels do not take, the
same products are plain einsums over the tiles (``lax.platform_dependent``:
one traced function for every platform). ``moe_grouped_lowerings`` counts
the kernel products a lowered program holds.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from brpc_tpu.ops.lowered import count_lowering

_VMEM_LIMIT = 64 << 20

# The ``checkpoint_name`` that every field of a ``group_layout`` carries, and
# that a caller gives the routing the layout was made from: integers only,
# about 1.5 MB an expert layer at 49,152 assignments. A ``jax.checkpoint``
# whose policy saves it does not sort the assignments again in its
# recomputation; anywhere else it is an identity.
LAYOUT_NAME = "moe_group_layout"


class GroupLayout(NamedTuple):
    """Where each assignment's row lies, and what each row holds."""
    dest: jax.Array         # [A] row of assignment a (any row where not held)
    held: jax.Array         # [A] bool: the assignment's expert lives here
    row_source: jax.Array   # [M] assignment whose row this is
    row_valid: jax.Array    # [M] bool: the row holds an assignment
    tile_group: jax.Array   # [M / tile] group of each row tile
    n_tiles: jax.Array      # [1] tiles in use (the rest of the bound is idle)
    group_sizes: jax.Array  # [G] rows present per group


def choose_tile(n_assignments: int, n_groups: int) -> int:
    """Rows in a tile: 256 at the sizes a chip trains at. Each expert's run
    is rounded up to a tile, so a larger tile adds rows to every gather
    around the products; the products themselves hardly care: on a v5e at
    6,155 rows over 16 experts of 2,048 x 768 inside a bound of 49,152,
    forward / forward + backward read 0.25 / 0.66 ms at 128, 0.27 / 0.64 at
    256 and 0.23 / 0.58 at 512 (PERF.md section 6, PR 28). A power of two
    down to 8 where the whole bound is a few hundred rows (CPU tests)."""
    tile = 8
    while tile < 256 and tile * 16 <= n_assignments // n_groups:
        tile *= 2
    return tile


def bound_rows(n_assignments: int, n_groups: int, tile: int) -> int:
    """The layout's static row count: every assignment held here, each
    group's run rounded up to a tile (and an empty group given one)."""
    return (-(-n_assignments // tile) + n_groups) * tile


def group_layout(group_of: jax.Array, n_groups: int, tile: int) -> GroupLayout:
    """``group_of``: [A] int32, the held group (0..n_groups-1) of each
    assignment, or ``n_groups`` for one whose expert lives elsewhere."""
    (a,) = group_of.shape
    m = bound_rows(a, n_groups, tile)
    held = group_of < n_groups
    order = jnp.argsort(group_of, stable=True).astype(jnp.int32)
    one_hot = group_of[:, None] == jnp.arange(n_groups, dtype=jnp.int32)
    rank = jnp.cumsum(one_hot.astype(jnp.int32), axis=0) - 1       # [A, G]
    sizes = rank[-1] + 1
    tiles = jnp.maximum(-(-sizes // tile), 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile          # first row of each group
    first = jnp.cumsum(sizes) - sizes              # first sorted assignment
    safe = jnp.minimum(group_of, n_groups - 1)
    dest = row_start[safe] + jnp.take_along_axis(
        rank, safe[:, None], axis=1)[:, 0]
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(m // tile, dtype=jnp.int32),
                         side="right"), n_groups - 1).astype(jnp.int32)
    row = jnp.arange(m, dtype=jnp.int32)
    g = tile_group[row // tile]
    in_group = row - row_start[g]
    row_valid = (in_group < sizes[g]) & (row // tile < tile_end[-1])
    row_source = order[jnp.clip(first[g] + in_group, 0, a - 1)]
    return GroupLayout(*(checkpoint_name(field, LAYOUT_NAME) for field in (
        dest.astype(jnp.int32), held, row_source, row_valid, tile_group,
        tile_end[-1:].astype(jnp.int32), sizes)))


# -- rows in, rows out: gathers both ways -------------------------------------

@jax.custom_vjp
def dispatch(x, row_token, row_valid, dest, held):
    """x [N,H] -> rows [M,H]: row r is token ``row_token[r]``'s, zeros where
    the row holds nothing. ``dest`` / ``held`` [N,k] are the inverse map, for
    the transpose."""
    return jnp.where(row_valid[:, None], x[row_token], 0)


def _dispatch_fwd(x, row_token, row_valid, dest, held):
    return dispatch.fun(x, row_token, row_valid, dest, held), (dest, held)


def _dispatch_bwd(res, d_rows):
    dest, held = res
    picked = jnp.where(held[..., None], d_rows[dest], 0)         # [N,k,H]
    dx = jnp.sum(picked.astype(jnp.float32), axis=1).astype(d_rows.dtype)
    return dx, None, None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows, weights, dest, held, row_token, row_slot, row_valid):
    """rows [M,H], weights [N,k] float32 -> [N,H]: token t's result is the
    sum over its held assignments j of weights[t,j] * rows[dest[t,j]], in
    float32."""
    picked = jnp.where(held[..., None], rows[dest], 0)           # [N,k,H]
    return jnp.einsum("nk,nkh->nh", weights, picked.astype(jnp.float32)
                      ).astype(rows.dtype)


def _combine_fwd(*args):
    return combine.fun(*args), args


def _combine_bwd(res, dy):
    rows, weights, dest, held, row_token, row_slot, row_valid = res
    w_row = weights[row_token, row_slot]                          # [M]
    d_rows = jnp.where(
        row_valid[:, None],
        w_row[:, None] * dy[row_token].astype(jnp.float32), 0
    ).astype(rows.dtype)
    picked = jnp.where(held[..., None], rows[dest], 0)
    d_w = jnp.einsum("nh,nkh->nk", dy.astype(jnp.float32),
                     picked.astype(jnp.float32))
    return d_rows, d_w, None, None, None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


# -- the product ---------------------------------------------------------------

def kernels_take(rhs_shape, tile: int, dtype) -> bool:
    """Whether the compiled kernels take these operands: bf16 tiles of whole
    sublane packs, K and N that fill the lanes, and one expert's matrix with
    its float32 accumulator well inside VMEM."""
    _, k, n = rhs_shape
    return (dtype == jnp.bfloat16 and tile % 16 == 0 and k % 128 == 0
            and n % 128 == 0 and k * n * 12 <= _VMEM_LIMIT // 2)


def _tile(i, n_tiles_ref):
    """The tile a grid step works on: its own, or past the tiles in use the
    last of them (same block as the step before: nothing is copied)."""
    return jnp.minimum(i, n_tiles_ref[0] - 1)


def _fwd_kernel(tile_group_ref, n_tiles_ref, lhs_ref, rhs_ref, out_ref, *,
                transposed: bool):
    @pl.when(pl.program_id(0) < n_tiles_ref[0])
    def _():
        dims = (((1,), (1 if transposed else 0,)), ((), ()))
        out_ref[...] = lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _drhs_kernel(tile_group_ref, n_tiles_ref, lhs_ref, dout_ref, drhs_ref,
                 acc_ref):
    i = pl.program_id(0)
    last = n_tiles_ref[0] - 1

    @pl.when(i <= last)
    def _():
        g = tile_group_ref[i]
        prod = lax.dot_general(lhs_ref[...], dout_ref[...],
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        opens = jnp.logical_or(i == 0, tile_group_ref[jnp.maximum(i - 1, 0)]
                               != g)
        closes = jnp.logical_or(
            i == last, tile_group_ref[jnp.minimum(i + 1, last)] != g)

        @pl.when(opens)
        def _():
            acc_ref[...] = prod

        @pl.when(jnp.logical_not(opens))
        def _():
            acc_ref[...] += prod

        @pl.when(closes)
        def _():
            drhs_ref[0] = acc_ref[...].astype(drhs_ref.dtype)


def _rows_spec(tile: int, width: int):
    """A tile of a row array [M, width], by the grid step's tile."""
    return pl.BlockSpec((tile, width), lambda i, tg, nt: (_tile(i, nt), 0))


def _matrix_spec(k: int, n: int):
    """One expert's [K, N] of a stack [G, K, N], by the tile's group."""
    return pl.BlockSpec((1, k, n), lambda i, tg, nt: (tg[_tile(i, nt)], 0, 0))


def _call(kernel, name: str, interpret: bool, tile_group, n_tiles, operands,
          in_specs, out_specs, out_shape, scratch_shapes=()):
    """One kernel over the bound's row tiles, the two layout arrays
    prefetched as scalars."""
    return pl.pallas_call(
        kernel, name=name, interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tile_group.shape[0],),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(tile_group, n_tiles, *operands)


def _gmm_kernel(lhs, rhs, tile_group, n_tiles, *, transposed: bool,
                interpret: bool):
    """lhs [M,K] × rhs [G,K,N] -> [M,N]; ``transposed``: lhs [M,N] × rhs
    [G,K,N]ᵀ -> [M,K]."""
    m, width = lhs.shape
    _, k, n = rhs.shape
    tile = m // tile_group.shape[0]
    out_width = k if transposed else n
    return _call(
        functools.partial(_fwd_kernel, transposed=transposed),
        "moe_gmm_dlhs" if transposed else "moe_gmm_fwd", interpret,
        tile_group, n_tiles, (lhs, rhs),
        [_rows_spec(tile, width), _matrix_spec(k, n)],
        _rows_spec(tile, out_width),
        jax.ShapeDtypeStruct((m, out_width), lhs.dtype))


def _drhs_call(lhs, dout, tile_group, n_tiles, *, n_groups: int,
               interpret: bool):
    """lhs [M,K], dout [M,N] -> [G,K,N]: per group, lhsᵀ × dout over its
    tiles."""
    k, n = lhs.shape[1], dout.shape[1]
    tile = lhs.shape[0] // tile_group.shape[0]
    return _call(
        _drhs_kernel, "moe_gmm_drhs", interpret, tile_group, n_tiles,
        (lhs, dout), [_rows_spec(tile, k), _rows_spec(tile, n)],
        _matrix_spec(k, n),
        jax.ShapeDtypeStruct((n_groups, k, n), lhs.dtype),
        scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)])


def _gmm_dense(lhs, rhs, tile_group, *, transposed: bool):
    tiles = lhs.reshape(tile_group.shape[0], -1, lhs.shape[1])
    spec = "itn,ikn->itk" if transposed else "itk,ikn->itn"
    out = jnp.einsum(spec, tiles, rhs[tile_group],
                     preferred_element_type=jnp.float32)
    return out.reshape(lhs.shape[0], -1).astype(lhs.dtype)


def _drhs_dense(lhs, dout, tile_group, n_groups: int):
    n_t = tile_group.shape[0]
    per_tile = jnp.einsum("itk,itn->ikn", lhs.reshape(n_t, -1, lhs.shape[1]),
                          dout.reshape(n_t, -1, dout.shape[1]),
                          preferred_element_type=jnp.float32)
    return jax.ops.segment_sum(per_tile, tile_group, n_groups).astype(
        lhs.dtype)


def _product(kernel, dense, rhs_shape, interpret, a, b, tile_group, n_tiles):
    """One of the three products, by kernel or einsum as ``llama.attention``
    chooses: by the operands while tracing, by the platform when lowered.
    ``kernel(a, b, tile_group, n_tiles, interpret=)``, ``dense(a, b,
    tile_group)``."""
    if interpret is not None:
        return kernel(a, b, tile_group, n_tiles, interpret=interpret)
    tile = a.shape[0] // tile_group.shape[0]
    if not kernels_take(rhs_shape, tile, a.dtype):
        return dense(a, b, tile_group)
    return lax.platform_dependent(
        a, b, tile_group, n_tiles,
        tpu=lambda a, b, tile_group, n_tiles: kernel(
            count_lowering(a, "moe_grouped_lowerings"), b, tile_group,
            n_tiles, interpret=False),
        default=lambda a, b, tile_group, n_tiles: dense(a, b, tile_group))


def _rows_product(lhs, rhs, tile_group, n_tiles, transposed, interpret):
    return _product(functools.partial(_gmm_kernel, transposed=transposed),
                    functools.partial(_gmm_dense, transposed=transposed),
                    rhs.shape, interpret, lhs, rhs, tile_group, n_tiles)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(lhs, rhs, tile_group, n_tiles, interpret=None):
    """lhs [M,K] rows laid out by ``group_layout``, rhs [G,K,N] -> [M,N]:
    each row tile times the matrix of its group. Rows of tiles not in use
    are left as they come (nothing reads them). ``interpret``: None chooses
    kernel or einsum as above; True / False force the kernels through the
    Pallas interpreter or the compiler (tests)."""
    return _rows_product(lhs, rhs, tile_group, n_tiles, False, interpret)


def _grouped_fwd(lhs, rhs, tile_group, n_tiles, interpret):
    out = _rows_product(lhs, rhs, tile_group, n_tiles, False, interpret)
    return out, (lhs, rhs, tile_group, n_tiles)


def _grouped_bwd(interpret, res, dout):
    lhs, rhs, tile_group, n_tiles = res
    n_groups = rhs.shape[0]
    dlhs = _rows_product(dout, rhs, tile_group, n_tiles, True, interpret)
    drhs = _product(
        functools.partial(_drhs_call, n_groups=n_groups),
        functools.partial(_drhs_dense, n_groups=n_groups),
        rhs.shape, interpret, lhs, dout, tile_group, n_tiles)
    return dlhs, drhs, None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
