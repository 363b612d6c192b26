"""Grouped matrix product over the experts a chip holds — the routed half of
a sparse expert layer (models/experts.py, which models/deepseek.py and
models/hybrid.py both call), forward and backward, with no padding to a
capacity and no dropped row.

The (token, expert) assignments that fall on held experts are laid out by
expert: ``group_layout`` sorts them and gives every expert a run of whole
row tiles (at least one, so that an expert nobody chose still gets a zero
gradient), ``dispatch`` fetches the tokens' rows into that layout,
``grouped_matmul`` multiplies every tile by its own expert's matrix, and
``combine`` fetches each token's rows back and sums them by weight. The
buffers' static bound is every assignment landing here plus one tile of
slack per expert. The work follows the rows present, in the products and in
the row movement around them alike: a kernel's grid over row tiles runs over
the bound, but a step past the last tile in use maps to the block the step
before it held (no DMA) and does nothing, and rows of tiles not in use are
left as they come, since nothing reads them. A dropless layer has to bound
its buffers by what may arrive, several times what does: buffers by the
bound, work by the rows present.

Six Pallas TPU kernels, named for the device trace. The products:
``moe_gmm_fwd`` (tile x its expert's [K,N]), ``moe_gmm_dlhs`` (dOut tile x
the matrix transposed) and ``moe_gmm_drhs`` (tile^T x dOut tile, accumulated
in float32 over an expert's consecutive tiles); K and N are whole in VMEM
(an expert's matrix is 3 MB in bf16 at 2,048 x 768). The row movement:
``moe_rows_gather`` (a row tile at a time, each row's token row fetched by a
DMA of its own: ``dispatch``, and ``combine``'s transpose, which scales the
rows and takes the weights' gradient as one dot a row in the same pass),
``moe_rows_combine`` (a token tile at a time, only the held assignments'
rows fetched, summed by weight in float32 in the slots' order: ``combine``,
and ``dispatch``'s transpose) and ``moe_rows_pack`` (rewrites the tiles in
use so that every row is a run of tiles of its own, the only form a single
row can be fetched from). The transposes of ``dispatch`` and ``combine`` are
gathers through the inverse map, since a scatter-add of rows serialises on a
TPU. When lowered for another platform, or at shapes the kernels do not
take, the products are plain einsums over the tiles and the row movement
plain gathers of whole rows over the bound (``lax.platform_dependent``: one
traced function for every platform). ``moe_grouped_lowerings`` counts the
kernel products a lowered program holds, ``moe_rows_lowerings`` its row
movements by kernel.

No scalar is looked up over the bound, on any platform: XLA:TPU gathers and
scatters single elements one at a time, 8 ns each, and the bound is 50,000 to
90,000 of them (PERF.md section 7 row 7). The layout is a permutation of the
bound's rows: ``dest``, continued over the rows that hold nothing, and
``row_source`` are each other's inverse, and a permutation is applied, or
inverted, by a sort that carries what is to move (``group_layout``,
``rows_of``, ``assignments_of``). An assignment's place comes from a running
count and a compare against the groups held, and what a tile's rows share is
made once a tile.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from brpc_tpu.ops.lowered import VMEM_LIMIT, choose, compiler_params

# The ``checkpoint_name`` that every field of a ``group_layout`` carries, and
# that a caller gives the routing the layout was made from: integers only,
# about 1.5 MB an expert layer at 49,152 assignments. A ``jax.checkpoint``
# whose policy saves it does not sort the assignments again in its
# recomputation; anywhere else it is an identity.
LAYOUT_NAME = "moe_group_layout"


class GroupLayout(NamedTuple):
    """Where each assignment's row lies, and what each row holds."""
    # [A] row of assignment a: its group's first row + those of the group
    # before it; where not held an empty row of its own, any of them
    dest: jax.Array
    held: jax.Array         # [A] bool: the assignment's expert lives here
    # [M] the row's assignment (rising in a group): a permutation of 0..M-1,
    # ``dest``'s inverse; a row that holds nothing has an assignment not held
    # or a spare number from A on
    row_source: jax.Array
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


def _rows_before(sizes: jax.Array, tile: int):
    """Per group, from the rows present in each: its tiles (at least one),
    the tiles up to and with it, its first row, and its first row less the
    assignments of the groups before it (the rows those left empty)."""
    tiles = jnp.maximum(-(-sizes // tile), 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile
    return tiles, tile_end, row_start, row_start - (jnp.cumsum(sizes) - sizes)


def _empty_row(j, sizes: jax.Array, tile: int):
    """The j-th row (from 0) that holds no assignment, the idle tiles' rows
    after the groups' own: j plus the assignments of every group whose
    rows begin at or before it. ``sizes`` [G] against j [...]: a compare
    and a sum over the groups, no lookup."""
    *_, empty_before = _rows_before(sizes, tile)
    return j + jnp.sum(jnp.where(j[..., None] >= empty_before, sizes, 0),
                       axis=-1)


def _every_row(dest: jax.Array, sizes: jax.Array, n_rows: int, tile: int):
    """``dest`` [A] continued over the spare numbers A..M-1, [M]: they take
    the empty rows left when every assignment not held has taken one, so
    the whole is a permutation of the bound's rows."""
    spare = jnp.arange(dest.shape[0], n_rows, dtype=jnp.int32)
    return jnp.concatenate(
        [dest, _empty_row(spare - jnp.sum(sizes), sizes, tile)])


def group_layout(group_of: jax.Array, n_groups: int, tile: int) -> GroupLayout:
    """``group_of``: [A] int32, the held group (0..n_groups-1) of each
    assignment, or ``n_groups`` for one whose expert lives elsewhere.

    Made with no lookup of a scalar over A or M. An assignment's place in its
    group is a running count over [A, G] (G the groups held), read with its
    group's first row by a compare against the groups. The rows' sources are
    the inverse of that map, and a permutation is inverted by a sort: every
    assignment not held and M - A spare numbers take the empty rows in turn
    (``_every_row``), so that ``dest`` with the spare rows is a permutation
    of the bound's rows, and sorting the row numbers carries each row's
    source to its place. What is the same for a tile's rows is made a tile
    at a time and broadcast."""
    (a,) = group_of.shape
    m = bound_rows(a, n_groups, tile)
    held = group_of < n_groups
    one_hot = group_of[:, None] == jnp.arange(n_groups, dtype=jnp.int32)
    count = jnp.cumsum(one_hot.astype(jnp.int32), axis=0)  # [A, G], inclusive
    sizes = count[-1]
    _, tile_end, row_start, _ = _rows_before(sizes, tile)
    number = jnp.arange(a, dtype=jnp.int32)
    dest = jnp.where(
        held, jnp.sum(jnp.where(one_hot, row_start + count - 1, 0), axis=1),
        _empty_row(number - jnp.sum(count, axis=1), sizes, tile))
    _, row_source = lax.sort((_every_row(dest, sizes, m, tile),
                              jnp.arange(m, dtype=jnp.int32)), num_keys=1)
    tile_number = jnp.arange(m // tile, dtype=jnp.int32)
    tile_group = jnp.minimum(
        jnp.sum(tile_number[:, None] >= tile_end, axis=1, dtype=jnp.int32),
        n_groups - 1)
    # rows of its group before the tile, and in it: [M / tile], then by row
    before = tile_number * tile - row_start[tile_group]
    present = jnp.where(tile_number < tile_end[-1],
                        sizes[tile_group] - before, 0)
    row_valid = (jnp.arange(tile, dtype=jnp.int32) < present[:, None]
                 ).reshape(m)
    return GroupLayout(*(checkpoint_name(field, LAYOUT_NAME) for field in (
        dest, held, row_source, row_valid, tile_group,
        tile_end[-1:].astype(jnp.int32), sizes)))


def rows_of(lay: GroupLayout, values: jax.Array) -> jax.Array:
    """values [A], one an assignment -> [M]: row r's is that of the
    assignment it holds (anything where it holds none). The sort of
    ``group_layout`` again, carrying the values where that carried their
    numbers: the layout keeps integers only."""
    (m,) = lay.row_source.shape
    _, by_row = lax.sort(
        (_every_row(lay.dest, lay.group_sizes, m,
                    m // lay.tile_group.shape[0]),
         jnp.pad(values, (0, m - values.shape[0]))), num_keys=1)
    return by_row


def assignments_of(lay: GroupLayout, values: jax.Array) -> jax.Array:
    """values [M], one a row -> [A]: assignment a's is that of the row
    ``dest[a]``, the inverse of ``rows_of``: ``row_source`` is a permutation
    of 0..M-1, and sorting it carries every row's value to its source's
    place."""
    _, by_source = lax.sort((lay.row_source, values), num_keys=1)
    return by_source[:lay.dest.shape[0]]


def _tile(i, n_tiles_ref):
    """The tile a grid step works on: its own, or past the tiles in use the
    last of them (same block as the step before: nothing is copied)."""
    return jnp.minimum(i, n_tiles_ref[0] - 1)


# -- rows in, rows out --------------------------------------------------------
#
# dispatch, combine and their transposes each have a plain form, gathers
# over the whole bound, and a kernel form whose work follows the layout's
# ``n_tiles`` and ``held``.

def rows_kernels_take(n_tokens: int, width: int, tile: int, dtype) -> bool:
    """Whether the compiled ``moe_rows_*`` kernels take these rows: bf16, a
    width of whole pairs of 128 lanes (two bf16 pieces of a row share a
    32-bit word), row tiles and token tiles of whole sublane packs."""
    return (dtype == jnp.bfloat16 and width % 256 == 0 and tile % 16 == 0
            and n_tokens % 16 == 0)


def _maps(lay: GroupLayout, n: int):
    """The layout as ``dispatch`` and ``combine`` read it, for n tokens of
    k assignments each: dest [n,k], held [n,k], row_token [M], row_slot
    [M], and the rows in a tile. A row that holds a spare number reads as
    the last assignment's (a kernel fetches every row of a tile in use)."""
    k = lay.dest.shape[0] // n
    source = jnp.minimum(lay.row_source, n * k - 1)
    return (lay.dest.reshape(n, k), lay.held.reshape(n, k),
            source // k, source % k,
            lay.row_valid.shape[0] // lay.tile_group.shape[0])


def _token_tile(n: int) -> int:
    """Tokens a grid step takes: a power of two up to 256 that divides n."""
    tokens = 256
    while n % tokens:
        tokens //= 2
    return tokens


def _call(kernel, name: str, interpret: bool, grid: int, prefetch, operands,
          in_specs, out_specs, out_shape, scratch_shapes=()):
    """One kernel over a grid of tiles, ``prefetch`` as scalars."""
    return pl.pallas_call(
        kernel, name=name, interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(grid,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=compiler_params(("arbitrary",)),
    )(*prefetch, *operands)


def _row_major(a):
    """[R,H] -> [R, C, L], L the lane width: every row a run of tiles of
    its own, which one DMA fetches. (The tiles of [R,H] interleave 8 or 16
    rows, and Mosaic slices no single row out of them.) A row is whole tiles
    where its H / L planes are a multiple of 8; where they are not (2,304 =
    18 x 128: no power of two) C is the next multiple, 24, and the planes
    past H / L hold zeros that a DMA carries and nothing reads
    (``_real_planes``). To XLA this reshape is a relayout of every row, so it
    is made of arrays with a row a token; rows over the bound go through
    ``_pack_rows``, by the tiles in use."""
    r, h = a.shape
    lanes = 128 if h % 128 == 0 else h
    a = a.reshape(r, h // lanes, lanes)
    spare = -(h // lanes) % 8 if lanes == 128 else 0
    return jnp.pad(a, ((0, 0), (0, spare), (0, 0))) if spare else a


def _real_planes(planes, width: int, pieces: int = 1) -> int:
    """Of a packed ref's planes (``_row_major``, or ``_planes`` with its
    pieces a plane) those that hold a row of ``width``: the others pad the
    row to whole tiles."""
    return width // (planes.shape[2] * pieces)


def _pack_kernel(n_tiles_ref, *refs):
    *rows_refs, out_ref = refs

    @pl.when(pl.program_id(0) < n_tiles_ref[0])
    def _():
        lanes = out_ref.shape[2]
        # a store wants a static plane
        for q in range(_real_planes(out_ref, rows_refs[0].shape[1])):
            piece, *more = (ref[:, q * lanes:(q + 1) * lanes]
                            for ref in rows_refs)
            if more:
                piece = sum((p.astype(jnp.float32) for p in more),
                            piece.astype(jnp.float32)).astype(out_ref.dtype)
            out_ref[:, q, :] = piece


def _pack_rows(rows, n_tiles, tile: int, *, interpret: bool):
    """The sum of ``rows`` (arrays [M,H]) -> the same, ``_row_major``, over
    the tiles in use; rows of the other tiles are left as they come."""
    packed = jax.eval_shape(_row_major, rows[0])
    block = pl.BlockSpec((tile, rows[0].shape[1]),
                         lambda i, nt: (_tile(i, nt), 0))
    return _call(
        _pack_kernel, "moe_rows_pack", interpret, packed.shape[0] // tile,
        (n_tiles,), rows, [block] * len(rows),
        pl.BlockSpec((tile, *packed.shape[1:]),
                     lambda i, nt: (_tile(i, nt), 0, 0)),
        packed)


def _planes(ref):
    """A packed VMEM ref [R, C, L] as (planes, pieces a plane): bf16 rows of
    an even number of pieces are read as 32-bit words of two (piece 2q low,
    2q+1 high), since one piece alone is half of every sublane of a packed
    tile, and reading it so takes a v5e a third longer (PERF.md section 6,
    PR 33)."""
    if ref.dtype == jnp.bfloat16 and ref.shape[1] % 2 == 0:
        return ref.bitcast(jnp.uint32), 2
    return ref, 1


def _pieces(planes, rows, q):
    """Plane q of the rows ``rows`` (a slice) of ``_planes``' ref as its
    float32 [rows, L] pieces, left to right; a word's halves are widened in
    place (a bf16 is the upper half of its float32)."""
    plane = planes[rows, q, :]
    if plane.dtype != jnp.uint32:
        return (plane.astype(jnp.float32),)
    return (lax.bitcast_convert_type(plane << 16, jnp.float32),
            lax.bitcast_convert_type(plane & np.uint32(0xFFFF0000),
                                     jnp.float32))


def _columns(q, i: int, pieces: int, lanes: int):
    """The columns of piece i of plane q."""
    return pl.ds(pl.multiple_of((q * pieces + i) * lanes, lanes), lanes)


_UNROLL = 8     # row copies started (or awaited) a trip of the scalar loop


def _gather_kernel(n_tiles_ref, token_ref, src_ref, valid_ref, *rest,
                   scaled: bool):
    """One row tile: every row's source row fetched by a DMA of its own,
    all in flight at once, then masked, scaled and stored as a block."""
    if scaled:
        scale_ref, other_ref, out_ref, dots_ref, buf, sem = rest
    else:
        out_ref, buf, sem = rest
    tile, lanes = buf.shape[0], buf.shape[2]
    i = pl.program_id(0)

    @pl.when(i < n_tiles_ref[0])
    def _():
        def copy(r, source_row):
            return pltpu.make_async_copy(src_ref.at[source_row], buf.at[r],
                                         sem)

        def start(g, _):
            for r in range(_UNROLL):
                r = g * _UNROLL + r
                copy(r, token_ref[i * tile + r]).start()

        def wait(g, _):
            for r in range(_UNROLL):
                copy(g * _UNROLL + r, 0).wait()

        lax.fori_loop(0, tile // _UNROLL, start, None)
        lax.fori_loop(0, tile // _UNROLL, wait, None)
        valid = valid_ref[...] != 0
        planes, pieces = _planes(buf)

        def plane(q, dots):
            for i, piece in enumerate(_pieces(planes, slice(None), q)):
                cols = _columns(q, i, pieces, lanes)
                if scaled:
                    dots += jnp.sum(piece * other_ref[:, cols].astype(
                        jnp.float32), axis=1, keepdims=True)
                    piece = piece * scale_ref[...]
                out_ref[:, cols] = jnp.where(valid, piece, 0).astype(
                    out_ref.dtype)
            return dots

        dots = lax.fori_loop(
            0, _real_planes(planes, out_ref.shape[1], pieces), plane,
            jnp.zeros((tile, 1), jnp.float32))
        if scaled:
            dots_ref[...] = dots


def _gather_rows(src, scale=None, other=None, *, row_token, row_valid,
                 n_tiles, tile: int, interpret: bool):
    """src [N,H] -> [M,H]: row r of a tile in use is ``src[row_token[r]]``,
    zeros where not ``row_valid``; rows of the other tiles are left as they
    come. With ``scale`` [M] float32 and ``other`` [M,H]: the rows times
    ``scale``, and [M] float32, row r's fetched row . ``other[r]``."""
    (m,), (_, h) = row_token.shape, src.shape
    scaled = scale is not None
    clamped = lambda i, nt, tok: (_tile(i, nt), 0)  # noqa: E731
    column = pl.BlockSpec((tile, 1), clamped)
    block = pl.BlockSpec((tile, h), clamped)
    rows = jax.ShapeDtypeStruct((m, h), src.dtype)
    src = _row_major(src)
    operands = [src, row_valid.astype(jnp.int32)[:, None]]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY), column]
    if scaled:
        operands += [scale[:, None], other]
        in_specs += [column, block]
    out = _call(
        functools.partial(_gather_kernel, scaled=scaled), "moe_rows_gather",
        interpret, m // tile, (n_tiles, row_token), operands, in_specs,
        (block, column) if scaled else block,
        (rows, jax.ShapeDtypeStruct((m, 1), jnp.float32)) if scaled
        else rows,
        [pltpu.VMEM((tile, *src.shape[1:]), src.dtype),
         pltpu.SemaphoreType.DMA(())])
    return (out[0], out[1][:, 0]) if scaled else out


def _combine_kernel(first_ref, place_ref, rows_ref, held_ref, weights_ref,
                    out_ref, buf, sem, *, groups: int):
    """One token tile: the rows of its held assignments fetched by a DMA
    each, slot-major into ``buf``, then summed by weight over the slots in
    their order, in float32. The rows of a group lie in the order of their
    tokens, so this tile's are the run first[i, g] .. first[i + 1, g] of
    each group g: the scalar loop runs over the rows held, not over the
    assignments."""
    tokens, k = held_ref.shape
    lanes = buf.shape[2]
    i = pl.program_id(0)

    def copy(row):
        return pltpu.make_async_copy(rows_ref.at[row],
                                     buf.at[place_ref[row]], sem)

    def start(g, started):
        run = first_ref[i * groups + g], first_ref[(i + 1) * groups + g]
        lax.fori_loop(*run, lambda row, _: copy(row).start(), None)
        return started + run[1] - run[0]

    started = lax.fori_loop(0, groups, start, np.int32(0))
    lax.fori_loop(0, started, lambda _, c: copy(0).wait(), None)
    held = [held_ref[:, j:j + 1] != 0 for j in range(k)]
    weight = [weights_ref[:, j:j + 1] for j in range(k)]
    planes, pieces = _planes(buf)

    def plane(q, _):
        totals = [jnp.zeros((tokens, lanes), jnp.float32)] * pieces
        for j in range(k):      # a row never fetched is what the buffer held
            slot = _pieces(planes, pl.ds(j * tokens, tokens), q)
            totals = [total + jnp.where(held[j], weight[j] * piece, 0)
                      for total, piece in zip(totals, slot)]
        for i, total in enumerate(totals):
            out_ref[:, _columns(q, i, pieces, lanes)] = total.astype(
                out_ref.dtype)

    lax.fori_loop(0, _real_planes(planes, out_ref.shape[1], pieces), plane,
                  None)


def _combine_rows(weights, *rows, lay: GroupLayout, interpret: bool):
    """weights [N,k], arrays [M,H] that add up to the rows -> [N,H]: token
    t's sum over its held assignments j, in their order, of weights[t,j] *
    rows[dest[t,j]], in float32; only those rows are fetched."""
    (n, k), (_, h) = weights.shape, rows[0].shape
    _, held, row_token, row_slot, tile = _maps(lay, n)
    tokens, groups = _token_tile(n), lay.group_sizes.shape[0]
    # first[i, g]: group g's first row of a token of token tile i or later
    # = the group's first row + its rows of earlier tokens, counted a row
    # tile at a time
    bounds = jnp.arange(n // tokens + 1) * tokens
    earlier = jnp.sum((lay.row_valid[:, None] & (row_token[:, None] < bounds)
                       ).reshape(-1, tile, bounds.shape[0]), axis=1)
    group = jnp.arange(groups)
    first = tile * jnp.sum(lay.tile_group[:, None] < group, axis=0) + jnp.sum(
        jnp.where((lay.tile_group[:, None] == group)[:, None, :],
                  earlier[:, :, None], 0), axis=0)
    packed = _pack_rows(rows, lay.n_tiles, tile, interpret=interpret)
    per_token = pl.BlockSpec((tokens, k), lambda i, first, place: (i, 0))
    return _call(
        functools.partial(_combine_kernel, groups=groups),
        "moe_rows_combine", interpret, n // tokens,
        (first.reshape(-1), row_slot * tokens + row_token % tokens),
        [packed, held.astype(jnp.int32), weights],
        [pl.BlockSpec(memory_space=pl.ANY), per_token, per_token],
        pl.BlockSpec((tokens, h), lambda i, first, place: (i, 0)),
        jax.ShapeDtypeStruct((n, h), packed.dtype),
        [pltpu.VMEM((k * tokens, *packed.shape[1:]), packed.dtype),
         pltpu.SemaphoreType.DMA(())])


def _combine_plain(weights, *rows, lay: GroupLayout):
    dest, held, *_ = _maps(lay, weights.shape[0])
    rows = functools.reduce(jnp.add, rows)
    picked = jnp.where(held[..., None], rows[dest], 0)           # [N,k,H]
    return jnp.einsum("nk,nkh->nh", weights, picked.astype(jnp.float32)
                      ).astype(rows.dtype)


def _rows_choose(kernel, plain, n_tokens: int, width: int, dtype, lay,
                 interpret, *operands):
    """``choose`` for a movement between n tokens and rows of ``width``
    laid out by ``lay``."""
    tile = lay.row_valid.shape[0] // lay.tile_group.shape[0]
    return choose(
        kernel, plain, rows_kernels_take(n_tokens, width, tile, dtype),
        "moe_rows_lowerings", interpret, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def dispatch(x, lay: GroupLayout, interpret=None, copies: int = 1):
    """x [N,H] -> rows [M,H]: row r of a tile in use is the token's whose
    assignment ``lay.row_source[r]`` is, zeros where the row holds nothing.
    Rows of idle tiles are zeros or left as they come (nothing reads
    them). ``interpret`` as ``grouped_matmul``'s. ``copies`` > 1: a tuple of
    the same rows that many times, one a consumer, so that the transpose is
    handed their cotangents apart and adds them a tile in use at a time,
    where JAX would add them over the whole bound."""
    _, _, row_token, _, tile = _maps(lay, x.shape[0])
    rows = _rows_choose(
        functools.partial(_gather_rows, row_token=row_token,
                          row_valid=lay.row_valid, n_tiles=lay.n_tiles,
                          tile=tile),
        lambda x: jnp.where(lay.row_valid[:, None], x[row_token], 0),
        *x.shape, x.dtype, lay, interpret, x)
    return rows if copies == 1 else (rows,) * copies


def _dispatch_fwd(x, lay, interpret, copies):
    # ones [N,k]: what each held assignment weighs in the transpose
    return dispatch(x, lay, interpret, copies), (lay, jnp.ones(
        (x.shape[0], lay.dest.shape[0] // x.shape[0]), jnp.float32))


def _dispatch_bwd(interpret, copies, res, d_rows):
    """The transpose, a gather through the inverse map: token t's sum of
    its held assignments' rows, each weighing one."""
    lay, ones = res
    d_rows = (d_rows,) if copies == 1 else d_rows
    dx = _rows_choose(
        functools.partial(_combine_rows, lay=lay),
        functools.partial(_combine_plain, lay=lay),
        ones.shape[0], d_rows[0].shape[1], d_rows[0].dtype, lay, interpret,
        ones, *d_rows)
    return dx, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine(rows, weights, lay: GroupLayout, interpret=None):
    """rows [M,H], weights [N,k] float32 -> [N,H]: token t's result is the
    sum over its held assignments j of weights[t,j] * rows[dest[t,j]], in
    float32."""
    return _rows_choose(
        functools.partial(_combine_rows, lay=lay),
        functools.partial(_combine_plain, lay=lay),
        weights.shape[0], rows.shape[1], rows.dtype, lay, interpret, weights,
        rows)


def _combine_fwd(rows, weights, lay, interpret):
    return combine(rows, weights, lay, interpret), (rows, weights, lay)


def _combine_bwd(interpret, res, dy):
    """d_rows[r] = the weight of r's assignment * dy[its token]. d_weights
    [t,j] = dy[t] . rows[dest[t,j]], which is row dest[t,j]'s dot of what it
    fetched for d_rows with ``rows``: the kernel form takes it in the same
    pass over the row tiles and gathers no row by token."""
    rows, weights, lay = res
    dest, held, row_token, _, tile = _maps(lay, weights.shape[0])
    w_row = rows_of(lay, weights.reshape(-1))                     # [M]

    def kernel(dy, rows, *, interpret):
        d_rows, dots = _gather_rows(
            dy, w_row, rows, row_token=row_token, row_valid=lay.row_valid,
            n_tiles=lay.n_tiles, tile=tile, interpret=interpret)
        return d_rows, jnp.where(
            held, assignments_of(lay, dots).reshape(held.shape), 0)

    def plain(dy, rows):
        d_rows = jnp.where(
            lay.row_valid[:, None],
            w_row[:, None] * dy[row_token].astype(jnp.float32), 0
        ).astype(rows.dtype)
        picked = jnp.where(held[..., None], rows[dest], 0)
        return d_rows, jnp.einsum("nh,nkh->nk", dy.astype(jnp.float32),
                                  picked.astype(jnp.float32))

    d_rows, d_w = _rows_choose(kernel, plain, *dy.shape, dy.dtype, lay,
                               interpret, dy, rows)
    return d_rows, d_w, None


combine.defvjp(_combine_fwd, _combine_bwd)


# -- the product ---------------------------------------------------------------

def kernels_take(rhs_shape, tile: int, dtype) -> bool:
    """Whether the compiled kernels take these operands: bf16 tiles of whole
    sublane packs, K and N that fill the lanes, and one expert's matrix with
    its float32 accumulator well inside VMEM."""
    _, k, n = rhs_shape
    return (dtype == jnp.bfloat16 and tile % 16 == 0 and k % 128 == 0
            and n % 128 == 0 and k * n * 12 <= VMEM_LIMIT // 2)


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
        tile_group.shape[0], (tile_group, n_tiles), (lhs, rhs),
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
        _drhs_kernel, "moe_gmm_drhs", interpret, tile_group.shape[0],
        (tile_group, n_tiles), (lhs, dout), [_rows_spec(tile, k), _rows_spec(tile, n)],
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
    """One of the three products, by kernel or einsum (``choose``).
    ``kernel(a, b, tile_group, n_tiles, interpret=)``, ``dense(a, b,
    tile_group)``."""
    tile = a.shape[0] // tile_group.shape[0]
    return choose(
        kernel, lambda a, b, tile_group, n_tiles: dense(a, b, tile_group),
        kernels_take(rhs_shape, tile, a.dtype), "moe_grouped_lowerings",
        interpret, a, b, tile_group, n_tiles)


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
