"""The Mamba-2 state-space scan in its chunked ("state-space duality", SSD)
form — the token mixer of a Mamba-2 layer (models/mamba.py), forward and
backward, as Pallas TPU kernels.

Per head h of H, with a state S in R^{N x P} that starts at 0, a step
Delta_t > 0, a log-decay a_t = Delta_t A_h <= 0 and ONE pair B_t, C_t in R^N
that every head shares (one group)::

    S_t = exp(a_t) S_{t-1} + Delta_t B_t x_t^T        y_t = S_t^T C_t

(``D x_t`` is the caller's). A scan of one step a token cannot be the
training path, so the sequence is taken in chunks of ``CHUNK`` positions.
Inside a chunk, with cum the running sum of a from the chunk's start (the
position itself included), L_ij = exp(cum_i - cum_j) for j <= i and S_0 the
state on entry::

    G = C B^T                                   # once a chunk, for every head
    Y = (G * L * Delta_j) X + diag(e^cum) C S_0
    S_C = e^{cum_C} S_0 + B^T diag(e^{cum_C - cum} Delta) X

Every exponent is a difference that is <= 0, so nothing overflows however
strong the decay.

Heads are 64 wide, half the 128 lanes of a vector register and of the MXU,
so heads go through the kernels in runs of ``128 // P`` side by side in the
lanes (two at P = 64): a run's x, y and state are [C, 128] and [N, 128].
The products with a state (C S_0, B^T (w X)) and their transposes are then
one MXU pass for the run, the contraction over the lanes of a transpose
summing the run's heads where the result is shared (dB, dC). Only the
[C, C] products with a head's own decay mask are per head, each a full pass
whose result is kept in that head's lanes. Taking one head of 64 at a time
would leave half of every pass and of every vector op idle; transposing to a
head-major [B, H, T, 64] would add two HBM passes of x and y a call. Heads
that do not fill whole 128-lane tiles (the tests' and dry runs' narrow
ones) go one a run, through the plain form alone.

Two kernels, named for the device trace. ``ssd_chunk_fwd``'s grid is
(batch, chunk, run of heads), the runs innermost: G is made at a chunk's
first run into VMEM and read by all the others, and the float32 states of
every run ([H P / 128, N, 128], 2 MiB at the published widths) stay in VMEM
from chunk to chunk. It writes y and every chunk's entry states.
``ssd_chunk_bwd`` walks the chunks in reverse with the states' cotangents in
VMEM, recomputes every within-chunk quantity from x, B, C, the decays and
the chunk's entry state, sums the heads' dG in VMEM and turns it into dB and
dC at a chunk's last run; it gives dx, dB, dC, dcum and dDelta: no per-token
state is ever stored. x, B and C are read where the layer's convolution left
them, the columns x | B | C of one [B, T, H P + 2 N] array, by block index:
nothing is sliced or padded. The MXU gets its operands in the dtype they
arrive in (bf16 on the training path); the states, their cotangents, the
decays, G and every accumulator are float32.

The per-chunk mathematics is ONE pair of functions, ``_chunk_fwd`` /
``_chunk_bwd`` (with ``_gram`` and ``_gram_bwd`` for G), which the kernels
call on their blocks and the plain form calls under ``vmap`` and
``lax.scan``: the same chunked algorithm in plain ``jax.numpy`` wherever the
kernels are not taken (another platform, float32, other widths), chosen as
every kernel of ``ops/`` is (``lowered.choose``). ``ssd_lowerings`` counts
the programs lowered with the kernels. What the forward pass makes carries
names (``RESIDUAL_NAMES``: y and the chunks' entry states) so that a caller
who recomputes its layers under ``jax.checkpoint`` can save them.

Layout: ``xbc`` [B, T, H P + 2 N] (x head after head, then B, then C);
Delta and a [B, T, H]. Per-token scalars travel inside as one row a chunk
and head, [B, T/C, H, 1, C] (a column is made from a row inside the kernel,
by the diagonal of its broadcast).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from brpc_tpu.ops.lowered import choose, compiler_params

CHUNK = 128
_LANES = 128

# ``checkpoint_name``s of the forward pass's y and of the chunks' entry
# states, which is all the backward pass keeps beside its inputs.
RESIDUAL_NAMES = ("ssd_out", "ssd_states")


def _mm(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


_nn = functools.partial(_mm, contract=((1,), (0,)))      # a @ b
_nt = functools.partial(_mm, contract=((1,), (1,)))      # a @ b.T
_tn = functools.partial(_mm, contract=((0,), (0,)))      # a.T @ b


def run_width(heads: int, head_dim: int) -> int:
    """Heads side by side in a run: as many as fill 128 lanes (two of 64)
    where the heads fill whole 128-lane tiles, else one (only the plain
    form runs there)."""
    g = _LANES // head_dim if _LANES % head_dim == 0 else 1
    return g if (heads * head_dim) % _LANES == 0 else 1


# -- the per-chunk mathematics ------------------------------------------------

def _gram(b, c):
    """G = C B^T of a chunk, float32 [C, C]: every head's."""
    return _nt(c, b)


def _gram_bwd(dg, b, c):
    """The heads' summed float32 dG -> (dB, dC) of the chunk, float32."""
    dg = dg.astype(b.dtype)
    return _tn(dg, c), _nn(dg, b)


def _sides(cum, dt, shape):
    """What of a chunk the decays make, for a run of heads side by side in
    the lanes. cum, dt: tuples of float32 rows [1, C], a head each; shape:
    the run's x block [C, g P]."""
    c, width = shape
    g = len(cum)
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = row == col
    head = lax.broadcasted_iota(jnp.int32, (1, width), 1) // (width // g)
    last = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    to_col = lambda r: jnp.sum(jnp.where(eye, r, 0.0), axis=1,  # noqa: E731
                               keepdims=True)
    heads = []
    for cum_row, dt_row in zip(cum, dt):
        cum_col = to_col(cum_row)
        cum_last = jnp.sum(jnp.where(last, cum_row, 0.0), axis=1,
                           keepdims=True)
        rest = jnp.exp(cum_last - cum_col)                 # e^{cum_C - cum}
        dt_col = to_col(dt_row)
        heads.append(dict(
            decay=jnp.exp(jnp.where(row >= col, cum_col - cum_row, -jnp.inf)),
            dt_row=dt_row, e_col=jnp.exp(cum_col), rest=rest, dt_col=dt_col,
            w_col=rest * dt_col, e_last=jnp.exp(cum_last)))

    def lanes(key):
        """A per-head column (or scalar) spread over its head's lanes."""
        out = 0.0
        for h, d in enumerate(heads):
            out = out + jnp.where(head == h, d[key], 0.0)
        return out

    return dict(eye=eye, head=head, last=last, heads=heads,
                e=lanes("e_col"), w=lanes("w_col"), e_last=lanes("e_last"))


def _chunk_fwd(s, gm, x, cum, dt, b, c):
    """One chunk of a run of g heads. s: float32 state on entry [N, g P];
    gm: the chunk's G; x [C, g P]; cum, dt: tuples of g float32 rows
    [1, C]; b, c [C, N] -> (y float32 [C, g P], the state on exit)."""
    dtype = x.dtype
    k = _sides(cum, dt, x.shape)
    y = k["e"] * _nn(c, s.astype(dtype))
    for h, d in enumerate(k["heads"]):
        m = (gm * d["decay"] * d["dt_row"]).astype(dtype)
        y = y + jnp.where(k["head"] == h, _nn(m, x), 0.0)
    wx = (k["w"] * x.astype(jnp.float32)).astype(dtype)
    return y, k["e_last"] * s + _tn(b, wx)


def _chunk_bwd(s, ds, gm, x, cum, dt, b, c, dy):
    """One chunk's transpose. s: the entry state as the forward pass used
    it; ds: float32 cotangent of the exit state; dy [C, g P]. Returns
    float32 (dx, this run's dG [C, C], its parts of dB and dC [C, N] that
    do not go through G, dcum and dDelta as tuples of rows, the cotangent
    of the entry state)."""
    dtype = x.dtype
    k = _sides(cum, dt, x.shape)
    xf, dyf = x.astype(jnp.float32), dy.astype(jnp.float32)
    s_in, ds_in = s.astype(dtype), ds.astype(dtype)
    cs = _nn(c, s_in)                           # C S_0, before e^cum
    ey = (k["e"] * dyf).astype(dtype)
    bds = _nn(b, ds_in)                         # B dS_C
    wx = (k["w"] * xf).astype(dtype)
    dc = _nt(ey, s_in)
    db = _nt(wx, ds_in)
    ds_out = k["e_last"] * ds + _tn(c, ey)
    dx = k["w"] * bds
    dgm = 0.0
    to_row = lambda col_: jnp.sum(jnp.where(k["eye"], col_, 0.0),  # noqa
                                  axis=0, keepdims=True)
    dcum, ddt = [], []
    for h, d in enumerate(k["heads"]):
        own = k["head"] == h
        gl = gm * d["decay"]
        m = gl * d["dt_row"]
        dx = dx + jnp.where(own, _tn(m.astype(dtype), dy), 0.0)
        dm = _nt(jnp.where(own, dyf, 0.0).astype(dtype), x)
        dgm = dgm + dm * d["decay"] * d["dt_row"]
        q = dm * m
        dw = jnp.sum(jnp.where(own, bds * xf, 0.0), axis=1, keepdims=True)
        # cum_i adds where it is the later position of a pair, through the
        # decay mask and e^cum; cum_j takes away; cum_C gathers the rest
        d_col = (jnp.sum(q, axis=1, keepdims=True) - dw * d["w_col"]
                 + jnp.sum(jnp.where(own, dyf * k["e"] * cs, 0.0), axis=1,
                           keepdims=True))
        d_last = (jnp.sum(dw * d["w_col"], axis=0, keepdims=True)
                  + d["e_last"] * jnp.sum(jnp.sum(
                      jnp.where(own, ds * s_in.astype(jnp.float32), 0.0),
                      axis=1, keepdims=True), axis=0, keepdims=True))
        dcum.append(to_row(d_col) - jnp.sum(q, axis=0, keepdims=True)
                    + jnp.where(k["last"], d_last, 0.0))
        ddt.append(jnp.sum(dm * gl, axis=0, keepdims=True)
                   + to_row(dw * d["rest"]))
    return dx, dgm, db, dc, tuple(dcum), tuple(ddt), ds_out


# -- the kernels --------------------------------------------------------------

def kernels_take(shape, heads: int, head_dim: int, dtype,
                 chunk: int = CHUNK) -> bool:
    """Whether the compiled kernels take these operands: bf16, runs of
    heads that fill 128 lanes exactly, B and C of whole 128-lane tiles
    starting on a tile, and a sequence of whole chunks of whole tiles."""
    _, t, width = shape
    state = (width - heads * head_dim) // 2
    g = run_width(heads, head_dim)
    return (jnp.dtype(dtype) == jnp.bfloat16 and g * head_dim == _LANES
            and state > 0 and state % _LANES == 0
            and width == heads * head_dim + 2 * state
            and (heads * head_dim) % state == 0
            and chunk % _LANES == 0 and t % chunk == 0)


def _rows_of(ref, g: int):
    """A [1, 1, g, 1, C] block of per-token rows -> g rows [1, C]."""
    return tuple(ref[0, 0, h] for h in range(g))


def _fwd_kernel(x_ref, b_ref, c_ref, cum_ref, dt_ref, y_ref, states_ref,
                s_acc, gm):
    i, p = pl.program_id(1), pl.program_id(2)
    g = cum_ref.shape[2]

    @pl.when(i == 0)
    def _():
        s_acc[p] = jnp.zeros(s_acc.shape[1:], jnp.float32)

    @pl.when(p == 0)
    def _():
        gm[...] = _gram(b_ref[0], c_ref[0])

    s = s_acc[p]
    states_ref[0, 0, 0] = s.astype(states_ref.dtype)
    y, s_acc[p] = _chunk_fwd(s, gm[...], x_ref[0], _rows_of(cum_ref, g),
                             _rows_of(dt_ref, g), b_ref[0], c_ref[0])
    y_ref[0] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, b_ref, c_ref, cum_ref, dt_ref, dy_ref, states_ref,
                dx_ref, db_ref, dc_ref, dcum_ref, ddt_ref, ds_acc, gm, dgm):
    i, p = pl.program_id(1), pl.program_id(2)
    g = cum_ref.shape[2]

    @pl.when(i == 0)
    def _():
        ds_acc[p] = jnp.zeros(ds_acc.shape[1:], jnp.float32)

    @pl.when(p == 0)
    def _():
        gm[...] = _gram(b_ref[0], c_ref[0])
        dgm[...] = jnp.zeros_like(dgm)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    dx, dg, db, dc, dcum, ddt, ds_acc[p] = _chunk_bwd(
        states_ref[0, 0, 0].astype(jnp.float32), ds_acc[p], gm[...],
        x_ref[0], _rows_of(cum_ref, g), _rows_of(dt_ref, g), b_ref[0],
        c_ref[0], dy_ref[0])
    dx_ref[0] = dx.astype(dx_ref.dtype)
    dgm[...] += dg
    db_ref[0] += db
    dc_ref[0] += dc
    for h in range(g):
        dcum_ref[0, 0, h] = dcum[h]
        ddt_ref[0, 0, h] = ddt[h]

    @pl.when(p == pl.num_programs(2) - 1)
    def _():
        db, dc = _gram_bwd(dgm[...], b_ref[0], c_ref[0])
        db_ref[0] += db
        dc_ref[0] += dc


def _specs(t: int, chunk: int, heads: int, head_dim: int, state: int,
           reverse: bool = False):
    """Block specs by grid (batch, chunk, run of heads), the chunks last to
    first where ``reverse``: x / y / dy (a run's lanes), B and C (their
    tiles of xbc), a [B, T/C, H, 1, C] row array, a [B, T/C, runs, N, 128]
    state array, and dB / dC (one block a chunk, kept across the runs)."""
    n = t // chunk
    g = _LANES // head_dim
    at = (lambda i: n - 1 - i) if reverse else (lambda i: i)
    b_block = heads * head_dim // state
    return dict(
        x=pl.BlockSpec((1, chunk, _LANES), lambda bi, i, p: (bi, at(i), p)),
        b=pl.BlockSpec((1, chunk, state),
                       lambda bi, i, p: (bi, at(i), b_block)),
        c=pl.BlockSpec((1, chunk, state),
                       lambda bi, i, p: (bi, at(i), b_block + 1)),
        rows=pl.BlockSpec((1, 1, g, 1, chunk),
                          lambda bi, i, p: (bi, at(i), p, 0, 0)),
        states=pl.BlockSpec((1, 1, 1, state, _LANES),
                            lambda bi, i, p: (bi, at(i), p, 0, 0)),
        shared=pl.BlockSpec((1, chunk, state),
                            lambda bi, i, p: (bi, at(i), 0)))


def _fwd_kernels(xbc, cum, dt, *, chunk: int, head_dim: int,
                 interpret: bool):
    bsz, t, width = xbc.shape
    heads = cum.shape[2]
    state = (width - heads * head_dim) // 2
    runs = heads * head_dim // _LANES
    s = _specs(t, chunk, heads, head_dim, state)
    with jax.named_scope("ssd.chunk_fwd"):
        return tuple(pl.pallas_call(
            _fwd_kernel, name="ssd_chunk_fwd", interpret=interpret,
            grid=(bsz, t // chunk, runs),
            in_specs=[s["x"], s["b"], s["c"], s["rows"], s["rows"]],
            out_specs=[s["x"], s["states"]],
            out_shape=[
                jax.ShapeDtypeStruct((bsz, t, heads * head_dim), xbc.dtype),
                jax.ShapeDtypeStruct((bsz, t // chunk, runs, state, _LANES),
                                     xbc.dtype)],
            scratch_shapes=[pltpu.VMEM((runs, state, _LANES), jnp.float32),
                            pltpu.VMEM((chunk, chunk), jnp.float32)],
            compiler_params=compiler_params(
                ("parallel", "arbitrary", "arbitrary")),
        )(xbc, xbc, xbc, cum, dt))


def _bwd_kernels(xbc, cum, dt, dy, states, *, chunk: int, head_dim: int,
                 interpret: bool):
    bsz, t, width = xbc.shape
    heads = cum.shape[2]
    state = (width - heads * head_dim) // 2
    runs = heads * head_dim // _LANES
    s = _specs(t, chunk, heads, head_dim, state, reverse=True)
    per_token = jax.ShapeDtypeStruct((bsz, t, state), jnp.float32)
    with jax.named_scope("ssd.chunk_bwd"):
        return tuple(pl.pallas_call(
            _bwd_kernel, name="ssd_chunk_bwd", interpret=interpret,
            grid=(bsz, t // chunk, runs),
            in_specs=[s["x"], s["b"], s["c"], s["rows"], s["rows"], s["x"],
                      s["states"]],
            out_specs=[s["x"], s["shared"], s["shared"], s["rows"],
                       s["rows"]],
            out_shape=[jax.ShapeDtypeStruct(dy.shape, dy.dtype), per_token,
                       per_token,
                       jax.ShapeDtypeStruct(cum.shape, jnp.float32),
                       jax.ShapeDtypeStruct(cum.shape, jnp.float32)],
            scratch_shapes=[pltpu.VMEM((runs, state, _LANES), jnp.float32),
                            pltpu.VMEM((chunk, chunk), jnp.float32),
                            pltpu.VMEM((chunk, chunk), jnp.float32)],
            compiler_params=compiler_params(
                ("parallel", "arbitrary", "arbitrary")),
        )(xbc, xbc, xbc, cum, dt, dy, states))


# -- the plain form -----------------------------------------------------------

def _by_run_of_heads(x, heads: int, chunk: int):
    """[B, T, H P] -> [B, runs, T/C, C, g P]."""
    bsz, t, hp = x.shape
    g = run_width(heads, hp // heads)
    return x.reshape(bsz, t // chunk, chunk, heads // g,
                     g * hp // heads).transpose(0, 3, 1, 2, 4)


def _from_runs(x):
    """The inverse of ``_by_run_of_heads``."""
    bsz, runs, n, chunk, width = x.shape
    return x.transpose(0, 2, 3, 1, 4).reshape(bsz, n * chunk, runs * width)


def _split(xbc, heads: int, head_dim: int, chunk: int):
    """xbc -> x by run [B, runs, T/C, C, g P], B and C by chunk
    [B, T/C, C, N]."""
    bsz, t, width = xbc.shape
    hp = heads * head_dim
    state = (width - hp) // 2
    by_chunk = lambda a: a.reshape(bsz, t // chunk, chunk, state)  # noqa
    return (_by_run_of_heads(xbc[..., :hp], heads, chunk),
            by_chunk(xbc[..., hp:hp + state]), by_chunk(xbc[..., hp + state:]))


def _by_run(rows, g: int):
    """[B, T/C, H, 1, C] -> [B, runs, T/C, g, 1, C]."""
    bsz, n, heads, _, c = rows.shape
    return rows.reshape(bsz, n, heads // g, g, 1, c).transpose(0, 2, 1, 3, 4,
                                                                 5)


def _unrun(a):
    """[B, runs, T/C, ...] -> [B, T/C, runs, ...]."""
    return jnp.moveaxis(a, 1, 2)


def _over_batch_and_runs(per_run, run_args, shared_args):
    """``per_run(*run_args_i, *shared_args)`` for every batch row and run of
    heads; the shared arguments (B, C, G) are a batch row's for every run."""
    n_run = len(run_args)
    inner = jax.vmap(per_run,
                     in_axes=(0,) * n_run + (None,) * len(shared_args))
    return jax.vmap(inner)(*run_args, *shared_args)


def _fwd_plain(xbc, cum, dt, *, chunk: int, head_dim: int):
    bsz, t, _ = xbc.shape
    heads = cum.shape[2]
    g = run_width(heads, head_dim)
    x, b, c = _split(xbc, heads, head_dim, chunk)
    gm = jax.vmap(jax.vmap(_gram))(b, c)

    def per_run(x, cum, dt, gm, b, c):
        def step(s, xs):
            gm_i, x_i, cum_i, dt_i, b_i, c_i = xs
            y, s_next = _chunk_fwd(s, gm_i, x_i, tuple(cum_i), tuple(dt_i),
                                   b_i, c_i)
            return s_next, (y.astype(x.dtype), s.astype(x.dtype))
        s0 = jnp.zeros((b.shape[-1], x.shape[-1]), jnp.float32)
        return lax.scan(step, s0, (gm, x, cum, dt, b, c))[1]

    y, states = _over_batch_and_runs(
        per_run, (x, _by_run(cum, g), _by_run(dt, g)), (gm, b, c))
    return _from_runs(y), _unrun(states)


def _bwd_plain(xbc, cum, dt, dy, states, *, chunk: int, head_dim: int):
    bsz, t, _ = xbc.shape
    heads = cum.shape[2]
    g = run_width(heads, head_dim)
    x, b, c = _split(xbc, heads, head_dim, chunk)
    gm = jax.vmap(jax.vmap(_gram))(b, c)

    def per_run(x, cum, dt, dy, states, gm, b, c):
        def step(ds, xs):
            gm_i, x_i, cum_i, dt_i, b_i, c_i, dy_i, s_i = xs
            dx, dg, db, dc, dcum, ddt, ds = _chunk_bwd(
                s_i.astype(jnp.float32), ds, gm_i, x_i, tuple(cum_i),
                tuple(dt_i), b_i, c_i, dy_i)
            return ds, (dx.astype(x.dtype), dg, db, dc, jnp.stack(dcum),
                        jnp.stack(ddt))
        ds0 = jnp.zeros((b.shape[-1], x.shape[-1]), jnp.float32)
        return lax.scan(step, ds0, (gm, x, cum, dt, b, c, dy, states),
                        reverse=True)[1]

    dx, dg, db, dc, dcum, ddt = _over_batch_and_runs(
        per_run, (x, _by_run(cum, g), _by_run(dt, g),
                  _by_run_of_heads(dy, heads, chunk),
                  jnp.moveaxis(states, 2, 1)), (gm, b, c))
    db_g, dc_g = jax.vmap(jax.vmap(_gram_bwd))(dg.sum(1), b, c)
    db = (db.sum(1) + db_g).reshape(bsz, t, -1)
    dc = (dc.sum(1) + dc_g).reshape(bsz, t, -1)
    dx = _from_runs(dx)
    rows = lambda a: _unrun(a).reshape(cum.shape)   # noqa: E731
    return dx, db, dc, rows(dcum), rows(ddt)


# -- kernel or plain form, and the scan's VJP ---------------------------------

def _rows(a, chunk: int):
    """[B, T, H] -> float32 [B, T/C, H, 1, C], a row a chunk and head."""
    bsz, t, heads = a.shape
    return a.astype(jnp.float32).reshape(
        bsz, t // chunk, chunk, heads).transpose(0, 1, 3, 2)[:, :, :, None]


def _unrows(rows, shape):
    """The inverse of ``_rows``."""
    return rows[:, :, :, 0].transpose(0, 1, 3, 2).reshape(shape)


def _choose(kernels, plain, interpret, chunk: int, head_dim: int, xbc,
            *operands):
    """``lowered.choose`` at ``chunk``, counted as ``ssd_lowerings``."""
    taken = kernels_take(xbc.shape, operands[0].shape[2], head_dim,
                         xbc.dtype, chunk)
    return choose(functools.partial(kernels, chunk=chunk, head_dim=head_dim),
                  functools.partial(plain, chunk=chunk, head_dim=head_dim),
                  taken, "ssd_lowerings", interpret, xbc, *operands)


def _forward(xbc, dt, a, head_dim, chunk, interpret):
    cum = jnp.cumsum(_rows(a, chunk), axis=-1)
    return _choose(_fwd_kernels, _fwd_plain, interpret, chunk, head_dim, xbc,
                   cum, _rows(dt, chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _scan(xbc, dt, a, head_dim, chunk, interpret):
    return _forward(xbc, dt, a, head_dim, chunk, interpret)[0]


def _scan_fwd(xbc, dt, a, head_dim, chunk, interpret):
    y, states = _forward(xbc, dt, a, head_dim, chunk, interpret)
    y = checkpoint_name(y, RESIDUAL_NAMES[0])
    states = checkpoint_name(states, RESIDUAL_NAMES[1])
    return y, (xbc, dt, a, states)


def _scan_bwd(head_dim, chunk, interpret, residuals, dy):
    xbc, dt, a, states = residuals
    cum = jnp.cumsum(_rows(a, chunk), axis=-1)
    dx, db, dc, dcum, ddt = _choose(
        _bwd_kernels, _bwd_plain, interpret, chunk, head_dim, xbc, cum,
        _rows(dt, chunk), dy, states)
    # cum is a chunk's running sum of a: its transpose runs the other way
    da = jnp.flip(jnp.cumsum(jnp.flip(dcum, -1), axis=-1), -1)
    dxbc = jnp.concatenate([dx, db.astype(xbc.dtype), dc.astype(xbc.dtype)],
                           axis=-1)
    return (dxbc, _unrows(ddt, dt.shape).astype(dt.dtype),
            _unrows(da, a.shape).astype(a.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def chunk_for(t: int) -> int:
    """The chunk at a sequence of ``t``: ``CHUNK``, or where the sequence
    is shorter the sequence itself."""
    return min(CHUNK, t)


@functools.partial(jax.jit, static_argnames=("head_dim", "chunk",
                                             "interpret"))
def ssd(xbc, dt, a, *, head_dim: int, chunk: int | None = None,
        interpret: bool | None = None):
    """The scan of every head. xbc: [B, T, H P + 2 N], the heads' x, then
    B, then C; dt (Delta, > 0) and a (Delta A, <= 0): [B, T, H] -> y
    [B, T, H P] in xbc's dtype, differentiable in all three. T must be
    whole chunks."""
    t = xbc.shape[1]
    chunk = chunk or chunk_for(t)
    if t % chunk:
        raise ValueError(f"seq {t} must be whole chunks of {chunk}")
    return _scan(xbc, dt, a, head_dim, chunk, interpret)
