"""The gated delta rule in chunked form — the token mixer of a Gated DeltaNet
layer (models/hybrid.py), forward and backward, as Pallas TPU kernels.

Per value head, with a state S in R^{dk x dv} that starts at 0, log-decay
g_t <= 0 and write strength beta_t::

    S <- exp(g_t) S;  delta = beta_t (v_t - S^T k_t);  S <- S + k_t delta^T
    o_t = S^T q_t

A scan of one step a token cannot be the training path, so the sequence is
taken in chunks of ``CHUNK`` positions in the WY form of the Gated DeltaNet
paper. Inside a chunk, with gamma the cumulative log-decay from the chunk's
start, D_ij = exp(gamma_i - gamma_j) for j <= i and S_0 the state on entry::

    A = strict_lower(beta_i (k_i . k_j) D_ij)       T = (I + A)^-1
    Delta = T (beta v - (beta e^gamma k) S_0)       # every delta of the chunk
    O = (q e^gamma) S_0 + (q k^T * D) Delta
    S_C = e^{gamma_C} S_0 + (k e^{gamma_C - gamma})^T Delta

Every exponent is a difference that is <= 0, so nothing overflows however
strong the decay. ``T`` is made of matrix products alone: A is cut into its
block diagonal (blocks of 16, each inverted by the Neumann series in its
doubling form, exact at a nilpotent block) and the rest, which is nilpotent
by blocks and inverted the same way.

Three kernels, named for the device trace. ``gdn_chunk_prep`` makes what of
a chunk no state touches and every pass needs, T, for every value head and
chunk, once: A from k k^T (made once a key head for the value heads that
share it), beta and the decay in float32, the inverse's products at
``highest``, the result rounded to the operands' dtype. Its grid has no
sequential axis — no chunk waits for another — so two chunks are taken side
by side in the 128 lanes of a vector register ([C, 2C]: the elementwise work
and the MXU's passes cost a pair what they cost one chunk of 64, and a
product of the pair is one product with the right factor laid out
block-diagonally, its zeros adding nothing to any sum) and several pairs a
trip of the loop; T is stored that way, [B, Hv, T/(2C), C, 2C], which is also
the only form in which XLA keeps it at its size ([C, C] tiles of 64 lanes are
padded to 128). ``gdn_chunk_fwd`` walks a head's chunks in order (the grid's
last axis) with the float32 state in VMEM, writes the output and each chunk's
entry state. ``gdn_chunk_bwd`` walks them in reverse with the state's
cotangent in VMEM, recomputes every within-chunk quantity but T from q, k, v,
gamma, beta and the chunk's entry state, and gives dq, dk, dv, dgamma and
dbeta: no per-token state is ever stored. Both take T as an operand: the
sequential kernels invert nothing. The MXU gets its operands in the dtype
they arrive in (bf16 on the training path); the state, its cotangent, the
decays, A, the inverse (its own products at ``highest``) and every
accumulator are float32. The per-chunk mathematics is ONE pair of functions,
``_chunk_fwd`` / ``_chunk_bwd``, and one ``_chunk_prep`` before them, which
the kernels call on their blocks and the plain form calls under ``vmap`` and
``lax.scan``: the same chunked algorithm in plain ``jax.numpy`` wherever the
kernels are not taken (another platform, float32, widths that are no multiple
of 128), chosen by ``lax.platform_dependent`` like ``llama.attention``.
``gdn_lowerings`` counts the programs lowered with the kernels.

What the forward pass makes carries names, so that a caller who recomputes
its layers under ``jax.checkpoint`` can save it: T (``INVERSE_NAME``, 33.5 MB
a layer of 8,192 tokens and 32 value heads), which then is made once however
often the forward kernel runs, and the forward kernel's two results
(``RESIDUAL_NAMES``: the head-major output and the chunks' entry states),
which would save its second run.

Layout: q, k [B, T, Hk, dk], v [B, T, Hv, dv], g, beta [B, T, Hv]; key head
h // (Hv // Hk) serves value head h. q and k come normalised and scaled as
the caller wants them. Inside, arrays are head-major, and per-token scalars
travel as one row a chunk, [B, Hv, T/C, 1, C] (a column is made from a row
inside the kernel, by the diagonal of its broadcast).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from brpc_tpu.ops.lowered import count_lowering

CHUNK = 64
_INNER = 16              # the diagonal blocks of A inverted on their own
_VMEM_LIMIT = 64 << 20

# ``checkpoint_name``s of the forward pass's head-major output and of the
# chunks' entry states, which is all the backward pass keeps beside its
# inputs.
RESIDUAL_NAMES = ("gdn_out", "gdn_states")
# ... and of what ``gdn_chunk_prep`` makes, every chunk's T: a caller that
# recomputes its layers saves this one (models/hybrid.py).
INVERSE_NAME = "gdn_inverse"
# runs of chunks side by side that a trip of each kernel's loop takes: the
# fastest of 1, 2, 4 and 8 each on a v5e at 8,192 tokens (PERF.md, PR 35)
_PREP_TRIP, _FWD_TRIP, _BWD_TRIP = 2, 4, 2


def _mm(a, b, contract, precision=None):
    return lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


_nn = functools.partial(_mm, contract=((1,), (0,)))      # a @ b
_nt = functools.partial(_mm, contract=((1,), (1,)))      # a @ b.T
_tn = functools.partial(_mm, contract=((0,), (0,)))      # a.T @ b


def _precise(a, b, beside):
    """a @ b of float32 operands to float32 accuracy (the inverse's own
    products: its result is rounded to the operands' dtype once, after).
    Of chunks side by side (``beside``: each one's lanes) every [C, C]
    block of a times its own of b: b laid out block-diagonally gives them
    in one product, the zeros adding nothing to any sum."""
    if beside:
        b = jnp.concatenate([jnp.where(own, b, 0.0) for own in beside], axis=0)
    return _nn(a, b, precision=lax.Precision.HIGHEST)


def _inverse(a, x):
    """(I + a)^-1 of a strictly lower triangular float32 [C, C], C at most
    ``_INNER`` or a multiple of it, by matrix products alone; of several
    side by side in the lanes at once (``x``: their ``_chunk_lanes``)."""
    c, row, col = a.shape[0], x["row"], x["col"]
    inner = min(_INNER, c)
    eye = x["eye"].astype(jnp.float32)
    precise = functools.partial(_precise, beside=x["beside"])

    def neumann(m, index):
        """sum_{n < index} (-m)^n = (I - m)(I + m^2)(I + m^4)..., the whole
        inverse of I + m where m^index = 0."""
        t, n = eye - m, 2
        while n < index:
            m = precise(m, m)
            t = precise(t, eye + m)
            n *= 2
        return t

    if c == inner:
        return neumann(a, c)
    same = (row // inner) == (col // inner)
    t_diag = neumann(jnp.where(same, a, 0.0), inner)
    # I + a = (I + a_diag)(I + t_diag a_rest), the second nilpotent by blocks
    rest = precise(t_diag, jnp.where(same, 0.0, a))
    return precise(neumann(rest, c // inner), t_diag)


def _chunk_lanes(g_row, b_row, c: int):
    """Where the [C, C] matrices of w chunks side by side in the lanes lie,
    and gamma and beta down their rows. g_row (gamma), b_row (beta):
    float32 [1, w C], chunk after chunk. One chunk (w = 1) wherever a state
    is near: its columns are [C, 1]."""
    lanes = g_row.shape[1]
    row = lax.broadcasted_iota(jnp.int32, (c, lanes), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, lanes), 1)
    if lanes == c:
        beside, eye = [], row == col
        to_col = lambda r: jnp.sum(jnp.where(eye, r, 0.0),   # noqa: E731
                                   axis=1, keepdims=True)
    else:
        beside = [(col >= j * c) & (col < (j + 1) * c)
                  for j in range(lanes // c)]
        col = sum(jnp.where(own, col - j * c, 0)
                  for j, own in enumerate(beside))
        eye = row == col
        to_col = lambda r: sum(jnp.where(own, jnp.sum(       # noqa: E731
            jnp.where(eye & own, r, 0.0), axis=1, keepdims=True), 0.0)
            for own in beside)
    return dict(row=row, col=col, eye=eye, beside=beside,
                g_col=to_col(g_row), b_col=to_col(b_row))


def _decay(x, g_row):
    """D_ij = exp(gamma_i - gamma_j) for j <= i and 0 above, float32."""
    return jnp.exp(jnp.where(x["row"] >= x["col"], x["g_col"] - g_row,
                             -jnp.inf))


def _chunk_squares(x, kk, g_row):
    """Adds to ``x`` the float32 [C, C] matrices that no state touches, of
    each chunk in its lanes: the decay D, k k^T and A. kk: k k^T of all the
    chunks' keys, [w C, w C]."""
    c = x["row"].shape[0]
    decay = _decay(x, g_row)
    if x["beside"]:                     # each chunk's own block of kk
        kk = sum(jnp.where(own, kk[j * c:(j + 1) * c], 0.0)
                 for j, own in enumerate(x["beside"]))
    a = jnp.where(x["row"] > x["col"], x["b_col"] * kk * decay, 0.0)
    return dict(x, decay=decay, kk=kk, a=a)


def _chunk_prep(kk, g_row, b_row, c: int, dtype):
    """What a chunk's passes share and no state touches, made once: T =
    (I + A)^-1, rounded once to the operands' ``dtype``. Of w chunks at
    once, side by side in the lanes, [C, w C]: a vector register has 128
    lanes and a chunk of 64 fills half, so the elementwise work and the
    MXU's passes cost two chunks what they cost one. kk: k k^T of the w
    chunks' keys together, float32 [w C, w C]; g_row, b_row [1, w C]."""
    x = _chunk_squares(_chunk_lanes(g_row, b_row, c), kk, g_row)
    return _inverse(x["a"], x).astype(dtype)


def _chunk_sides(q, k, g_row, b_row):
    """One chunk's lanes, columns and the [C, dk] operands of the products
    with the state. q, k [C, dk]; g_row, b_row [1, C]."""
    c, dt = q.shape[0], q.dtype
    x = _chunk_lanes(g_row, b_row, c)
    g_col, b_col = x["g_col"], x["b_col"]
    last = x["col"][:1] == c - 1
    g_last = jnp.sum(jnp.where(last, g_row, 0.0), axis=1, keepdims=True)
    e_col = jnp.exp(g_col)                    # e^gamma
    e_rest = jnp.exp(g_last - g_col)          # e^{gamma_C - gamma}
    kf, qf = k.astype(jnp.float32), q.astype(jnp.float32)
    return dict(
        x, last=last, e_col=e_col, e_rest=e_rest, e_last=jnp.exp(g_last),
        kf=kf, qf=qf, kw=(b_col * e_col * kf).astype(dt),
        kd=(e_rest * kf).astype(dt), qg=(e_col * qf).astype(dt))


def _chunk_fwd(s, q, k, v, g_row, b_row, t):
    """One chunk. s: the float32 state on entry [dk, dv]; t: the chunk's T
    [C, C] -> (o [C, dv] float32, the state on exit)."""
    dt = q.dtype
    x = _chunk_sides(q, k, g_row, b_row)
    s_in = s.astype(dt)
    r = x["b_col"] * v.astype(jnp.float32) - _nn(x["kw"], s_in)
    delta = _nn(t, r.astype(dt)).astype(dt)
    p = _nt(q, k) * _decay(x, g_row)
    o = _nn(x["qg"], s_in) + _nn(p.astype(dt), delta)
    return o, x["e_last"] * s + _tn(x["kd"], delta)


def _chunk_bwd(s_in, ds, q, k, v, g_row, b_row, do, t):
    """One chunk's transpose. s_in: the entry state as the forward pass
    used it [dk, dv]; ds: float32 cotangent of the exit state; do [C, dv];
    t: the chunk's T as the forward pass used it. Returns float32 (dq, dk,
    dv, dgamma [1, C], dbeta [1, C], cotangent of the entry state)."""
    dt = q.dtype
    x = _chunk_squares(_chunk_sides(q, k, g_row, b_row), _nt(k, k), g_row)
    p = _nt(q, k) * x["decay"]
    b_col, e_col, e_rest = x["b_col"], x["e_col"], x["e_rest"]
    vf = v.astype(jnp.float32)
    r = b_col * vf - _nn(x["kw"], s_in)
    delta = _nn(t, r.astype(dt)).astype(dt)
    ds_in = ds.astype(dt)

    d_delta = _tn(p.astype(dt), do) + _nn(x["kd"], ds_in)
    d_r = _tn(t, d_delta.astype(dt))
    d_r_in = d_r.astype(dt)
    d_p = jnp.where(x["row"] >= x["col"], _nt(do, delta), 0.0)
    d_a = jnp.where(x["row"] > x["col"], -_nt(d_r_in, delta), 0.0)
    d_qg = _nt(do, s_in)
    d_kw = -_nt(d_r_in, s_in)
    d_kd = _nt(delta, ds_in)
    ds_out = _tn(x["qg"], do) + x["e_last"] * ds - _tn(x["kw"], d_r_in)

    d_qk = (d_p * x["decay"]).astype(dt)
    d_kk = (d_a * b_col * x["decay"]).astype(dt)
    dq = _nn(d_qk, k) + e_col * d_qg
    dk = (_tn(d_qk, q) + _nn(d_kk, k) + _tn(d_kk, k)
          + b_col * e_col * d_kw + e_rest * d_kd)
    dv = b_col * d_r

    rows = lambda m: jnp.sum(m, axis=1, keepdims=True)   # noqa: E731
    to_row = lambda col_: jnp.sum(                       # noqa: E731
        jnp.where(x["eye"], col_, 0.0), axis=0, keepdims=True)
    kw_k = rows(d_kw * x["kf"]) * e_col
    kd_k = rows(d_kd * x["kf"]) * e_rest
    d_b = rows(d_a * x["kk"] * x["decay"]) + rows(d_r * vf) + kw_k
    # the decays: D enters P and A; gamma_i adds, gamma_j takes away
    m = d_p * p + d_a * x["a"]
    d_g = (rows(m) + kw_k * b_col + rows(d_qg * x["qf"]) * e_col - kd_k)
    d_g_last = (jnp.sum(kd_k, axis=0, keepdims=True) + x["e_last"] * jnp.sum(
        rows(ds * s_in.astype(jnp.float32)), axis=0, keepdims=True))
    d_g_row = (to_row(d_g) - jnp.sum(m, axis=0, keepdims=True)
               + jnp.where(x["last"], d_g_last, 0.0))
    return dq, dk, dv, d_g_row, to_row(d_b), ds_out


# -- the kernels --------------------------------------------------------------

def kernels_take(q_shape, v_shape, dtype, chunk: int = CHUNK) -> bool:
    """Whether the compiled kernels take these operands: bf16, key and
    value widths that fill the MXU's 128 lanes, whole key-head groups and a
    sequence of whole chunks of a size the inverse takes."""
    _, t, hk, dk = q_shape
    hv, dv = v_shape[2:]
    return (jnp.dtype(dtype) == jnp.bfloat16 and dk % 128 == 0
            and dv % 128 == 0 and hv % hk == 0 and chunk % _INNER == 0
            and t % chunk == 0)


def _beside(t: int, chunk: int) -> int:
    """w, the chunks whose T's lie side by side in the lanes of the stored
    [B, Hv, T/(w C), C, w C]: two where the chunks come in pairs and a pair
    fits a vector register's 128 lanes."""
    return 2 if 2 * chunk <= 128 and (t // chunk) % 2 == 0 else 1


def _block(t: int, chunk: int) -> int:
    """Positions a grid step takes: whole runs of chunks side by side, 1,024
    where that divides (the state's round trip through the scratch and a
    step's fixed cost are then a sixteenth a chunk)."""
    run = _beside(t, chunk) * chunk
    return next((b for b in (1024, 512, 256, 128)
                 if t % b == 0 and b % run == 0), run)


def _loop(n: int, trip: int, visit, reverse: bool = False):
    """``visit(i)`` for i = 0 .. n - 1 in order (n - 1 .. 0 where
    ``reverse``), ``trip`` of them a trip of the loop where that divides:
    what of one visit waits for nothing of the one before it is scheduled
    beside it."""
    trip = math.gcd(n, trip)

    def some(i, carry):
        for j in range(trip):
            visit(n - 1 - (i * trip + j) if reverse else i * trip + j)
        return carry

    lax.fori_loop(0, n // trip, some, 0)


def _prep_kernel(k_ref, g_ref, b_ref, t_ref, *, chunk: int):
    """A key head's block of chunks, for every value head it serves: k k^T
    is the group's. No run of chunks waits for another."""
    span = g_ref.shape[4]

    def run(ri):
        k = k_ref[0, 0, pl.ds(pl.multiple_of(ri * span, span), span), :]
        kk = _nt(k, k)
        for h in range(g_ref.shape[1]):
            t_ref[0, h, ri] = _chunk_prep(
                kk, g_ref[0, h, ri], b_ref[0, h, ri], chunk, t_ref.dtype)

    _loop(g_ref.shape[2], _PREP_TRIP, run)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, o_ref, states_ref,
                s_acc, *, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_acc[...] = jnp.zeros_like(s_acc)

    w = t_ref.shape[4] // chunk

    def run(ri):
        for j in range(w):
            ci = ri * w + j
            rows = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
            s = s_acc[...]
            states_ref[0, 0, ci] = s.astype(states_ref.dtype)
            o, s_acc[...] = _chunk_fwd(
                s, q_ref[0, 0, rows, :], k_ref[0, 0, rows, :],
                v_ref[0, 0, rows, :], g_ref[0, 0, ci], b_ref[0, 0, ci],
                t_ref[0, 0, ri, :, j * chunk:(j + 1) * chunk])
            o_ref[0, 0, rows, :] = o.astype(o_ref.dtype)

    _loop(t_ref.shape[2], _FWD_TRIP, run)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, states_ref, t_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_acc, *,
                chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_acc[...] = jnp.zeros_like(ds_acc)

    w = t_ref.shape[4] // chunk

    def run(ri):
        for j in reversed(range(w)):
            ci = ri * w + j
            rows = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
            dq, dk, dv, dg, db, ds_acc[...] = _chunk_bwd(
                states_ref[0, 0, ci], ds_acc[...], q_ref[0, 0, rows, :],
                k_ref[0, 0, rows, :], v_ref[0, 0, rows, :], g_ref[0, 0, ci],
                b_ref[0, 0, ci], do_ref[0, 0, rows, :],
                t_ref[0, 0, ri, :, j * chunk:(j + 1) * chunk])
            dq_ref[0, 0, rows, :] = dq.astype(dq_ref.dtype)
            dk_ref[0, 0, rows, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, 0, rows, :] = dv.astype(dv_ref.dtype)
            dg_ref[0, 0, ci] = dg
            db_ref[0, 0, ci] = db

    _loop(t_ref.shape[2], _BWD_TRIP, run, reverse=True)


def _specs(t: int, chunk: int, reverse: bool = False):
    """The grid's steps a head and its block specs, by grid (batch, head,
    block of chunks), the blocks taken last to first where ``reverse``:
    ``rows(width, g)`` of a [B, H, T, width] operand, head ``h // g``;
    ``per(every, a, b, heads)`` of a [B, H, T/every, a, b] one, ``heads``
    of them a step."""
    block = _block(t, chunk)
    n = t // block
    at = (lambda i: n - 1 - i) if reverse else (lambda i: i)
    rows = lambda width, g=1: pl.BlockSpec(              # noqa: E731
        (1, 1, block, width), lambda bi, h, i: (bi, h // g, at(i), 0))
    per = lambda every, a, b, heads=1: pl.BlockSpec(     # noqa: E731
        (1, heads, block // every, a, b),
        lambda bi, h, i: (bi, h, at(i), 0, 0))
    return n, rows, per


def _call(kernel, name, interpret, sequential: bool = True, **kwargs):
    """The grid's last axis carries a state from block to block where
    ``sequential``."""
    return pl.pallas_call(
        kernel, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary" if sequential else "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        **kwargs)


def _runs(scalars, w: int):
    """[B, H, T/C, 1, C], a row a chunk -> [B, H, T/(w C), 1, w C], a row a
    run of w chunks."""
    b, h, n, _, c = scalars.shape
    return scalars.reshape(b, h, n // w, 1, w * c)


def _prep_kernels(k, g_rows, b_rows, *, chunk: int, interpret: bool):
    (b, hk, t, dk), hv = k.shape, g_rows.shape[1]
    w = _beside(t, chunk)
    run = w * chunk
    n, rows, per = _specs(t, chunk)
    with jax.named_scope("gdn.chunk_prep"):
        return _call(
            functools.partial(_prep_kernel, chunk=chunk), "gdn_chunk_prep",
            interpret, sequential=False, grid=(b, hk, n),
            in_specs=[rows(dk), per(run, 1, run, hv // hk),
                      per(run, 1, run, hv // hk)],
            out_specs=per(run, chunk, run, hv // hk),
            out_shape=jax.ShapeDtypeStruct((b, hv, t // run, chunk, run),
                                           k.dtype),
        )(k, _runs(g_rows, w), _runs(b_rows, w))


def _fwd_kernels(q, k, v, g_rows, b_rows, t_all, *, chunk: int,
                 interpret: bool):
    b, hv, t, dv = v.shape
    dk, group = q.shape[3], hv // q.shape[1]
    run = t_all.shape[4]
    n, rows, per = _specs(t, chunk)
    with jax.named_scope("gdn.chunk_fwd"):
        return tuple(_call(
            functools.partial(_fwd_kernel, chunk=chunk), "gdn_chunk_fwd",
            interpret, grid=(b, hv, n),
            in_specs=[rows(dk, group), rows(dk, group), rows(dv),
                      per(chunk, 1, chunk), per(chunk, 1, chunk),
                      per(run, chunk, run)],
            out_specs=[rows(dv), per(chunk, dk, dv)],
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct((b, hv, t // chunk, dk, dv),
                                            v.dtype)],
            scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        )(q, k, v, g_rows, b_rows, t_all))


def _bwd_kernels(q, k, v, g_rows, b_rows, do, states_in, t_all, *,
                 chunk: int, interpret: bool):
    b, hv, t, dv = v.shape
    hk, dk = q.shape[1], q.shape[3]
    group, run = hv // hk, t_all.shape[4]
    n, rows, per = _specs(t, chunk, reverse=True)
    scalars = per(chunk, 1, chunk)
    per_head = jax.ShapeDtypeStruct((b, hv, t, dk), jnp.float32)
    with jax.named_scope("gdn.chunk_bwd"):
        dq, dk_, dv_, dg, db = _call(
            functools.partial(_bwd_kernel, chunk=chunk), "gdn_chunk_bwd",
            interpret, grid=(b, hv, n),
            in_specs=[rows(dk, group), rows(dk, group), rows(dv), scalars,
                      scalars, rows(dv), per(chunk, dk, dv),
                      per(run, chunk, run)],
            out_specs=[rows(dk), rows(dk), rows(dv), scalars, scalars],
            out_shape=[per_head, per_head,
                       jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct(g_rows.shape, jnp.float32),
                       jax.ShapeDtypeStruct(g_rows.shape, jnp.float32)],
            scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        )(q, k, v, g_rows, b_rows, do, states_in, t_all)
    return _group_sum(dq, hk, q.dtype), _group_sum(dk_, hk, k.dtype), dv_, \
        dg, db


def _group_sum(per_value_head, hk: int, dtype):
    """[B, Hv, T, dk] float32 -> the key heads' [B, Hk, T, dk]."""
    b, hv, t, d = per_value_head.shape
    return per_value_head.reshape(b, hk, hv // hk, t, d).sum(2).astype(dtype)


# -- the plain form -----------------------------------------------------------

def _by_chunk(x, chunk: int):
    b, h, t, d = x.shape
    return x.reshape(b, h, t // chunk, chunk, d)


def _over_heads(per_head, *operands):
    return jax.vmap(jax.vmap(per_head))(*operands)


def _per_value_head(x, group: int, every: int):
    """A key head's [B, Hk, T, dk], a copy a value head, ``every``
    positions together."""
    return _by_chunk(jnp.repeat(x, group, axis=1), every)


def _prep_plain(k, g_rows, b_rows, *, chunk: int):
    w = _beside(k.shape[2], chunk)
    k = _per_value_head(k, g_rows.shape[1] // k.shape[1], w * chunk)
    return _over_heads(
        jax.vmap(lambda k, g, b: _chunk_prep(_nt(k, k), g, b, chunk,
                                             k.dtype)),
        k, _runs(g_rows, w), _runs(b_rows, w))


def _each_chunks(t_all, chunk: int):
    """T as stored, [B, H, T/(w C), C, w C] -> [B, H, T/C, C, C]."""
    b, h, n, c, run = t_all.shape
    return t_all.reshape(b, h, n, c, run // c, c).transpose(
        0, 1, 2, 4, 3, 5).reshape(b, h, -1, c, c)


def _fwd_plain(q, k, v, g_rows, b_rows, t_all, *, chunk: int):
    dk, dv = q.shape[3], v.shape[3]
    group = v.shape[1] // q.shape[1]

    def per_head(*chunks):
        def step(s, xs):
            o, s_next = _chunk_fwd(s, *xs)
            return s_next, (o.astype(v.dtype), s.astype(v.dtype))
        _, (o, states) = lax.scan(step, jnp.zeros((dk, dv), jnp.float32),
                                  chunks)
        return o.reshape(-1, dv), states

    return _over_heads(
        per_head, _per_value_head(q, group, chunk),
        _per_value_head(k, group, chunk), _by_chunk(v, chunk), g_rows, b_rows,
        _each_chunks(t_all, chunk))


def _bwd_plain(q, k, v, g_rows, b_rows, do, states, t_all, *, chunk: int):
    hk, dk, dv = q.shape[1], q.shape[3], v.shape[3]
    group = v.shape[1] // hk

    def per_head(q, k, v, g, b, do, states, t_each):
        def step(ds, xs):
            s_in, *rest = xs
            *grads, ds = _chunk_bwd(s_in, ds, *rest)
            return ds, grads
        _, grads = lax.scan(step, jnp.zeros((dk, dv), jnp.float32),
                            (states, q, k, v, g, b, do, t_each), reverse=True)
        dq, dk_, dv_, dg, db = grads
        return (dq.reshape(-1, dk), dk_.reshape(-1, dk),
                dv_.reshape(-1, dv).astype(v.dtype), dg, db)

    dq, dk_, dv_, dg, db = _over_heads(
        per_head, _per_value_head(q, group, chunk),
        _per_value_head(k, group, chunk), _by_chunk(v, chunk), g_rows, b_rows,
        _by_chunk(do, chunk), states, _each_chunks(t_all, chunk))
    return _group_sum(dq, hk, q.dtype), _group_sum(dk_, hk, k.dtype), dv_, \
        dg, db


# -- kernel or plain form, and the rule's VJP ---------------------------------

def _choose(kernels, plain, taken: bool, interpret, chunk: int, *operands):
    """As ``llama.attention`` chooses: by the operands while tracing, by the
    platform when lowered; ``interpret`` True / False forces the kernels
    through the Pallas interpreter or the compiler (tests)."""
    if interpret is not None:
        return kernels(*operands, chunk=chunk, interpret=interpret)
    plain = functools.partial(plain, chunk=chunk)
    if not taken:
        return plain(*operands)
    return lax.platform_dependent(
        *operands, default=plain,
        tpu=lambda first, *rest: kernels(
            count_lowering(first, "gdn_lowerings"), *rest, chunk=chunk,
            interpret=False))


def _rows(x, chunk: int):
    """[B, H, T] -> float32 [B, H, T/C, 1, C], a row a chunk."""
    b, h, t = x.shape
    return x.astype(jnp.float32).reshape(b, h, t // chunk, 1, chunk)


def _taken(q, v, chunk: int) -> bool:
    """``kernels_take`` of head-major q [B, Hk, T, dk] and v."""
    (b, hk, t, dk), (_, hv, _, dv) = q.shape, v.shape
    return kernels_take((b, t, hk, dk), (b, t, hv, dv), q.dtype, chunk)


def _forward(q, k, v, g, beta, chunk, interpret):
    """-> (o, the chunks' entry states, every chunk's T under its name: it
    depends on no state, so a caller that saves it by name runs
    ``gdn_chunk_prep`` once however often it runs the rest)."""
    gamma, b_rows = jnp.cumsum(_rows(g, chunk), axis=-1), _rows(beta, chunk)
    how = (_taken(q, v, chunk), interpret, chunk)
    t_all = checkpoint_name(
        _choose(_prep_kernels, _prep_plain, *how, k, gamma, b_rows),
        INVERSE_NAME)
    return *_choose(_fwd_kernels, _fwd_plain, *how, q, k, v, gamma, b_rows,
                    t_all), t_all


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk, interpret):
    return _forward(q, k, v, g, beta, chunk, interpret)[0]


def _rule_fwd(q, k, v, g, beta, chunk, interpret):
    o, states, t_all = _forward(q, k, v, g, beta, chunk, interpret)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    states = checkpoint_name(states, RESIDUAL_NAMES[1])
    return o, (q, k, v, g, beta, states, t_all)


def _rule_bwd(chunk, interpret, residuals, do):
    q, k, v, g, beta, states, t_all = residuals
    gamma = jnp.cumsum(_rows(g, chunk), axis=-1)
    dq, dk, dv, d_gamma, d_beta = _choose(
        _bwd_kernels, _bwd_plain, _taken(q, v, chunk), interpret, chunk, q, k,
        v, gamma, _rows(beta, chunk), do, states, t_all)
    # gamma is a chunk's running sum of g: its transpose runs the other way
    dg = jnp.flip(jnp.cumsum(jnp.flip(d_gamma, -1), axis=-1), -1)
    return (dq, dk, dv, dg.reshape(g.shape).astype(g.dtype),
            d_beta.reshape(beta.shape).astype(beta.dtype))


_rule.defvjp(_rule_fwd, _rule_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK,
                     interpret: bool | None = None):
    """q, k: [B, T, Hk, dk]; v: [B, T, Hv, dv]; g (log-decay, <= 0) and beta:
    [B, T, Hv] -> o [B, T, Hv, dv] in v's dtype, differentiable in all five.
    T must be whole chunks."""
    t = q.shape[1]
    if t % chunk or (chunk > _INNER and chunk % _INNER):
        raise ValueError(f"seq {t} must be whole chunks of {chunk}, a chunk "
                         f"at most {_INNER} or a multiple of it")
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))   # [B, H, T, D]
    g, beta = (x.transpose(0, 2, 1) for x in (g, beta))      # [B, H, T]
    return _rule(q, k, v, g, beta, chunk, interpret).transpose(0, 2, 1, 3)
