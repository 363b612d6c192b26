"""Flash attention — Pallas TPU kernels for the model hot path, forward and
backward.

No array shaped like the score matrix touches HBM in either pass. The
forward kernel owns one [BLOCK_Q, D] query tile and streams K/V tiles
through the MXU with the online-softmax recurrence (running max / sum /
accumulator); it also returns each row's log-sum-exp, which with q, k, v and
the output is all the backward pass keeps. Of those five the kernel's own
two results carry names (``RESIDUAL_NAMES``: the head-major output — with a
gate, the gated output — and the log-sum-exp), so that a caller who
recomputes its layers under ``jax.checkpoint`` can save them by name and not
run the forward kernel a second time; q, k and v are its inputs and are the
caller's to keep or recompute. A per-head output gate (``gate``: one float32
logit a query head and position, ``flash_attention_head_major``) is the
forward kernel's epilogue, each row of ``acc / l`` times the sigmoid of its
logit before the one cast; its gradient is ``(1 - sigmoid) * delta``, and
the backward kernel is the same with and without one. The backward pass is
one kernel, per KV head and key tile: it recomputes the probabilities and dS
from q, k, the log-sum-exp and ``delta`` once for each pair of tiles and
takes all three gradients from them. It loops over the head's whole query
group, so that the GQA sum happens in dK's and dV's accumulators; dQ of the
group gathers in VMEM, in float32, across the head's key tiles (the grid's
last axis, which runs in order) and is written once, at the last of them.
Causal programs stop at the diagonal, so the wasted triangle is skipped at
tile granularity, and only the tiles the diagonal crosses pay for a mask
(guide: /opt/skills/guides/pallas_guide.md).

Precision: the MXU gets its operands in the dtype they arrive in (bf16 on
the training path) and accumulates in float32; scores, softmax statistics,
``delta`` and every accumulator are float32; the probabilities and dS are
cast to the operand dtype only as matmul operands.

GQA layout, as every model's projections leave it: q [B, T, Hq, D], k/v
[B, T, Hkv, D]; the kv head for q head h is h // (Hq // Hkv). q and k share
one width and v (with the output and dO) may have another: latent attention
(models/deepseek.py) has q, k of 192 = 128 + 64 rope dims and v of 128. The
scores are scaled by the q/k width. Inside, the
kernels work head-major ([B, H, T, D]): Mosaic wants the last two block
dimensions to be (a multiple of 8, a multiple of 128) or the whole array
dimension, and a one-head block of the [B, T, H, D] layout has 1 against H
there. The wrapper pays the transposes for it, outside the custom VJP, so
the residuals are kept head-major and the backward pass repeats none; a
caller whose q and k are head-major already (ops/qk_layout.py writes them
so) enters at ``flash_attention_head_major``, which turns v and the output
alone. Per-row
statistics travel as [B, H, T/BLOCK, 1, BLOCK]: a row vector per tile, whole
in its last two dimensions whatever the block.

K and V of one head in the forward kernel, and in the backward kernel Q, dO
and both forms of dQ of one query group, stay whole in VMEM. Where a whole
group is too much for that (8 query heads a KV head of 256 at 8,192 tokens:
models/hybrid.py's gated attention), the backward kernel takes the query side
through the grid instead, one query head a program row against its KV head's
key tiles, writes that head's own dK and dV in float32, and the group's sum
is one pass outside the kernel. ``supported`` bounds the sequence by what one
query head needs.

A window (``window=W``: query i sees key j iff 0 <= i - j < W, sliding-window
attention) is the same two kernels under names of their own,
``attn_band_fwd`` and ``attn_band_bwd``: a query tile's loop starts at the
first key tile the band's lower edge reaches (a key tile's loop stops at the
last query tile it reaches), so the tiles outside the band are never
visited, and only the tiles that one of the band's two edges cuts pay for a
mask. A query tile of B rows loads B + W - 1 keys' worth of tiles for its
B x W visible pairs. Where W is a whole number of equal tiles (``_merges``),
the lower edge's tile and the diagonal's are complementary triangles, and
the kernels take them in one step: both score tiles on the MXU, one select
into one tile, one softmax over it with no rescale between the two, and
each tile's products from its own half; any other window pays a softmax
and a mask a cut tile (``choose_block`` has the measurements).
``band_tiles`` counts, with the kernels' own arithmetic, the pairs of
positions loaded against the pairs visible, and ``band_softmax_pairs`` the
pairs the softmax runs over. ``window=None`` is the causal path as it was.

``flash_attention(..., interpret=True)`` runs the same kernels through the
Pallas interpreter (CPU tests); on TPU leave it False.

The models do not call the kernels: they call ``attention`` (token-major q
and k) or ``attention_head_major`` (q and k head-major, an output gate or
none), which choose the kernels or ``dense_attention`` by the operands while
tracing and by the platform when lowered, and count which one each lowered
program holds. ``dense_attention`` is also the plain form the tests hold the
kernels to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from brpc_tpu.ops.lowered import VMEM_LIMIT, compiler_params, count_lowering

_MASKED = -1e30          # finite: exp(_MASKED - finite) is 0, never NaN

# ``checkpoint_name``s of the forward kernel's head-major output and its
# log-sum-exp. A ``jax.checkpoint`` whose policy saves them does not run the
# forward kernel again in its recomputation; anywhere else they are
# identities.
RESIDUAL_NAMES = ("attn_flash_out", "attn_flash_lse")


def choose_block(t: int, backward: bool = False,
                 window: int | None = None) -> int:
    """The tile along a sequence of ``t``, queries and keys alike: 512 where
    it divides, and in the backward kernel 1,024 from 8,192 tokens on. On a
    v5e, each kernel alone (PERF.md section 6, PR 31): at T = 2,048, 32
    heads over 8 of 128, forward 0.61 ms and backward 0.95 at 512 against
    0.67 / 1.10 at 1,024 and 1.25 at 256 (the diagonal's tiles are half
    wasted, and there are few others); at T = 8,192, 32 heads of 192 / 128,
    forward 7.8 ms at 512 and 8.1 at 1,024, backward 15.5 at 512, 15.0 at
    1,024 and 18.5–19.8 at 256; at 4,096 tokens the backward kernel reads
    the same at both (4.59 / 4.60 ms), at 8,192 tokens of 128 / 128 9.8 /
    9.1. The log-sum-exp's rows are the same bytes whatever the tile, so the
    two passes need not share one. A sequence none of them divides is one
    tile. With a window the backward kernel stays at 512 too: a tile of B
    rows touches B + W - 1 keys' worth, and where W is a whole number of
    tiles the lower edge's and the diagonal's share one softmax
    (``_merges``; its times in PERF.md section 7 row 6). Before that merge,
    at T = 8,192, W = 512, 64
    heads over 8 of 128 (PERF.md section 6, PR 39; by query tile, key tile,
    two calls of one timing): forward 6.3 / 6.5 ms and backward 10.3 / 10.3
    at (512, 512) (2.0 times the visible pairs visited) against 7.2 / 7.2
    and 11.3 / 11.5 at (256, 256) (1.5 times), 6.1 / 6.2 and 11.7 / 12.0 at
    (256, 512), 7.6 / 7.6 and 10.5 / 10.8 at (512, 256); in the first call
    alone 10.2 and 14.8 at (128, 128) (1.25 times) and 12.9 and 22.5 ms for
    the causal kernels on the same heads."""
    sizes = (512, 256, 128)
    if backward and t >= 8192 and window is None:
        sizes = (1024, *sizes)
    return next((c for c in sizes if t % c == 0), t)


def _resident(group: int, t: int, d_qk: int, d_v: int, dtype) -> int:
    """Bytes of the backward kernel that stay whole in VMEM for ``group``
    query heads: Q and dO (double-buffered by the pipeline), dQ transposed
    in float32 and dQ's output block (double-buffered too)."""
    lanes_qk = -(-d_qk // 128) * 128
    lanes_v = -(-d_v // 128) * 128
    size = jnp.dtype(dtype).itemsize
    return group * t * (2 * (lanes_qk + lanes_v) * size  # Q, dO
                        + d_qk * 4                          # dQ.T
                        + 2 * lanes_qk * size)              # dQ's block


def heads_together(group: int, t: int, d_qk: int, d_v: int, dtype) -> bool:
    """Whether the backward kernel takes a KV head's whole query group at
    once (the GQA sum then happens in dK's and dV's accumulators), or each
    query head on its own with the group summed afterwards."""
    return _resident(group, t, d_qk, d_v, dtype) <= VMEM_LIMIT // 2


def supported(q_shape, kv_shape, dtype, v_shape=None, window=None) -> bool:
    """Whether the compiled kernels take these operands: whole query groups,
    q/k and v widths (``v_shape``, k's where it is not given) that are
    multiples of 64 (192 or 64 is a block as wide as its array, which Mosaic
    takes whole and pads to 256 or 128 lanes in VMEM; a 192-wide
    contraction or result fills one and a half passes of the 128-wide MXU,
    a 64-wide one half a pass: models/mamba.py's heads), and a sequence
    whose resident blocks leave VMEM room for the score tiles. The backward kernel holds the most (``_resident``):
    27.3 MB at T = 8,192, one head a group, 192 / 128, and 16.8 MB at
    T = 2,048, four heads a group, 128 / 128, where a group of 4 fits 4,096
    tokens. A group that does not fit whole — 8 query heads a KV head of
    256 / 256 at T = 8,192 would be 268 MB — goes through the kernel a query
    head at a time (``heads_together``), which is 32 MiB there, the most the
    kernels admit: 256 / 256 stops at 8,192 tokens, 192 / 128 at 9,728 and
    128 / 128 at 16,384, whatever the group. The forward kernel's K and V of
    a head are less than that at any group size. A window may be any
    whole number of positions from 1 on, a multiple of no tile: the edges'
    tiles are masked by position; from T on it is the causal mask."""
    _, t, hq, d_qk = q_shape
    if window is not None and window < 1:
        return False
    hkv = kv_shape[2]
    d_v = (v_shape or kv_shape)[3]
    if d_qk % 64 or d_v % 64 or t % 128 or hq % hkv:
        return False
    return _resident(1, t, d_qk, d_v, dtype) <= VMEM_LIMIT // 2


def _nt(a, b):
    """a @ b.T on the MXU, float32 out."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _tn(a, b):
    """a.T @ b on the MXU, float32 out."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _scores(a, b, scale: float, masked: bool, q0, k0, q_axis: int,
            window=None):
    """The float32 score tile ``a @ b.T * scale``. ``masked``: causally, for
    a tile whose queries start at ``q0`` along ``q_axis`` and whose keys
    start at ``k0`` along the other axis, and with a ``window`` also to the
    keys fewer than ``window`` positions back."""
    s = _nt(a, b) * scale
    if not masked:
        return s
    q_pos = q0 + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    seen = k_pos <= q_pos
    if window is not None:
        seen &= k_pos > q_pos - window
    return jnp.where(seen, s, _MASKED)


def _key_tiles(qi, block_q: int, block_k: int, t: int, causal: bool):
    """For query tile ``qi``: how many key tiles lie wholly at or below the
    diagonal (no mask needed), and how many it attends to at all."""
    n = t // block_k
    if not causal:
        return n, n
    return (qi * block_q + 1) // block_k, pl.cdiv((qi + 1) * block_q, block_k)


def _query_tiles(kj, block_q: int, block_k: int, t: int, causal: bool):
    """For key tile ``kj``: the first query tile that attends to it, and the
    first that sees all of it (no mask needed)."""
    if not causal:
        return 0, 0
    return ((kj * block_k) // block_q,
            jnp.minimum(pl.cdiv((kj + 1) * block_k - 1, block_q),
                        t // block_q))


def _band_key_tiles(qi, block_q: int, block_k: int, window: int):
    """For query tile ``qi`` under a window, its loops over key tiles in
    order, each (first tile, one past the last, whether masked): the tiles
    the band's lower edge cuts, the tiles wholly inside the band (at or
    below the diagonal of the tile's first query and fewer than ``window``
    back from its last), the tiles the diagonal cuts. No other tile holds a
    visible pair."""
    q0, q1 = qi * block_q, (qi + 1) * block_q
    lo = jnp.maximum(q0 - window + 1, 0) // block_k
    hi = pl.cdiv(q1, block_k)
    c0 = jnp.clip(pl.cdiv(jnp.maximum(q1 - window, 0), block_k), lo, hi)
    c1 = jnp.clip((q0 + 1) // block_k, c0, hi)
    return (lo, c0, True), (c0, c1, False), (c1, hi, True)


def _band_query_tiles(kj, block_q: int, block_k: int, t: int, window: int):
    """For key tile ``kj`` under a window, its loops over query tiles in
    order: the tiles the diagonal cuts, the tiles that see all of it, the
    tiles the band's lower edge cuts."""
    k0, k1 = kj * block_k, (kj + 1) * block_k
    lo = k0 // block_q
    hi = jnp.minimum(pl.cdiv(k1 + window - 1, block_q), t // block_q)
    c0 = jnp.clip(pl.cdiv(k1 - 1, block_q), lo, hi)
    c1 = jnp.clip((k0 + window) // block_q, c0, hi)
    return (lo, c0, True), (c0, c1, False), (c1, hi, True)


def _merges(window: int, block_q: int, block_k: int) -> bool:
    """Whether the band kernels take the two tiles the band's edges cut as
    one score tile: where the window is a whole number of equal tiles, a
    query tile's lower edge's tile (``window // block_k`` tiles back) and
    its diagonal's tile are complementary triangles in local coordinates,
    key b visible to query a iff b > a in the first and b <= a in the
    second."""
    return window % block_k == 0 and block_q == block_k


def _diagonal_half(shape, q_axis: int):
    """Within a tile pair on the diagonal, in local coordinates: whether
    the key is at or before the query (the pairs the diagonal's tile holds;
    the lower edge's tile holds the others)."""
    return (lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
            <= lax.broadcasted_iota(jnp.int32, shape, q_axis))


def _halves(diag, x):
    """A merged score tile's ``x`` split back into the diagonal's tile's
    half and the lower edge's, each zero where the other tile sees."""
    return jnp.where(diag, x, 0), jnp.where(diag, 0, x)


def _band_key_loops(qi, block_q: int, block_k: int, window: int):
    """The forward kernel's loops for query tile ``qi`` under a window: the
    merged loop (first, one past the last lower edge's tile, each taken
    with the diagonal's tile ``window // block_k`` on), or None where the
    tiles do not merge, and the other loops as ``_band_key_tiles`` gives
    them. The lower edge cuts at most one tile where they merge: a query
    tile that has one runs no diagonal tile alone."""
    edge, clear, diag = _band_key_tiles(qi, block_q, block_k, window)
    if not _merges(window, block_q, block_k):
        return None, (edge, clear, diag)
    return edge[:2], (clear, (diag[0] + edge[1] - edge[0], diag[1], True))


def _band_query_loops(kj, block_q: int, block_k: int, t: int, window: int):
    """The backward kernel's loops for key tile ``kj`` under a window: the
    merged loop (first, one past the last diagonal's query tile, each taken
    with the lower edge's tile ``window // block_q`` on), or None where the
    tiles do not merge, and the other loops. Where they merge and the edge's
    tile lies past T, the diagonal's tile runs alone."""
    diag, clear, edge = _band_query_tiles(kj, block_q, block_k, t, window)
    if not _merges(window, block_q, block_k):
        return None, (diag, clear, edge)
    split = diag[0] + edge[1] - edge[0]
    return (diag[0], split), ((split, diag[1], True), clear)


def band_tiles(t: int, window: int, blocks: tuple) -> dict:
    """What the band kernels visit at a sequence of ``t``, by their own
    arithmetic: pairs of positions in the tile pairs the forward and the
    backward kernel loop over, a head, and the pairs the band holds.
    ``blocks``: as ``_attend`` takes them."""
    (fq, fk), (bq, bk) = blocks
    fwd = sum(int(stop - start) for qi in range(t // fq)
              for start, stop, _ in _band_key_tiles(qi, fq, fk, window))
    bwd = sum(int(stop - start) for kj in range(t // bk)
              for start, stop, _ in _band_query_tiles(kj, bq, bk, t, window))
    w = min(window, t)
    return {"fwd_pairs": fwd * fq * fk, "bwd_pairs": bwd * bq * bk,
            "visible_pairs": w * (w + 1) // 2 + (t - w) * w}


def band_softmax_pairs(t: int, window: int, blocks: tuple) -> dict:
    """The pairs of positions the band kernels' softmax runs over at a
    sequence of ``t``, a head, by their own arithmetic: a merged step's two
    tiles are one tile of softmax. ``blocks``: as ``_attend`` takes them."""
    def tiles(loops):
        merged, ranges = loops
        return (sum(int(stop - start) for start, stop, _ in ranges)
                + (int(merged[1] - merged[0]) if merged else 0))

    (fq, fk), (bq, bk) = blocks
    fwd = sum(tiles(_band_key_loops(qi, fq, fk, window))
              for qi in range(t // fq))
    bwd = sum(tiles(_band_query_loops(kj, bq, bk, t, window))
              for kj in range(t // bk))
    return {"fwd_softmax_pairs": fwd * fq * fk,
            "bwd_softmax_pairs": bwd * bq * bk}


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def band_calls(jaxpr) -> set:
    """The band kernels' calls in a traced program (``jax.jit(f).trace(...)
    .jaxpr.jaxpr``, sub-jaxprs included), each as (kernel name, query tile,
    key tile) read from the call itself, for ``band_tiles``: the tiles a
    step really runs, whoever chose them. The forward kernel's query tile is
    its block of q and its key tile the rows of K its loops load at a time;
    the backward kernel's key tile is its block of k and its query tile the
    width of a row of the log-sum-exp."""
    found = set()
    for eqn in _equations(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        name, refs = eqn.params["name"], eqn.params["jaxpr"].invars
        if name == "attn_band_fwd":
            # q's block is K's whole shape where one tile holds all queries:
            # of the loads from refs shaped like K the smallest is K's
            block_k = min(
                e.outvars[0].aval.shape[0]
                for e in _equations(eqn.params["jaxpr"])
                if e.primitive.name == "get"
                and e.invars[0].aval == refs[1].aval)
            found.add((name, refs[0].aval.shape[2], block_k))
        elif name == "attn_band_bwd":
            found.add((name, refs[4].aval.shape[-1], refs[1].aval.shape[2]))
    return found


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, block_k: int, causal: bool,
                scale: float, window=None):
    """``refs``: the gate's logits where there is a gate, then the output
    and the log-sum-exp."""
    *gate_ref, o_ref, lse_ref = refs
    block_q = q_ref.shape[2]
    t, d_v = v_ref.shape[2:]
    qi = pl.program_id(2)
    q = q_ref[0, 0]                                          # [BQ, Dqk]

    def tiles(kj):
        rows = pl.ds(pl.multiple_of(kj * block_k, block_k), block_k)
        return k_ref[0, 0, rows, :], v_ref[0, 0, rows, :]

    def online(carry, s, products):
        """The online softmax's step over the score tile ``s``;
        ``products(p)``: the probabilities' [BQ, Dv] product with V."""
        m, l, acc = carry
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        return m_new, l, acc * alpha + products(p)

    def step(kj, carry, masked):
        k, v = tiles(kj)
        s = _scores(q, k, scale, masked, qi * block_q, kj * block_k, 0,
                    window)
        return online(carry, s, lambda p: _nn(p.astype(v.dtype), v))

    def merged_step(kj, carry):
        """The lower edge's tile ``kj`` and the diagonal's as one score
        tile, each pair taken from the tile that sees it: one softmax, and
        each tile's probabilities against its own V."""
        (k_edge, v_edge), (k_diag, v_diag) = tiles(kj), tiles(kj + shift)
        diag = _diagonal_half((block_q, block_k), 0)
        s = jnp.where(diag, _nt(q, k_diag), _nt(q, k_edge)) * scale

        def products(p):
            p_diag, p_edge = _halves(diag, p)
            return (_nn(p_diag.astype(v_diag.dtype), v_diag)
                    + _nn(p_edge.astype(v_edge.dtype), v_edge))

        return online(carry, s, products)

    merged = None
    if window is None:
        n_clear, n_k = _key_tiles(qi, block_q, block_k, t, causal)
        ranges = ((0, n_clear, False), (n_clear, n_k, True))
    else:
        # the lower edge's tiles come first: a row all masked in them has
        # its sums wiped (alpha = 0) by its first visible key
        merged, ranges = _band_key_loops(qi, block_q, block_k, window)
        shift = window // block_k
    carry = (jnp.full((block_q, 1), -jnp.inf, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32),
             jnp.zeros((block_q, d_v), jnp.float32))
    if merged:
        carry = lax.fori_loop(*merged, merged_step, carry)
    for start, stop, masked in ranges:
        carry = lax.fori_loop(start, stop,
                              functools.partial(step, masked=masked), carry)
    m, l, acc = carry
    o = acc / l
    if gate_ref:        # each row scaled by its sigmoid, before the one cast
        o = o * jax.nn.sigmoid(gate_ref[0][0, 0, 0]).reshape(block_q, 1)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    lse_ref[0, 0, 0] = (m + jnp.log(l)).reshape(1, block_q)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dqt_acc, *, block_q: int, causal: bool,
                scale: float, window=None):
    """One key tile of one KV head against the query tiles of its whole
    query group, the probabilities and dS recomputed once a tile pair.
    Scores are held transposed ([BK, BQ]), so the per-query statistics
    broadcast as the row vectors they are stored as. dK and dV gather in the
    loop's carry. dQ gathers transposed, ``k.T @ dS`` into the [D, T] of
    each head of the group in ``dqt_acc``, across the key tiles (the grid's
    last axis, which runs in order), and is turned back, scaled and written
    once, at the head's last key tile: the one product that contracts over
    the tile's leading dimension then transposes k, the narrow operand,
    where ``dS.T @ k`` would transpose a [BK, BQ] tile a pair."""
    group, t = q_ref.shape[1:3]
    block_k, d = k_ref.shape[2:]
    d_v = v_ref.shape[3]
    kj = pl.program_id(2)
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    merged = None
    if window is None:
        first, clear = _query_tiles(kj, block_q, block_k, t, causal)
        ranges = ((first, clear, True), (clear, t // block_q, False))
    else:
        merged, ranges = _band_query_loops(kj, block_q, block_k, t, window)
        shift = window // block_q

    def tile(qj):
        return pl.ds(pl.multiple_of(qj * block_q, block_q), block_q)

    @pl.when(kj == 0)
    def _():
        dqt_acc[...] = jnp.zeros_like(dqt_acc)

    def step(qj, carry, g, masked):
        dk, dv = carry
        q = q_ref[0, g, tile(qj), :]
        do = do_ref[0, g, tile(qj), :]
        s = _scores(k, q, scale, masked, qj * block_q, kj * block_k, 1,
                    window)
        p = jnp.exp(s - lse_ref[0, g, qj])
        dv = dv + _nn(p.astype(do.dtype), do)
        ds = (p * (_nt(v, do) - delta_ref[0, g, qj])).astype(q.dtype)
        dqt_acc[g, :, tile(qj)] += _tn(k, ds)
        return dk + _nn(ds, q), dv

    def merged_step(qj, carry, g):
        """The diagonal's query tile ``qj`` and the lower edge's as one
        score tile, each pair taken from the tile that sees it, with that
        tile's log-sum-exp and ``delta``: the probabilities and dS made
        once, each tile's gradients from its own half."""
        dk, dv = carry
        edge = qj + shift
        q_diag, q_edge = q_ref[0, g, tile(qj), :], q_ref[0, g, tile(edge), :]
        do_diag = do_ref[0, g, tile(qj), :]
        do_edge = do_ref[0, g, tile(edge), :]
        diag = _diagonal_half((block_k, block_q), 1)
        s = jnp.where(diag, _nt(k, q_diag), _nt(k, q_edge)) * scale
        p = jnp.exp(s - jnp.where(diag, lse_ref[0, g, qj],
                                  lse_ref[0, g, edge]))
        p_diag, p_edge = _halves(diag, p)
        dv = (dv + _nn(p_diag.astype(do_diag.dtype), do_diag)
              + _nn(p_edge.astype(do_edge.dtype), do_edge))
        dp = jnp.where(diag, _nt(v, do_diag), _nt(v, do_edge))
        ds = p * (dp - jnp.where(diag, delta_ref[0, g, qj],
                                 delta_ref[0, g, edge]))
        ds_diag, ds_edge = (x.astype(q_diag.dtype) for x in _halves(diag, ds))
        dqt_acc[g, :, tile(qj)] += _tn(k, ds_diag)
        dqt_acc[g, :, tile(edge)] += _tn(k, ds_edge)
        return dk + _nn(ds_diag, q_diag) + _nn(ds_edge, q_edge), dv

    dk = jnp.zeros((block_k, d), jnp.float32)
    carry = (dk, dk if d_v == d else jnp.zeros((block_k, d_v), jnp.float32))
    for g in range(group):
        if merged:
            carry = lax.fori_loop(
                *merged, functools.partial(merged_step, g=g), carry)
        for start, stop, masked in ranges:
            carry = lax.fori_loop(
                start, stop, functools.partial(step, g=g, masked=masked),
                carry)
    dk, dv = carry
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _():
        def put(qj, carry, g):
            dq_ref[0, g, tile(qj), :] = (
                dqt_acc[g, :, tile(qj)].T * scale).astype(dq_ref.dtype)
            return carry
        for g in range(group):
            lax.fori_loop(0, t // block_q, functools.partial(put, g=g), 0)


def _call(kernel, name, interpret, last_axis="parallel", **kwargs):
    return pl.pallas_call(
        kernel, name=name, interpret=interpret,
        compiler_params=compiler_params(("parallel", "parallel", last_axis)),
        **kwargs)


def _tile_spec(rows: int, width: int):
    """One head's [rows, width] tile of a head-major array, by grid (batch,
    head, tile)."""
    return pl.BlockSpec((1, 1, rows, width), lambda bi, h, i: (bi, h, i, 0))


def _whole_spec(t: int, width: int, group: int):
    """The whole [T, width] of the KV head that query head ``h`` attends."""
    return pl.BlockSpec((1, 1, t, width),
                        lambda bi, h, i: (bi, h // group, 0, 0))


def _forward(q, k, v, gate, causal: bool, blocks: tuple, interpret: bool,
             window=None):
    """Head-major q [B,Hq,T,Dqk], k [B,Hkv,T,Dqk], v [B,Hkv,T,Dv] and the
    gate's float32 logits [B,Hq,T] or None -> (o [B,Hq,T,Dv], each row
    times the sigmoid of its logit where there is a gate, float32 lse
    [B,Hq,T/BQ,1,BQ])."""
    b, hq, t, d = q.shape
    d_v = v.shape[3]
    group = hq // k.shape[1]
    block_q, block_k = blocks
    kind = "flash" if window is None else "band"
    row_spec = pl.BlockSpec((1, 1, 1, 1, block_q),
                            lambda bi, h, i: (bi, h, i, 0, 0))
    operands, gate_specs = (q, k, v), []
    if gate is not None:        # a row a query tile, as the lse is written
        operands += (gate.reshape(b, hq, t // block_q, 1, block_q),)
        gate_specs = [row_spec]
    with jax.named_scope(f"attn.{kind}_fwd"):
        return _call(
            functools.partial(_fwd_kernel, block_k=block_k, causal=causal,
                              scale=d ** -0.5, window=window),
            f"attn_{kind}_fwd", interpret,
            grid=(b, hq, t // block_q),
            in_specs=[_tile_spec(block_q, d), _whole_spec(t, d, group),
                      _whole_spec(t, d_v, group), *gate_specs],
            out_specs=[_tile_spec(block_q, d_v), row_spec],
            out_shape=[jax.ShapeDtypeStruct((b, hq, t, d_v), q.dtype),
                       jax.ShapeDtypeStruct((b, hq, t // block_q, 1, block_q),
                                            jnp.float32)],
        )(*operands)


def _backward(q, k, v, gate, o, lse, do, causal: bool, blocks: tuple,
              interpret: bool, window=None):
    """With a gate, ``o`` is the gated output σ·O and ``do`` its cotangent:
    the kernel's dO is σ·do, ``delta`` = Σ dO·O = Σ do·o is the formula
    without a gate, and the logits' cotangent σ(1 − σ) Σ O·do is (1 − σ) ·
    delta. The backward kernel is the same either way."""
    b, hq, t, d = q.shape
    d_v = v.shape[3]
    hkv = k.shape[1]
    # A query group too large to stay whole in VMEM: every query head is a
    # group of its own against its KV head's tiles, its dK and dV come out
    # in float32 a query head, and the group is summed after the kernel.
    together = heads_together(hq // hkv, t, d, d_v, q.dtype)
    group, shared = (hq // hkv, 1) if together else (1, hq // hkv)
    heads = hq // group
    scale = d ** -0.5
    block_q, block_k = blocks
    if not interpret and block_q % 128:
        raise ValueError(f"the compiled backward kernel slices dQ's [D, T] "
                         f"accumulator by query tile along the lanes: a "
                         f"tile of {block_q} is no multiple of 128")
    kind = "flash" if window is None else "band"
    with jax.named_scope(f"attn.{kind}_bwd"):
        # a row per query tile: of this pass's tiles, whatever the forward
        # pass's were (the same bytes in the same order)
        lse = lse.reshape(b, hq, t // block_q, 1, block_q)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        dgate = None
        if gate is not None:
            sigma = jax.nn.sigmoid(gate)
            dgate = (1 - sigma) * delta
            do = (do.astype(jnp.float32) * sigma[..., None]).astype(do.dtype)
        delta = delta.reshape(lse.shape)

        def group_spec(width):
            return pl.BlockSpec((1, group, t, width),
                                lambda bi, h, j: (bi, h, 0, 0))

        row_spec = pl.BlockSpec((1, group, t // block_q, 1, block_q),
                                lambda bi, h, j: (bi, h, 0, 0, 0))
        kv_specs = [_tile_spec(block_k, d), _tile_spec(block_k, d_v)]
        kv_in = kv_specs if together else [
            pl.BlockSpec((1, 1, block_k, width),
                         lambda bi, h, j: (bi, h // shared, j, 0))
            for width in (d, d_v)]
        kv_dtype = k.dtype if together else jnp.float32
        dq, dk, dv = _call(
            functools.partial(_bwd_kernel, block_q=block_q, causal=causal,
                              scale=scale, window=window),
            f"attn_{kind}_bwd", interpret, last_axis="arbitrary",
            grid=(b, heads, t // block_k),
            in_specs=[group_spec(d), *kv_in, group_spec(d_v), row_spec,
                      row_spec],
            out_specs=[group_spec(d), *kv_specs],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct((b, heads, t, d), kv_dtype),
                       jax.ShapeDtypeStruct((b, heads, t, d_v), kv_dtype)],
            scratch_shapes=[pltpu.VMEM((group, d, t), jnp.float32)],
        )(q, k, v, do, lse, delta)
        if not together:
            dk, dv = (x.reshape(b, hkv, shared, t, -1).sum(2).astype(k.dtype)
                      for x in (dk, dv))
    return dq, dk, dv, dgate


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attend(q, k, v, gate, causal, blocks, interpret, window):
    """``gate``: the gate's float32 logits [B, Hq, T], or None for no gate.
    ``blocks``: the forward pass's (query tile, key tile), then the backward
    pass's."""
    return _forward(q, k, v, gate, causal, blocks[0], interpret, window)[0]


def _attend_fwd(q, k, v, gate, causal, blocks, interpret, window):
    o, lse = _forward(q, k, v, gate, causal, blocks[0], interpret, window)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return o, (q, k, v, gate, o, lse)


def _attend_bwd(causal, blocks, interpret, window, residuals, do):
    return _backward(*residuals, do, causal, blocks[1], interpret, window)


_attend.defvjp(_attend_fwd, _attend_bwd)


def default_blocks(t: int, window=None, block_q=None, block_k=None) -> tuple:
    """((query tile, key tile) of the forward pass, of the backward pass)
    at a sequence of ``t``; ``block_q`` / ``block_k`` override both."""
    return tuple(tuple(min(given or choose_block(t, backward, window), t)
                       for given in (block_q, block_k))
                 for backward in (False, True))


def _checked_blocks(t: int, causal: bool, window, block_q, block_k) -> tuple:
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"a window of {window} needs causal attention and "
                         f"at least one position")
    blocks = default_blocks(t, window, block_q, block_k)
    if any(t % block for pair in blocks for block in pair):
        raise ValueError(f"seq {t} must divide blocks {blocks}")
    return blocks


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "window"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """q: [B,T,Hq,Dqk], k: [B,T,Hkv,Dqk], v: [B,T,Hkv,Dv] -> [B,T,Hq*Dv]
    (``attention``'s contract), differentiable. The tiles are chosen from T
    (and from whether there is a ``window``, which needs ``causal``);
    ``block_q`` / ``block_k`` override them in both passes (tests)."""
    b, t, hq, _ = q.shape
    blocks = _checked_blocks(t, causal, window, block_q, block_k)
    with jax.named_scope("attn.layout"):                    # [B, H, T, D]
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _attend(q, k, v, None, causal, blocks, interpret, window)
    with jax.named_scope("attn.layout"):
        return out.transpose(0, 2, 1, 3).reshape(b, t, hq * v.shape[3])


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def flash_attention_head_major(q: jax.Array, k: jax.Array, v: jax.Array,
                               gate: jax.Array | None = None, *,
                               interpret: bool = False,
                               window: int | None = None) -> jax.Array:
    """Causal ``flash_attention`` for a caller whose q and k are head-major
    already (ops/qk_layout.py): q: [B,Hq,T,Dqk], k: [B,Hkv,T,Dqk], v:
    [B,T,Hkv,Dv] -> [B,T,Hq*Dv]. Neither q nor k is transposed, in either
    pass; v and the output go the way they go there. ``gate``: a per-head
    output gate's float32 logits [B,T,Hq] or None; with them each head's
    output is ``o_h * sigmoid(g_h)``, taken in float32 by the forward
    kernel before its one cast, and the logits' gradient comes from the
    backward pass's ``delta``."""
    b, hq, t, _ = q.shape
    blocks = _checked_blocks(t, True, window, None, None)
    with jax.named_scope("attn.layout"):
        v = v.transpose(0, 2, 1, 3)
        if gate is not None:
            gate = gate.transpose(0, 2, 1)
    out = _attend(q, k, v, gate, True, blocks, interpret, window)
    with jax.named_scope("attn.layout"):
        return out.transpose(0, 2, 1, 3).reshape(b, t, hq * v.shape[3])


# -- the kernels or the dense form --------------------------------------------

def dense_attention(q, k, v, *, causal: bool = True, window=None):
    """Grouped-query attention with the scores materialised. q: [B,T,Hq,D],
    k: [B,T,Hkv,D], v: [B,T,Hkv,Dv] (Dv = D but for latent attention).
    ``window``: query i sees key j iff 0 <= i - j < window."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    q = q.reshape(b, t, hkv, group, d)
    scores = jnp.einsum("bthgd,bshd->bhgts", q, k).astype(jnp.float32)
    scores = scores * (d ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((t, t), bool), -window)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v)
    return out.reshape(b, t, hq * v.shape[-1])


def attention(q, k, v, *, causal: bool = True, window=None):
    """Grouped-query attention, by the implementation the operands and the
    platform being lowered for allow: the fused blockwise kernels
    (``flash_attention``, no [T,T] array in HBM in either pass) for causal
    bf16 attention at shapes they support (``supported``) when lowered for
    TPU, ``dense_attention`` for everything else. One traced function
    serves every platform. ``attn_kernel_lowerings`` /
    ``attn_dense_lowerings`` count which one each lowered program holds.
    ``window``: sliding-window attention, the same mask in both forms (the
    band kernels)."""

    def dense(q, k, v):
        with jax.named_scope("attn.dense"):
            return dense_attention(
                count_lowering(q, "attn_dense_lowerings"), k, v,
                causal=causal, window=window)

    def kernel(q, k, v):
        return flash_attention(
            count_lowering(q, "attn_kernel_lowerings"), k, v, causal=causal,
            window=window)

    if not (causal and q.dtype == k.dtype == v.dtype == jnp.bfloat16
            and supported(q.shape, k.shape, q.dtype, v.shape, window)):
        return dense(q, k, v)
    return lax.platform_dependent(q, k, v, tpu=kernel, default=dense)


def gated(o: jax.Array, gate: jax.Array) -> jax.Array:
    """The plain form of the per-head output gate: o: [B, T, H·d] times the
    sigmoid of the float32 logits ``gate`` [B, T, H], head by head, in
    float32."""
    b, t, nh = gate.shape
    return (o.reshape(b, t, nh, -1).astype(jnp.float32)
            * jax.nn.sigmoid(gate)[..., None]).astype(o.dtype).reshape(
                b, t, -1)


def attention_head_major(q: jax.Array, k: jax.Array, v: jax.Array, window,
                         gate=None):
    """``attention`` (causal, with its counters) for q: [B, Hq, T, D] and k:
    [B, Hkv, T, D] head-major already (ops/qk_layout.py writes them so) and
    bf16 operands the fused kernels take: ``flash_attention_head_major``
    reads q and k as they are; the dense form takes them token-major again.
    ``gate``: the per-head output gate's float32 logits [B, T, Hq], or None:
    the kernels take it into their epilogue (``attn_gated_kernel_lowerings``
    counts it), the dense form applies it after as ``gated``."""
    def dense(q, k, v, *gate):
        with jax.named_scope("attn.dense"):
            o = dense_attention(
                count_lowering(q.transpose(0, 2, 1, 3),
                               "attn_dense_lowerings"),
                k.transpose(0, 2, 1, 3), v, window=window)
        return gated(o, *gate) if gate else o

    def kernel(q, k, v, *gate):
        gate = (count_lowering(g, "attn_gated_kernel_lowerings") for g in gate)
        return flash_attention_head_major(
            count_lowering(q, "attn_kernel_lowerings"), k, v, *gate,
            window=window)

    gate = () if gate is None else (gate,)
    return lax.platform_dependent(q, k, v, *gate, tpu=kernel, default=dense)
