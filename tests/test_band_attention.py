"""The band (sliding-window) attention kernels: numerics in interpret mode on
the CPU against the dense masked form, forward and gradients, with the band's
two cut tiles merged into one score tile and without; the tiles the kernels
visit and the pairs their softmax runs over, counted by their own arithmetic
against the tiles that intersect the band; that ``window=None`` is the causal
path it was; and that the kernels compile for a v5e at the cells' geometry
(no chip needed)."""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.ops.flash_attention import (band_softmax_pairs, band_tiles,
                                          choose_block, default_blocks,
                                          dense_attention, flash_attention,
                                          supported)

fa = importlib.import_module("brpc_tpu.ops.flash_attention")


def _inputs(seed, b=1, t=128, hq=4, hkv=2, d=32, dtype=jnp.float32):
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(kq, (b, t, hq, d), dtype),
            jax.random.normal(kk, (b, t, hkv, d), dtype),
            jax.random.normal(kv, (b, t, hkv, d), dtype),
            jax.random.normal(kw, (b, t, hq * d), jnp.float32))


def _value_and_grads(attn, q, k, v, w):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w),
        (0, 1, 2))(q, k, v)


def _agree(got, want, tol):
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert g.shape == r.shape
        assert np.max(np.abs(g - r)) <= tol * max(np.max(np.abs(r)), 1.0)


# (case, window, shapes, blocks): a window that is no multiple of a block, a
# window of 1 (every query sees itself alone), a window of T and of more (the
# causal mask), a window narrower than a tile (both edges cut one tile),
# query groups of 3 and of 4, two sequences a batch, uneven tiles, and tiles
# aligned to the window. Equal tiles that divide the window merge the lower
# edge's tile and the diagonal's into one step: windows of one, two and three
# tiles, a group of 3, two sequences, and a window of three tiles in four,
# where one query tile has a lower edge and three key tiles' edges lie past
# T (every merged case starts with W / B query tiles that have none).
_CASES = {
    "no_multiple_of_a_block": (40, dict(), (32, 32)),
    "window_of_one": (1, dict(), (32, 32)),
    "window_of_t": (128, dict(), (32, 32)),
    "window_past_t": (300, dict(), (32, 32)),
    "narrower_than_a_tile": (5, dict(t=64), (32, 32)),
    "group_of_3": (24, dict(hq=6, hkv=2), (32, 32)),
    "group_of_4_two_sequences": (48, dict(b=2, hq=8, hkv=2), (32, 32)),
    "uneven_tiles": (50, dict(t=256), (32, 64)),
    "wide_query_tile": (33, dict(t=256), (64, 32)),
    # tiles aligned to the window
    "aligned": (64, dict(t=256), (32, 32)),
    "aligned_to_a_tile": (32, dict(t=128), (32, 32)),
    "aligned_wide_query_tile": (64, dict(t=256), (64, 32)),
    # merged: "aligned_to_a_tile" (W = B) and "aligned" (W = 2B) above too
    "merged_three_tiles": (96, dict(t=256), (32, 32)),
    "merged_group_of_3": (32, dict(hq=6, hkv=2), (32, 32)),
    "merged_two_sequences": (64, dict(b=2, hq=8, hkv=2), (32, 32)),
    "merged_edges_past_t": (96, dict(t=128), (32, 32)),
}


# every case in float32; in bf16, the cell's dtype, the casts of five
_BF16 = ("no_multiple_of_a_block", "group_of_3", "aligned",
         "merged_edges_past_t", "merged_group_of_3")


@pytest.mark.parametrize("case,dtype,tol", [
    *((c, jnp.float32, 3e-5) for c in sorted(_CASES)),
    *((c, jnp.bfloat16, 3e-2) for c in _BF16)],
    ids=lambda v: v if isinstance(v, str) else getattr(v, "__name__", None))
def test_band_kernels_match_the_dense_masked_form(case, dtype, tol):
    window, shape, (bq, bk) = _CASES[case]
    q, k, v, w = _inputs(3, dtype=dtype, **shape)
    want = _value_and_grads(functools.partial(
        dense_attention, window=window), q, k, v, w)
    got = _value_and_grads(functools.partial(
        flash_attention, window=window, block_q=bq, block_k=bk,
        interpret=True), q, k, v, w)
    _agree(got, want, tol)
    if window >= q.shape[1]:        # ... which is then the causal form
        _agree(want, _value_and_grads(dense_attention, q, k, v, w),
               1e-6)


@pytest.mark.parametrize("window", [40, 64], ids=["cut_tiles", "merged"])
@pytest.mark.parametrize("group", [3, 4])
def test_band_backward_a_query_head_at_a_time(monkeypatch, group, window):
    """The room made so small that the group does not stay whole in VMEM:
    every query head its own program row, dK and dV summed afterwards; a
    window of no whole number of tiles and one of two."""
    q, k, v, w = _inputs(5, b=2, hq=2 * group, hkv=2)
    want = _value_and_grads(functools.partial(
        dense_attention, window=window), q, k, v, w)
    monkeypatch.setattr(fa, "VMEM_LIMIT",
                        2 * fa._resident(1, 128, 32, 32, jnp.float32))
    assert not fa.heads_together(group, 128, 32, 32, jnp.float32)
    got = _value_and_grads(functools.partial(
        flash_attention, window=window, block_q=32, block_k=32,
        interpret=True), q, k, v, w)
    _agree(got, want, 3e-5)


@pytest.mark.parametrize("window,t", [(128, 384), (256, 384), (40, 384)],
                         ids=["merged", "merged_two_tiles", "cut_tiles"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_gated_head_major_band_matches_the_plain_form(window, t, dtype, tol):
    """The entry Laguna's window layers call: q and k head-major, the gate's
    logits into the forward kernel's epilogue, the tiles its own (128 at
    384 positions, so a window of 128 or 256 merges the band's two cut
    tiles and one of 40 does not), against the dense masked form gated
    after it; the output and the cotangents of q, k, v and the logits."""
    q, k, v, w = _inputs(7, t=t, dtype=dtype)
    g = 3 * jax.random.normal(jax.random.PRNGKey(8), (1, t, 4))
    assert default_blocks(t, window)[0] == (128, 128)

    def kernel(q, k, v, g):
        return fa.flash_attention_head_major(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v, g,
            window=window, interpret=True)

    def plain(q, k, v, g):
        return fa.gated(dense_attention(q, k, v, window=window), g)

    got, want = (jax.value_and_grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w), (0, 1, 2, 3))(
            q, k, v, g) for f in (kernel, plain))
    _agree(got, want, tol)


def test_the_dense_mask_is_the_band():
    q, k, v, _ = _inputs(0, t=16, hq=1, hkv=1, d=8)
    v = jnp.eye(16)[None, :, None, :]               # probabilities, by key
    p = np.asarray(dense_attention(q, k, v, window=5))[0]
    i, j = np.indices((16, 16))
    assert ((p > 0) == ((i - j >= 0) & (i - j < 5))).all()
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-6)


def _cut(t, window, i0, i1, j0, j1):
    """(whether any pair of queries [i0, i1) x keys [j0, j1) is visible,
    whether all are)."""
    i, j = np.indices((t, t))
    seen = ((i - j >= 0) & (i - j < window))[i0:i1, j0:j1]
    return seen.any(), seen.all()


@pytest.mark.parametrize("t,window,bq,bk", [
    (256, 40, 32, 32), (256, 64, 32, 32), (256, 1, 32, 64), (256, 5, 64, 32),
    (256, 100, 64, 64), (128, 128, 32, 32), (128, 500, 32, 32),
    (512, 128, 128, 64), (512, 130, 64, 128)])
def test_the_kernels_visit_the_tiles_that_intersect_the_band(t, window, bq,
                                                             bk):
    """Forward, a query tile's loops; backward, a key tile's: [lo, hi) is
    exactly the tiles with a visible pair, [c0, c1) of them exactly those
    with no hidden pair."""
    def bounds(ranges):     # (lo, c0, c1, hi) of three loops end to end
        (lo, c0, low), (c0_, c1, clear), (c1_, hi, high) = ranges
        assert (int(c0), int(c1)) == (int(c0_), int(c1_))
        assert (low, clear, high) == (True, False, True)
        return int(lo), int(c0), int(c1), int(hi)

    for qi in range(t // bq):
        lo, c0, c1, hi = bounds(fa._band_key_tiles(qi, bq, bk, window))
        for kj in range(t // bk):
            some, whole = _cut(t, window, qi * bq, (qi + 1) * bq,
                               kj * bk, (kj + 1) * bk)
            assert (lo <= kj < hi) == some, (qi, kj)
            assert (c0 <= kj < c1) == whole, (qi, kj)
    for kj in range(t // bk):
        lo, c0, c1, hi = bounds(fa._band_query_tiles(kj, bq, bk, t, window))
        for qj in range(t // bq):
            some, whole = _cut(t, window, qj * bq, (qj + 1) * bq,
                               kj * bk, (kj + 1) * bk)
            assert (lo <= qj < hi) == some, (kj, qj)
            assert (c0 <= qj < c1) == whole, (kj, qj)
    i, j = np.indices((t, t))
    tiles = sum(_cut(t, window, a, a + bq, c, c + bk)[0]
                for a in range(0, t, bq) for c in range(0, t, bk))
    counted = band_tiles(t, window, ((bq, bk), (bq, bk)))
    assert counted == {
        "fwd_pairs": tiles * bq * bk, "bwd_pairs": tiles * bq * bk,
        "visible_pairs": int(((i - j >= 0) & (i - j < window)).sum())}
    # a merged step is one tile of softmax for two loaded: one for each
    # query tile W / B or more tiles in, where the tiles merge
    merged = max(t // bq - window // bq, 0) if (
        window % bk == 0 and bq == bk) else 0
    assert band_softmax_pairs(t, window, ((bq, bk), (bq, bk))) == {
        "fwd_softmax_pairs": (tiles - merged) * bq * bk,
        "bwd_softmax_pairs": (tiles - merged) * bq * bk}


def test_the_cells_band_by_hand():
    """8,192 tokens under a window of 512: 4,063,488 visible pairs a head,
    12.1% of the causal mask's; the tiles chosen visit twice that."""
    blocks = default_blocks(8192, 512)
    assert blocks == ((512, 512), (512, 512))
    assert choose_block(8192, True) == 1024 and choose_block(8192) == 512
    counted = band_tiles(8192, 512, blocks)
    assert counted["visible_pairs"] == 4_063_488 == \
        512 * 513 // 2 + (8192 - 512) * 512
    assert round(100 * 4_063_488 / (8192 * 8193 // 2), 1) == 12.1
    # a tile of 512 rows reaches from 511 keys before its first row to its
    # last: two tiles of 512, but for the first tile's one
    assert counted["fwd_pairs"] == counted["bwd_pairs"] == \
        (16 * 2 - 1) * 512 * 512
    assert band_tiles(8192, 512, ((256, 256),) * 2)["fwd_pairs"] == \
        (32 * 3 - 3) * 256 * 256


# The two cells' band at 8,192 tokens, tiles of 512 (Laguna's window of 512
# and Mellum2's of 1,024), each as (window, the tile pairs the kernels load a
# head, the pairs the band holds): the three keys of ``band_tiles`` as they
# have read since the band kernels came, and the tiles of softmax where the
# two cut tiles merge.
_CELLS = {"laguna": (512, 16 * 2 - 1, 512 * 513 // 2 + (8192 - 512) * 512),
          "mellum2": (1024, 16 * 3 - 3,
                      1024 * 1025 // 2 + (8192 - 1024) * 1024)}


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_the_cells_softmax_pairs_by_hand(cell):
    """Laguna: the first query tile runs its diagonal alone, the other 15
    their lower edge's tile and diagonal's as one: 16 tiles of softmax for
    31 loaded, forward and backward (the last key tile's edge lies past T).
    Mellum2: the first two query tiles have no lower edge (1 + 2 tiles),
    the other 14 a merged step and one clear tile: 31 for 45 loaded."""
    window, loaded, visible = _CELLS[cell]
    blocks = default_blocks(8192, window)
    assert blocks == ((512, 512), (512, 512))
    tile = 512 * 512
    assert band_tiles(8192, window, blocks) == {
        "fwd_pairs": loaded * tile, "bwd_pairs": loaded * tile,
        "visible_pairs": visible}
    softmax = {"laguna": 1 + 15, "mellum2": 1 + 2 + 14 * 2}[cell]
    assert band_softmax_pairs(8192, window, blocks) == {
        "fwd_softmax_pairs": softmax * tile,
        "bwd_softmax_pairs": softmax * tile}
    # a window of no whole number of tiles merges nothing
    unmerged = band_tiles(8192, window - 12, blocks)
    assert band_softmax_pairs(8192, window - 12, blocks) == {
        "fwd_softmax_pairs": unmerged["fwd_pairs"],
        "bwd_softmax_pairs": unmerged["bwd_pairs"]}


def _most_products_a_loop(call):
    """The most matrix products in one loop body of a Pallas call: a step's
    own (forward 2, backward 5), or a merged step's two tiles' (4, 10)."""
    return max(sum(e.primitive.name == "dot_general"
                   for e in fa._equations(sub))
               for e in fa._equations(call.params["jaxpr"])
               if e.primitive.name == "while"
               for sub in jax.core.jaxprs_in_params(e.params))


@pytest.mark.parametrize("window,bq,bk,merged", [
    (512, 512, 512, True), (1024, 512, 512, True), (256, 256, 256, True),
    (500, 512, 512, False), (512, 256, 512, False), (512, 512, 256, False),
    (256, 512, 512, False)])
def test_the_merged_step_is_traced_where_the_tiles_divide_the_window(
        window, bq, bk, merged):
    """The band's two cut tiles merge exactly where the window is a whole
    number of tiles and the tiles are equal, in both kernels; any other
    window or tiles keep a loop a kind of tile."""
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, window=window, block_q=bq, block_k=bk).astype(
                jnp.float32)), argnums=(0, 1, 2)))(
        s(1, 2048, 8, 128), s(1, 2048, 2, 128), s(1, 2048, 2, 128))
    fwd, bwd = _calls(jaxpr.jaxpr)
    assert (fwd.params["name"], bwd.params["name"]) == (
        "attn_band_fwd", "attn_band_bwd")
    assert (_most_products_a_loop(fwd), _most_products_a_loop(bwd)) == (
        (4, 10) if merged else (2, 5))


@pytest.mark.parametrize("t, hq, hkv, bq, bk", [
    (1024, 8, 2, 256, 128), (1024, 8, 2, 128, 256), (512, 4, 1, 512, 128),
    (512, 4, 4, 512, 512), (8192, 64, 8, None, None)])
def test_a_traced_program_shows_its_band_calls_tiles(t, hq, hkv, bq, bk):
    """``band_calls`` reads each band call's tiles from the call itself
    (the blocks of its operands, the rows its loops load), whoever chose
    them: explicit tiles, one tile for all queries (q's block then has K's
    shape), a group taken together or a head at a time, the default."""
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731

    def traced(**kw):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, **kw).astype(
                jnp.float32)), argnums=(0, 1, 2))).trace(
                    s(1, t, hq, 128), s(1, t, hkv, 128), s(1, t, hkv, 128))

    fwd, bwd = default_blocks(t, 100, bq, bk)
    assert fa.band_calls(traced(window=100, block_q=bq,
                                block_k=bk).jaxpr.jaxpr) == {
        ("attn_band_fwd", *fwd), ("attn_band_bwd", *bwd)}
    assert fa.band_calls(traced().jaxpr.jaxpr) == set()


def test_supported_states_the_rule_for_a_window():
    bf16 = jnp.bfloat16
    q, kv = (1, 8192, 64, 128), (1, 8192, 8, 128)
    assert supported(q, kv, bf16, window=512)
    assert supported(q, kv, bf16, window=500)       # no multiple of a tile
    assert supported(q, kv, bf16, window=1)
    assert supported(q, kv, bf16, window=10 ** 6)   # the causal mask
    assert not supported(q, kv, bf16, window=0)
    assert supported(q, kv, bf16) and supported(q, kv, bf16, window=None)
    with pytest.raises(ValueError, match="needs causal"):
        flash_attention(*_inputs(0)[:3], causal=False, window=8,
                        interpret=True)


def _calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _calls(sub)


# Mistral-7B's, kanana-2's, Ouro's and Qwen3-Next's attention as the held
# train cells run it: (T, query heads, KV heads, q/k width, v width).
_HELD = {"mistral7b": (2048, 32, 8, 128, 128),
         "kanana2": (8192, 32, 32, 192, 128),
         "ouro": (4096, 16, 16, 128, 128),
         "qwen3next": (8192, 16, 2, 256, 256)}


@pytest.mark.parametrize("cell", sorted(_HELD))
def test_no_window_is_the_causal_path_it_was(monkeypatch, cell):
    """At the four held geometries ``window=None`` traces the causal
    kernels under their names, with the tiles they had, and never reaches
    the band's arithmetic (the builder compared the two commits' jaxprs at
    these geometries, value and gradient, through ``flash_attention`` and
    through ``llama.attention``: 6,700 lines, identical; PERF.md section 6,
    PR 39). With a window the same operands trace the band kernels."""
    t, hq, hkv, d_qk, d_v = _HELD[cell]
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    args = (s(1, t, hq, d_qk), s(1, t, hkv, d_qk), s(1, t, hkv, d_v))

    def traced(**kw):
        return jax.make_jaxpr(jax.value_and_grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, **kw).astype(
                jnp.float32)), argnums=(0, 1, 2)))(*args)

    banded = traced(window=512)
    assert [e.params["name"] for e in _calls(banded.jaxpr)] == [
        "attn_band_fwd", "attn_band_bwd"]

    def never(*a, **k):
        raise AssertionError("the causal path reached the band's arithmetic")

    monkeypatch.setattr(fa, "_band_key_tiles", never)
    monkeypatch.setattr(fa, "_band_query_tiles", never)
    jax.clear_caches()
    fwd, bwd = _calls(traced().jaxpr)
    assert (fwd.params["name"], bwd.params["name"]) == (
        "attn_flash_fwd", "attn_flash_bwd")
    block = choose_block(t)
    assert fwd.params["grid_mapping"].grid == (1, hq, t // block)
    assert bwd.params["grid_mapping"].grid[2] == t // choose_block(t, True)
    text = re.sub(r" at 0x[0-9a-f]+", "", str(traced()))
    assert text == re.sub(r" at 0x[0-9a-f]+", "", str(traced(window=None)))


# -- compiled for a v5e, no chip needed ------------------------------------------

@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.mark.parametrize("window,hq,hkv", [
    (512, 64, 8), (500, 64, 8), (1024, 32, 4)])
def test_band_kernels_compile_for_v5e_at_the_cells_geometry(v5e_device,
                                                            window, hq, hkv):
    """1 x 8,192 tokens, bf16, KV heads of 128: Laguna's 64 query heads over
    8 under its window of 512 (the band's two cut tiles merged) and under
    one of 500 (not merged), Mellum2's 32 over 4 under its 1,024 (merged,
    with a clear tile between): one Mosaic call of each band kernel, the
    query group a head at a time, no array shaped like the scores."""
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.bfloat16, sharding=sharding)
    assert not fa.heads_together(hq // hkv, 8192, 128, 128, jnp.bfloat16)
    text = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, window=window)
                                .astype(jnp.float32)), argnums=(0, 1, 2))
    ).lower(s(1, 8192, hq, 128), s(1, 8192, hkv, 128),
            s(1, 8192, hkv, 128)).compile().as_text()
    found = re.findall(r"%(attn_\w+?)(?:\.\d+)? = [^\n]*custom-call\(", text)
    assert sorted(found) == ["attn_band_bwd", "attn_band_fwd"]
    assert re.findall(r"\w+\[[\d,]*8192,8192\]", text) == []
