"""The band (sliding-window) attention kernels: numerics in interpret mode on
the CPU against the dense masked form, forward and gradients; the tiles the
kernels visit, counted by their own arithmetic against the tiles that
intersect the band; that ``window=None`` is the causal path it was; and that
the kernels compile for a v5e at the cell's geometry (no chip needed)."""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import llama
from brpc_tpu.ops.flash_attention import (band_tiles, choose_block,
                                          default_blocks, flash_attention,
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
# aligned to the window.
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
}


# every case in float32; in bf16, the cell's dtype, the casts of three
_BF16 = ("no_multiple_of_a_block", "group_of_3", "aligned")


@pytest.mark.parametrize("case,dtype,tol", [
    *((c, jnp.float32, 3e-5) for c in sorted(_CASES)),
    *((c, jnp.bfloat16, 3e-2) for c in _BF16)],
    ids=lambda v: v if isinstance(v, str) else getattr(v, "__name__", None))
def test_band_kernels_match_the_dense_masked_form(case, dtype, tol):
    window, shape, (bq, bk) = _CASES[case]
    q, k, v, w = _inputs(3, dtype=dtype, **shape)
    want = _value_and_grads(functools.partial(
        llama.dense_attention, window=window), q, k, v, w)
    got = _value_and_grads(functools.partial(
        flash_attention, window=window, block_q=bq, block_k=bk,
        interpret=True), q, k, v, w)
    _agree(got, want, tol)
    if window >= q.shape[1]:        # ... which is then the causal form
        _agree(want, _value_and_grads(llama.dense_attention, q, k, v, w),
               1e-6)


@pytest.mark.parametrize("group", [3, 4])
def test_band_backward_a_query_head_at_a_time(monkeypatch, group):
    """The room made so small that the group does not stay whole in VMEM:
    every query head its own program row, dK and dV summed afterwards."""
    q, k, v, w = _inputs(5, b=2, hq=2 * group, hkv=2)
    want = _value_and_grads(functools.partial(
        llama.dense_attention, window=40), q, k, v, w)
    monkeypatch.setattr(fa, "_VMEM_LIMIT",
                        2 * fa._resident(1, 128, 32, 32, jnp.float32))
    assert not fa.heads_together(group, 128, 32, 32, jnp.float32)
    got = _value_and_grads(functools.partial(
        flash_attention, window=40, block_q=32, block_k=32, interpret=True),
        q, k, v, w)
    _agree(got, want, 3e-5)


def test_the_dense_mask_is_the_band():
    q, k, v, _ = _inputs(0, t=16, hq=1, hkv=1, d=8)
    v = jnp.eye(16)[None, :, None, :]               # probabilities, by key
    p = np.asarray(llama.dense_attention(q, k, v, window=5))[0]
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


@pytest.mark.parametrize("window", [512, 500])
def test_band_kernels_compile_for_v5e_at_the_cells_geometry(v5e_device,
                                                            window):
    """1 x 8,192 tokens, 64 query heads over 8 KV heads of 128, bf16: one
    Mosaic call of each band kernel, the query group a head at a time, no
    array shaped like the scores."""
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.bfloat16, sharding=sharding)
    assert not fa.heads_together(8, 8192, 128, 128, jnp.bfloat16)
    text = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, window=window)
                                .astype(jnp.float32)), argnums=(0, 1, 2))
    ).lower(s(1, 8192, 64, 128), s(1, 8192, 8, 128),
            s(1, 8192, 8, 128)).compile().as_text()
    found = re.findall(r"%(attn_\w+?)(?:\.\d+)? = [^\n]*custom-call\(", text)
    assert sorted(found) == ["attn_band_bwd", "attn_band_fwd"]
    assert re.findall(r"\w+\[[\d,]*8192,8192\]", text) == []
