"""models/windowed.py as Mellum2-12B-A2.5B (the second configuration of the
file Laguna-XS.2 runs through) against the benchmark's plain reference
(benchmark/reference_mellum.py, which imports nothing of the program), at a
small size on the CPU with seeded weights: hidden 64, one period of 3 window
layers and the full layer that ends it, no leading dense layer, 4 query
heads over 2 KV heads of 32 on every layer, a window of 16, q/k norms, yarn
on the whole head, 8 softmax-routed experts of which 2 are held, top-2, no
shared expert, no output gate, vocabulary 256.

Tolerances as tests/test_windowed.py's: float32 compute does the
reference's arithmetic in another order (1e-4 of each array's scale); bf16,
the dtype the cell runs, stays inside 60% of a leaf's scale and far outside
the float32 tolerance.
"""

import dataclasses
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

import lowered_step  # noqa: E402
import reference  # noqa: E402
import reference_mellum  # noqa: E402

from brpc_tpu.models import experts, windowed  # noqa: E402
from brpc_tpu.ops import grouped_matmul as gm  # noqa: E402
from brpc_tpu.ops import qk_layout as ql  # noqa: E402

fa = importlib.import_module("brpc_tpu.ops.flash_attention")

# JetBrains/Mellum2-12B-A2.5B-Instruct config.json's rope_parameters, as
# published (no partial_rotary_factor: the whole head turns)
ROPES = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
SIZES = {
    "hidden_size": 64, "vocab_size": 256, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "sliding_window": 16, "rope_parameters": ROPES,
    "layer_types": _PERIOD * 3, "mlp_layer_types": ["sparse"] * 12,
    "num_experts": 2, "router_experts": 8, "expert_offset": 0,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "rms_norm_eps": 1e-6,
}
ADAMW = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
         "weight_decay": 1e-4}
SEED = 7
PUBLISHED = windowed.WindowedConfig.mellum2()
TINY = windowed.WindowedConfig.tiny_mellum2()
TINY32 = dataclasses.replace(TINY, dtype=jnp.float32)
CELL = windowed.WindowedConfig.mellum2(n_layers=4, vocab_size=24576,
                                       n_held=16)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: reference_mellum.mellum_init(k, SIZES))(
        reference.seed_key(SEED))


@pytest.fixture(scope="module")
def tokens():
    return reference.token_batches(SEED, 4, 2, 128, SIZES["vocab_size"])


def _scale_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) /
                 jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def test_tree_is_the_references_with_no_gate_no_shared_and_two_norms(params):
    mine = windowed.init_params(jax.random.PRNGKey(0), TINY)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(mine),
                            jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype, \
            jax.tree_util.keystr(path)
    assert set(mine) == {"embed", "window", "full", "final_norm", "lm_head"}
    assert set(mine["window"]) == set(mine["full"]) == {
        "wq", "wk", "wv", "wo", "attn_norm", "mlp_norm", "q_norm", "k_norm",
        "router", "w_gate", "w_up", "w_down"}
    assert mine["window"]["wq"].shape == (1, 3, 64, 4 * 32)
    assert mine["full"]["wk"].shape == (1, 64, 2 * 32)
    assert mine["window"]["q_norm"].shape == (1, 3, 32)
    assert mine["full"]["router"].shape == (1, 64, 8)


def test_the_published_stack_is_laid_out_from_its_tables():
    """28 layers, window, window, window, full seven times, every MLP
    sparse: seven periods, no leading layer, no tail; and the counts."""
    assert PUBLISHED.layer_kinds == ("window", "window", "window",
                                     "full") * 7
    assert PUBLISHED.layout == (0, 3, 7, 0) and PUBLISHED.stacks == (7, 0)
    p = jax.eval_shape(lambda k: windowed.init_params(k, PUBLISHED),
                       jax.random.PRNGKey(0))
    assert "first" not in p and "tail" not in p
    assert p["window"]["wq"].shape == (7, 3, 2304, 32 * 128)
    assert p["full"]["wk"].shape == (7, 2304, 4 * 128)
    assert p["window"]["w_gate"].shape == (7, 3, 64, 2304, 896)
    assert sum(x.size for x in jax.tree_util.tree_leaves(p)) \
        == 12_149_923_072                    # "12B" as described
    # the cell: one period, 16 of 64 experts, a quarter of the vocabulary
    p = jax.eval_shape(lambda k: windowed.init_params(k, CELL),
                       jax.random.PRNGKey(0))
    layer = {k: v.size for k, v in p["full"].items()}      # the full layer
    experts = sum(layer[k] for k in ("w_gate", "w_up", "w_down"))
    assert experts == 16 * 6_193_152 and layer["router"] == 147_456
    assert sum(x.size for x in jax.tree_util.tree_leaves(p["full"])) \
        - experts - layer["router"] == 21_238_528
    assert sum(x.size for x in jax.tree_util.tree_leaves(p)) == 595_154_176


@pytest.mark.parametrize("layers,want", [
    (4, (0, 3, 1, 0)), (8, (0, 3, 2, 0)), (6, (0, 3, 1, 2))])
def test_layout_of_a_prefix_of_the_tables(layers, want):
    assert dataclasses.replace(TINY, n_layers=layers).layout == want


def test_laguna_s_tables_give_the_layout_it_had():
    laguna = windowed.WindowedConfig()
    assert laguna.layout == (1, 3, 9, 3)
    assert dataclasses.replace(laguna, n_layers=5).layout == (1, 3, 1, 0)


@pytest.mark.parametrize("changed", [
    {"n_layers": 8, "mlp_layer_types": ("dense", "dense") + ("sparse",) * 6},
    {"mlp_layer_types": ("sparse", "dense", "sparse", "sparse")},
    {"layer_types": ("window",) * 4},
    {"n_layers": 5, "layer_types": ("window", "full", "window", "window",
                                    "full")},
    {"n_layers": 30},
    {"mlp_layer_types": ("dense",) + ("sparse",) * 3},    # dense, not full
], ids=["two_dense", "dense_inside", "no_full", "uneven_periods",
        "tables_too_short", "dense_window_first"])
def test_a_table_the_file_cannot_lay_out_is_refused(changed):
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, **changed).layout


def test_yarn_turns_the_whole_head():
    """Rotary factor 1: all 128 lanes turn; c(r) = 128 ln(8192 / (2 pi r)) /
    (2 ln 500000): c(32) = 18.08 so low = 18, c(1) = 34.98 so high = 35:
    pairs up to 18 keep 500000^(-2i/128), from 35 on that over 16, between
    the ramp (i - 18) / 17."""
    f = windowed.yarn_inv_freq(PUBLISHED)
    assert f.shape == (64,) and f.dtype == np.float32
    base = 500000.0 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(f[:19], base[:19], rtol=1e-6)
    np.testing.assert_allclose(f[35:], base[35:] / 16, rtol=1e-6)
    ramp = (27 - 18) / 17
    np.testing.assert_allclose(f[27], base[27] * (ramp / 16 + 1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(reference_mellum.yarn_inv_freq(
        ROPES["full_attention"], 128)), f, rtol=2e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 128))
    out = np.asarray(windowed.yarn_rope(PUBLISHED, x, jnp.arange(8)[None]))
    x = np.asarray(x)
    assert out.shape == x.shape
    np.testing.assert_allclose(out[0, 0], x[0, 0] * 1.2772588722239782,
                               rtol=1e-6)           # position 0: the scale
    ang = 5 * f
    want = 1.2772588722239782 * np.concatenate(
        [x[0, 5, :, :64] * np.cos(ang) - x[0, 5, :, 64:] * np.sin(ang),
         x[0, 5, :, :64] * np.sin(ang) + x[0, 5, :, 64:] * np.cos(ang)], -1)
    np.testing.assert_allclose(out[0, 5], want, rtol=2e-5, atol=2e-6)


def test_the_ropes_the_mask_and_the_norms_are_on_the_right_layers(
        monkeypatch, params, tokens):
    """Three window layers (plain rope at 500,000, attention with the
    window), then the full layer (yarn, attention with none); q and k go
    through a per-head norm first, on every layer."""
    calls = []
    real, real_norm = windowed.attention, windowed.rms_norm
    monkeypatch.setattr(windowed, "rope", lambda x, p, theta: (
        calls.append(("rope", x.shape[2], theta)), x)[1])
    monkeypatch.setattr(windowed, "yarn_rope", lambda cfg, x, p: (
        calls.append(("yarn", x.shape[2])), x)[1])
    monkeypatch.setattr(windowed, "attention", lambda q, k, v, window: (
        calls.append(("attn", q.shape[2], window)),
        real(q, k, v, window=window))[1])
    monkeypatch.setattr(windowed, "rms_norm", lambda x, w, eps: (
        calls.append(("norm", x.shape[-1])) if x.ndim == 4 else None,
        real_norm(x, w, eps))[1])
    jax.eval_shape(lambda p, t: windowed.loss_fn(p, t, TINY32)[0], params,
                   tokens[0])
    # the plain form turns q's heads and k's as one array (PR 43)
    assert calls[:4] == [("norm", 32), ("norm", 32),
                         ("rope", 4 + 2, 500000.0), ("attn", 4, 16)]
    assert calls[4:] == [("norm", 32), ("norm", 32), ("yarn", 4 + 2),
                         ("attn", 4, None)]


@pytest.mark.parametrize("path", ["dense", "kernels_interpreted"])
@pytest.mark.parametrize("cfg,loss_tol,leaf_tol", [
    (TINY32, 2e-6, 1e-4), (TINY, 5e-3, 0.6)], ids=["float32", "bfloat16"])
def test_loss_logits_stats_and_every_gradient_leaf(
        monkeypatch, params, tokens, cfg, loss_tol, leaf_tol, path):
    """The system's loss, logits and every leaf's gradient against the
    reference's; the expert layer by its plain form and by its kernels
    through the Pallas interpreter."""
    if path == "kernels_interpreted":
        choose = gm.choose
        monkeypatch.setattr(
            gm, "choose", lambda kernel, plain, taken, counter, _, *operands:
            choose(kernel, plain, taken, counter, True, *operands))
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            lambda p, t: windowed.loss_fn(p, t, cfg), has_aux=True))(
                params, tokens[0])
        logits = jax.jit(lambda p, t: windowed.forward(p, t, cfg))(
            params, tokens[0])
    (want_loss, selected), want = jax.jit(jax.value_and_grad(
        lambda p, t: reference_mellum.mellum_loss(p, t, SIZES),
        has_aux=True))(params, tokens[0])
    want_logits, _ = jax.jit(lambda p, t: reference_mellum.mellum_logits(
        p, t, SIZES))(params, tokens[0])
    assert abs(float(loss) - float(want_loss)) <= loss_tol * float(want_loss)
    assert _scale_gap(logits, want_logits) <= (1e-4 if cfg is TINY32 else 0.2)
    gaps = {jax.tree_util.keystr(p): _scale_gap(g, w) for (p, g), w in
            zip(jax.tree_util.tree_leaves_with_path(grads),
                jax.tree_util.tree_leaves(want))}
    assert max(gaps.values()) <= leaf_tol, max(gaps, key=gaps.get)
    if cfg is TINY:     # bf16 is seen by the float32 tolerance
        assert max(gaps.values()) > 1e-4 * 10
    agree = np.mean(np.sort(np.asarray(stats["selected"]), -1)
                    == np.sort(np.asarray(selected), -1))
    assert agree == 1.0 if cfg is TINY32 else agree > 0.95
    assert stats["routed"].shape == (4,) and int(stats["dropped"].sum()) == 0
    if cfg is TINY32:
        held = np.asarray(selected) < SIZES["num_experts"]
        assert np.array_equal(np.asarray(stats["routed"]),
                              held.sum(axis=(1, 2)))


# Kernel-eligible and small enough for the Pallas interpreter: 2 query heads
# over 1 KV head of 128, one tile of 128 tokens under a window of 64, rows
# of 256 lanes.
KERNEL_SIZES = {**SIZES, "hidden_size": 256, "num_attention_heads": 2,
                "num_key_value_heads": 1, "head_dim": 128,
                "sliding_window": 64, "moe_intermediate_size": 128}
KERNEL = dataclasses.replace(TINY, hidden=256, full_heads=2, window_heads=2,
                             n_kv_heads=1, head_dim=128, window=64,
                             moe_intermediate=128)


def test_every_kernel_interpreted_follows_the_reference(monkeypatch):
    """bf16 through the q/k layout kernels (norm, rope and transpose in one
    pass), the band and causal attention kernels and the expert layer's six,
    all by the Pallas interpreter: loss and every leaf's gradient inside
    what bf16 allows, the policy's names saved."""
    choose = gm.choose
    monkeypatch.setattr(
        gm, "choose", lambda kernel, plain, taken, counter, _, *operands:
        choose(kernel, plain, taken, counter, True, *operands))
    windows, layouts = [], []
    # the branch a program lowered for TPU holds, its kernels interpreted
    monkeypatch.setattr(windowed.lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))
    real_attention = fa.flash_attention_head_major
    monkeypatch.setattr(
        fa, "flash_attention_head_major", lambda q, k, v, window: (
            windows.append(window), real_attention(
                q, k, v, window=window, interpret=True))[1])
    for name in ("forward", "backward"):
        real = getattr(ql, name)
        monkeypatch.setattr(ql, name, lambda *operands, real=real, name=name: (
            layouts.append(name), real(*operands, interpret=True))[1])
    p = jax.jit(lambda k: reference_mellum.mellum_init(k, KERNEL_SIZES))(
        reference.seed_key(SEED + 2))
    t = reference.token_batches(SEED, 1, 1, 128, 256)[0]
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda p, t: windowed.loss_fn(p, t, KERNEL), has_aux=True))(p, t)
    assert set(windows) == {64, None}
    # a kind of layer: the pass, the pass again for the VJP's residuals
    # (its results are saved by name, so no third in the recomputation),
    # and the backward kernel
    assert sorted(layouts) == ["backward"] * 2 + ["forward"] * 4
    (want_loss, _), want = jax.jit(jax.value_and_grad(
        lambda p, t: reference_mellum.mellum_loss(p, t, KERNEL_SIZES),
        has_aux=True))(p, t)
    assert abs(float(loss) - float(want_loss)) <= 5e-3 * float(want_loss)
    gaps = {jax.tree_util.keystr(path): _scale_gap(g, w) for (path, g), w in
            zip(jax.tree_util.tree_leaves_with_path(grads),
                jax.tree_util.tree_leaves(want))}
    assert max(gaps.values()) <= 0.6, max(gaps, key=gaps.get)
    assert int(stats["dropped"].sum()) == 0


def test_eight_and_six_layers_run_in_the_layers_order():
    """Two periods, and one period with two window layers left over
    (``tail``): a row of stats a layer, the loss the reference's."""
    t = reference.token_batches(SEED, 1, 1, 64, 256)[0]
    for layers in (8, 6):
        sizes = {**SIZES, "num_hidden_layers": layers}
        cfg = dataclasses.replace(TINY32, n_layers=layers)
        p = jax.jit(lambda k: reference_mellum.mellum_init(k, sizes))(
            reference.seed_key(SEED + 1))
        assert ("tail" in p) == (layers == 6)
        with jax.default_matmul_precision("highest"):
            loss, stats = jax.jit(
                lambda p, t: windowed.loss_fn(p, t, cfg))(p, t)
        want, selected = jax.jit(
            lambda p, t: reference_mellum.mellum_loss(p, t, sizes))(p, t)
        assert abs(float(loss) - float(want)) <= 2e-6 * float(want)
        assert stats["selected"].shape == selected.shape == (layers, 64, 2)
        assert np.array_equal(np.sort(np.asarray(stats["selected"]), -1),
                              np.sort(np.asarray(selected), -1))


def test_three_adamw_steps_follow_the_reference(params, tokens):
    want = reference_mellum.train_reference(SEED, SIZES, ADAMW, tokens, 3)
    optimizer = optax.adamw(ADAMW["learning_rate"], b1=ADAMW["b1"],
                            b2=ADAMW["b2"], eps=ADAMW["eps"],
                            weight_decay=ADAMW["weight_decay"])
    step = jax.jit(windowed.make_train_step(TINY32, optimizer))
    p, state, losses = params, optimizer.init(params), []
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            p, state, loss, stats = step(p, state, tokens[i])
            losses.append(float(loss))
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-5)
    delta = {k: float(v) for k, v in reference.leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, p, params)).items()}
    assert max(abs(delta[k] - v) / v
               for k, v in want["delta_norms"].items()) <= 2e-3


def test_each_planted_fault_moves_the_reference(params, tokens):
    """Every fault the benchmark plants changes the loss: none is a no-op
    at this size (``drop_eighth`` the least: the second of two choices on
    two of eight experts, 1.2e-5 of the loss; float32 noise is 1e-7)."""
    loss = lambda **kw: float(jax.jit(  # noqa: E731
        lambda p, t: reference_mellum.mellum_loss(p, t, SIZES, **kw)[0])(
            params, tokens[0]))
    sound = loss()
    for fault in reference_mellum.FAULTS:
        assert abs(loss(fault=fault) - sound) > 5e-6 * sound, fault


def test_the_block_has_no_gate_and_norms_q_and_k(params):
    """The block is the reference's, norms and all, not the one without
    them; and it holds no gate: a ``wg`` put into the layer changes
    nothing."""
    lp = {k: v[0] for k, v in params["full"].items()}
    lp["q_norm"] = lp["q_norm"] * jnp.linspace(0.5, 2.0, 32)
    lp["k_norm"] = lp["k_norm"] * jnp.linspace(2.0, 0.5, 32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 64))
    pos = jnp.arange(32)[None]
    with jax.default_matmul_precision("highest"):
        got = windowed.attention_block(TINY32, "full", x, lp, pos) - x
        gated = windowed.attention_block(
            TINY32, "full", x, {**lp, "wg": jnp.ones((64, 4))}, pos) - x
    want = reference_mellum.attention_block(x, lp, SIZES, "full") - x
    bare = reference_mellum.attention_block(x, lp, SIZES, "full",
                                            fault="no_qk_norm") - x
    assert _scale_gap(got, want) <= 1e-5
    assert _scale_gap(got, bare) > 0.1
    assert np.array_equal(np.asarray(got), np.asarray(gated))


# -- a chip's share of the expert layer ---------------------------------------

def _whole_layer(params):
    """The full layer's expert weights with all 8 experts: the 2 held
    repeated with other seeds for the 6 absent."""
    lp = {k: v[0] for k, v in params["full"].items()}
    key = jax.random.PRNGKey(11)
    for name in ("w_gate", "w_up", "w_down"):
        key, sub = jax.random.split(key)
        lp[name] = jax.random.normal(sub, (8,) + lp[name].shape[1:]) \
            * lp[name].shape[1] ** -0.5
    return lp


def test_the_shares_add_up_to_the_uncut_layer(params):
    """What the four shares give (offsets 0, 2, 4, 6: the tiny size's 0, 16,
    32, 48) adds up to the uncut reference's whole layer; nothing is
    computed alike on every chip, so nothing is counted once."""
    lp = _whole_layer(params)
    y = jax.random.normal(jax.random.PRNGKey(12), (96, 64))
    whole, _ = reference_mellum.moe_mlp(
        y, lp, {**SIZES, "num_experts": 8, "expert_offset": 0})
    total = jnp.zeros_like(whole)
    with jax.default_matmul_precision("highest"):
        for offset in (0, 2, 4, 6):
            cfg = dataclasses.replace(TINY32, n_held=2, expert_offset=offset)
            share = {**lp, **{k: lp[k][offset:offset + 2]
                              for k in ("w_gate", "w_up", "w_down")}}
            out, stats = windowed.moe_mlp(cfg, y, share)
            want, _ = reference_mellum.moe_mlp(
                y, share, {**SIZES, "expert_offset": offset})
            assert _scale_gap(out, want) <= 1e-5, offset
            assert int(stats["dropped"]) == 0
            total = total + out
    assert _scale_gap(total, whole) <= 1e-5


def test_weights_are_the_largest_softmax_shares_renormalised(params):
    """The softmax router is ``experts.softmax_route``, the one home of
    it."""
    lp = {k: v[0] for k, v in params["full"].items()}
    y = jax.random.normal(jax.random.PRNGKey(12), (96, 64))
    with jax.default_matmul_precision("highest"):
        selected, w = experts.softmax_route(TINY32, y, lp["router"])
        p = np.asarray(jax.nn.softmax(y @ lp["router"], axis=-1))
    top = np.argsort(-p, axis=1)[:, :2]
    assert np.array_equal(np.sort(np.asarray(selected), -1), np.sort(top, -1))
    np.testing.assert_allclose(np.asarray(w.sum(axis=1)), 1.0, rtol=1e-6)
    picked = np.take_along_axis(p, np.asarray(selected), axis=1)
    np.testing.assert_allclose(np.asarray(w), picked / picked.sum(
        axis=1, keepdims=True), rtol=1e-5)


def test_a_router_of_another_name_is_refused(params):
    lp = {k: v[0] for k, v in params["full"].items()}
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        windowed.moe_mlp(dataclasses.replace(TINY32, router="top1"),
                         jnp.ones((16, 64)), lp)


def test_the_model_calls_the_one_softmax_router(monkeypatch, params):
    calls = []
    real = experts.softmax_route
    assert windowed.softmax_route is real
    monkeypatch.setattr(windowed, "softmax_route", lambda cfg, y, r: (
        calls.append(cfg.experts_per_token), real(cfg, y, r))[1])
    lp = {k: v[0] for k, v in params["full"].items()}
    windowed.moe_mlp(TINY32, jnp.ones((16, 64)), lp)
    assert calls == [2]


# -- scopes, and what the cell's program holds when lowered for TPU -----------

def _abstract_step(cfg, batch, seq):
    optimizer = optax.adamw(1e-4)
    p = jax.eval_shape(lambda k: windowed.init_params(k, cfg),
                       jax.random.PRNGKey(0))
    return jax.jit(windowed.make_train_step(cfg, optimizer)).trace(
        p, jax.eval_shape(optimizer.init, p),
        jax.ShapeDtypeStruct((batch, seq), jnp.int32))


@pytest.fixture(scope="module")
def lowered():
    return lowered_step.lowered_for_tpu(lambda: _abstract_step(CELL, 1, 8192))


def test_the_cells_program_lowered_for_tpu_holds_every_kernel(lowered):
    """At the cell's shapes (4 layers, 1 x 8,192 tokens, 16 of 64 experts of
    2,304 x 896) the program lowered for TPU holds the band kernels at a
    window of 1,024, the causal kernels and the expert layer's six, counts a
    kernel lowering a kind of layer and no dense attention, no plain
    product and no plain row movement."""
    traced, text, counts = lowered
    fwd, bwd = fa.default_blocks(8192, 1024)
    assert fa.band_calls(traced.jaxpr.jaxpr) == {
        ("attn_band_fwd", *fwd), ("attn_band_bwd", *bwd)}
    # window and full layers have one shape of heads: one lowering serves both
    assert counts["attn_kernel_lowerings"] == 1
    assert counts["attn_gated_kernel_lowerings"] == 0     # no gate
    assert counts["attn_dense_lowerings"] == 0
    assert counts["moe_grouped_lowerings"] > 0
    assert counts["moe_rows_lowerings"] > 0
    found = set(re.findall(r"(attn_band_\w+|attn_flash_\w+|moe_gmm_\w+|"
                           r"moe_rows_\w+)", text))
    assert {"attn_band_fwd", "attn_band_bwd", "attn_flash_fwd",
            "attn_flash_bwd", "moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs",
            "moe_rows_gather", "moe_rows_combine", "moe_rows_pack"} <= found
    # the shapes no cell had run: widths that are no power of two
    tile = gm.choose_tile(8192 * 8, 16)
    assert tile == 256 and gm.kernels_take((16, 2304, 896), tile,
                                           jnp.bfloat16)
    assert gm.kernels_take((16, 896, 2304), tile, jnp.bfloat16)
    assert gm.rows_kernels_take(8192, 2304, tile, jnp.bfloat16)
    assert gm.bound_rows(8192 * 8, 16, tile) == 69632


def test_q_and_k_reach_the_attention_kernels_in_one_pass(lowered):
    """The program lowered for TPU holds the q/k layout kernel's two bodies,
    counted once (window and full layers have one shape of heads: one
    lowering serves both) and the plain form never; under ``*.rope``
    no float32 [1, 8192, H, 128] is left (the norms and ropes were five
    such passes a layer), and under ``attn.layout`` only v, the output and
    their cotangents are turned: v in the forward pass and again in the
    recomputation of each kind of layer, dv once, the output once and dO
    once, where q and k were turned beside v and dq, dk beside dv."""
    _, text, counts = lowered
    assert {"qk_layout_fwd", "qk_layout_bwd"} <= set(
        re.findall(r"qk_layout_\w+", text))
    assert counts["qk_layout_kernel_lowerings"] == 1
    assert counts["qk_layout_plain_lowerings"] == 0
    assert lowered_step.float32_heads_under(text, ".rope") == []
    assert lowered_step.layout_transposes(text) == {
        "1x8192x4x128xbf16": 4, "1x4x8192x128xbf16": 2,       # v, dv
        "1x32x8192x128xbf16": 4, "1x8192x32x128xbf16": 2}     # the output, dO


def test_the_steps_pallas_call_sites_are_pinned(lowered):
    """Every Pallas call site of the lowered step is traced and lowered by
    Mosaic in every run's set-up, warm or cold: 0.05–0.1 s a site (ISSUE
    43). The step held 42 before the q/k layout kernels and holds 45 with
    them: the forward kernel a kind of layer (its output is saved by name,
    so the recomputation runs none) and one backward kernel, which the two
    kinds, alike in every shape, share. A PR that adds sites sees here what
    set-up it is spending."""
    _, text, _ = lowered
    sites = lowered_step.pallas_sites(text)
    assert len(sites) - sum(s.startswith("qk_layout") for s in sites) == 42
    assert sorted(s for s in sites if s.startswith("qk_layout")) == [
        "qk_layout_bwd", "qk_layout_fwd", "qk_layout_fwd"]
    assert len(sites) == 45


def test_step_names_its_scopes_and_no_shared_expert():
    text = _abstract_step(TINY, 2, 64).lower(
        lowering_platforms=("cpu",)).as_text(debug_info=True)
    for scope in ("swa.qkv", "swa.qknorm", "swa.rope", "swa.attn", "swa.out",
                  "full.qkv", "full.qknorm", "full.rope", "full.attn",
                  "full.out", "moe.router", "moe.sort", "moe.experts",
                  "moe.combine", "windowed.glue"):
        assert scope in text, scope
    assert "moe.shared" not in text and "dense.mlp" not in text
