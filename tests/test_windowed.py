"""models/windowed.py against the benchmark's plain reference
(benchmark/reference_swa.py, which imports nothing of the program: a mask of
compared positions, dense attention a head at a time, every held expert on
every token), at a small size on the CPU with seeded weights: hidden 64, the
dense full layer and one period of 3 window + 1 full layer, 6 (window) and 4
(full) query heads over 2 KV heads of 32, a window of 16, 8 experts of which
2 are held, top-2, vocabulary 256.

Tolerances. With float32 as the compute dtype the program and the reference
do the same arithmetic in another order (the grouped product sums a token's
experts after the matmuls; the head in chunks): 1e-4 of each array's scale.
In bf16, the dtype the cell runs, a leaf's gradient stays inside 60% of its
scale (the widest are the two held experts' matrices, where a token whose
routing flips moves a whole row of few) and far outside the float32
tolerance; what bf16 costs at the cell's size is read on the chip (PERF.md
section 2).
"""

import dataclasses
import importlib
import math
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
import reference_swa  # noqa: E402

from brpc_tpu.models import deepseek, experts, llama, windowed  # noqa: E402
from brpc_tpu.ops import grouped_matmul as gm  # noqa: E402

# the module: ``brpc_tpu.ops.flash_attention`` the attribute is the function
fa = importlib.import_module("brpc_tpu.ops.flash_attention")

# poolside/Laguna-XS.2 config.json's rope_parameters, as published
ROPES = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
    "original_max_position_embeddings": 4096}
SIZES = {
    "hidden_size": 64, "vocab_size": 256, "num_hidden_layers": 5,
    "intermediate_size": 128, "num_key_value_heads": 2, "head_dim": 32,
    "sliding_window": 16, "rope_parameters": ROPES,
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 3,
    "mlp_layer_types": ["dense"] + ["sparse"] * 8,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6, 6, 6, 4],
    "num_experts": 2, "router_experts": 8, "expert_offset": 0,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "moe_routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6,
}
ADAMW = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
         "weight_decay": 1e-4}
SEED = 5
TINY = dataclasses.replace(windowed.WindowedConfig.tiny(),
                           yarn_original_positions=4096)
TINY32 = dataclasses.replace(TINY, dtype=jnp.float32)
PUBLISHED = windowed.WindowedConfig()


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: reference_swa.windowed_init(k, SIZES))(
        reference.seed_key(SEED))


@pytest.fixture(scope="module")
def tokens():
    return reference.token_batches(SEED, 4, 2, 128, SIZES["vocab_size"])


def _scale_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) /
                 jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def test_tree_is_the_references_and_heads_go_by_layer_kind(params):
    mine = windowed.init_params(jax.random.PRNGKey(0), TINY)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(mine),
                            jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype, \
            jax.tree_util.keystr(path)
    # 4 query heads on full layers (layer 0 among them), 6 on window layers
    assert mine["first"]["wq"].shape == (64, 4 * 32)
    assert mine["first"]["w_gate"].shape == (64, 128)       # the dense MLP
    assert mine["window"]["wq"].shape == (1, 3, 64, 6 * 32)
    assert mine["window"]["wg"].shape == (1, 3, 64, 6)
    assert mine["full"]["wo"].shape == (1, 4 * 32, 64)
    assert mine["full"]["wk"].shape == (1, 64, 2 * 32)
    assert "router" not in mine["first"] and "tail" not in mine


def test_the_published_stack_is_48_and_64_heads_and_ends_in_three_windows():
    """40 layers: layer 0 (full, dense), nine periods of window, window,
    window, full, and three window layers left over."""
    kinds = PUBLISHED.layer_kinds
    assert kinds[:5] == ("full", "window", "window", "window", "full")
    assert kinds.count("full") == 10 and kinds[-3:] == ("window",) * 3
    assert PUBLISHED.stacks == (9, 3)
    p = jax.eval_shape(lambda k: windowed.init_params(k, PUBLISHED),
                       jax.random.PRNGKey(0))
    assert p["first"]["wq"].shape == (2048, 48 * 128)
    assert p["window"]["wq"].shape == (9, 3, 2048, 64 * 128)
    assert p["full"]["wq"].shape == (9, 2048, 48 * 128)
    assert p["tail"]["wg"].shape == (3, 2048, 64)
    assert p["window"]["w_gate"].shape == (9, 3, 256, 2048, 512)
    assert sum(x.size for x in jax.tree_util.tree_leaves(p)) \
        == 33_442_596_864                    # "33.4B" as described
    cell = dataclasses.replace(PUBLISHED, n_layers=5, vocab_size=12544,
                               n_held=16)
    p = jax.eval_shape(lambda k: windowed.init_params(k, cell),
                       jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(p)) == 490_297_344


def test_yarn_frequencies_by_hand():
    """rot = 64 lanes, 32 frequencies. c(r) = 64 ln(4096 / (2 pi r)) / (2 ln
    500000): c(64) = 5.66 so low = 5, c(1) = 15.80 so high = 16. Pairs up to
    5 keep 500000^(-2i/64); pairs from 16 on are that over 64; between, the
    ramp (i - 5) / 11."""
    c = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(500000))
    assert round(c(64), 2) == 5.66 and round(c(1), 2) == 15.80
    f = windowed.yarn_inv_freq(PUBLISHED)
    assert f.shape == (32,) and f.dtype == np.float32
    base = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:6], base[:6], rtol=1e-6)
    np.testing.assert_allclose(f[16:], base[16:] / 64, rtol=1e-6)
    # three by hand: i = 3 (extrapolated), i = 10 (ramp 5/11), i = 20
    # (interpolated)
    np.testing.assert_allclose(f[3], 500000 ** (-6 / 64), rtol=1e-6)
    ramp = 5 / 11
    np.testing.assert_allclose(
        f[10], 500000 ** (-20 / 64) * (ramp / 64 + 1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(f[20], 500000 ** (-40 / 64) / 64, rtol=1e-6)
    assert round(PUBLISHED.yarn_attention_factor, 5) == 1.41589 == round(
        0.1 * math.log(64) + 1, 5)
    # and the reference's, computed on its own
    np.testing.assert_allclose(np.asarray(reference_swa.yarn_inv_freq(
        ROPES["full_attention"], 64)), f, rtol=2e-6)


def test_the_two_ropes():
    """The full layers' rope turns the leading half of a head by the yarn
    frequencies, scaled by the attention factor, and passes the rest; the
    window layers' is ``llama.rope`` at 10,000 over the whole head."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 128))
    pos = jnp.arange(8)[None]
    out = np.asarray(windowed.yarn_rope(PUBLISHED, x, pos))
    x = np.asarray(x)
    np.testing.assert_array_equal(out[..., 64:], x[..., 64:])
    np.testing.assert_allclose(out[0, 0, :, :64],
                               x[0, 0, :, :64] * 1.4158883083359672,
                               rtol=1e-6)           # position 0: the scale
    ang = 5 * windowed.yarn_inv_freq(PUBLISHED)
    want = 1.4158883083359672 * np.concatenate(
        [x[0, 5, :, :32] * np.cos(ang) - x[0, 5, :, 32:64] * np.sin(ang),
         x[0, 5, :, :32] * np.sin(ang) + x[0, 5, :, 32:64] * np.cos(ang)], -1)
    np.testing.assert_allclose(out[0, 5, :, :64], want, rtol=2e-5, atol=2e-6)


def test_the_ropes_and_the_mask_are_on_the_right_layers(monkeypatch, params,
                                                        tokens):
    """A window layer calls the plain rope and attention with the window; a
    full layer (layer 0 too) calls the yarn rope and attention with none.
    The plain form turns q's heads and k's as one array (PR 43)."""
    calls = []
    real = windowed.attention
    monkeypatch.setattr(windowed, "rope", lambda x, p, theta: (
        calls.append(("rope", x.shape[2], theta)), llama.rope(x, p, theta))[1])
    monkeypatch.setattr(windowed, "yarn_rope", lambda cfg, x, p: (
        calls.append(("yarn", x.shape[2])), x)[1])
    monkeypatch.setattr(windowed, "attention", lambda q, k, v, window: (
        calls.append(("attn", q.shape[2], window)),
        real(q, k, v, window=window))[1])
    jax.eval_shape(lambda p, t: windowed.loss_fn(p, t, TINY32)[0], params,
                   tokens[0])
    first, window, full = calls[:2], calls[2:4], calls[4:6]
    assert first == full == [("yarn", 4 + 2), ("attn", 4, None)]
    assert window == [("rope", 6 + 2, 10000.0), ("attn", 6, 16)]


@pytest.mark.parametrize("cfg,loss_tol,leaf_tol", [
    (TINY32, 2e-6, 1e-4), (TINY, 5e-3, 0.6)], ids=["float32", "bfloat16"])
def test_loss_stats_and_every_gradient_leaf(params, tokens, cfg, loss_tol,
                                            leaf_tol):
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            lambda p, t: windowed.loss_fn(p, t, cfg), has_aux=True))(
                params, tokens[0])
    (want_loss, selected), want = jax.jit(jax.value_and_grad(
        lambda p, t: reference_swa.windowed_loss(p, t, SIZES), has_aux=True))(
            params, tokens[0])
    assert abs(float(loss) - float(want_loss)) <= loss_tol * float(want_loss)
    gaps = {jax.tree_util.keystr(path): _scale_gap(g, w) for (path, g), w in
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


def test_nine_layers_run_in_the_layers_order():
    """Layer 0, two periods: the stats come back a row an expert layer, and
    the loss is the reference's, which walks the layers by the published
    lists; seven layers end in two window layers left over (``tail``)."""
    t = reference.token_batches(SEED, 1, 1, 64, 256)[0]
    for layers in (9, 7):
        sizes = {**SIZES, "num_hidden_layers": layers}
        cfg = dataclasses.replace(TINY32, n_layers=layers)
        p = jax.jit(lambda k: reference_swa.windowed_init(k, sizes))(
            reference.seed_key(SEED + 1))
        assert ("tail" in p) == (layers == 7)
        with jax.default_matmul_precision("highest"):
            loss, stats = jax.jit(
                lambda p, t: windowed.loss_fn(p, t, cfg))(p, t)
        want, selected = jax.jit(
            lambda p, t: reference_swa.windowed_loss(p, t, sizes))(p, t)
        assert abs(float(loss) - float(want)) <= 2e-6 * float(want)
        assert stats["selected"].shape == selected.shape == (layers - 1, 64,
                                                             2)
        assert np.array_equal(np.sort(np.asarray(stats["selected"]), -1),
                              np.sort(np.asarray(selected), -1))


def test_forward_is_the_loss_s_logits(params, tokens):
    logits = windowed.forward(params, tokens[0], TINY32)
    nll = (jax.nn.logsumexp(logits[:, :-1], axis=-1) - jnp.take_along_axis(
        logits[:, :-1], tokens[0][:, 1:, None], axis=-1)[..., 0])
    loss, _ = windowed.loss_fn(params, tokens[0], TINY32)
    assert abs(float(jnp.mean(nll)) - float(loss)) <= 1e-6 * float(loss)


def test_three_adamw_steps_follow_the_reference(params, tokens):
    want = reference_swa.train_reference(SEED, SIZES, ADAMW, tokens, 3)
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
    """Every fault the benchmark plants changes the loss: none is a no-op at
    this size (``window_256`` halves the window)."""
    loss = lambda **kw: float(jax.jit(  # noqa: E731
        lambda p, t: reference_swa.windowed_loss(p, t, SIZES, **kw)[0])(
            params, tokens[0]))
    sound = loss()
    for fault in reference_swa.FAULTS:
        assert abs(loss(fault=fault) - sound) > 1e-4 * sound, fault


def test_the_gate_is_a_sigmoid_a_head(params):
    """With W_g = 0 every head's output is halved; with the seeded W_g the
    block is the reference's, gate and all, and not the ungated one."""
    lp = dict(params["first"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 64))
    pos = jnp.arange(32)[None]
    block = lambda lp: windowed.attention_block(  # noqa: E731
        TINY32, "full", x, lp, pos) - x
    ungated = reference_swa.attention_block(x, lp, SIZES, "full",
                                            fault="no_attn_gate") - x
    with jax.default_matmul_precision("highest"):
        assert _scale_gap(block({**lp, "wg": jnp.zeros_like(lp["wg"])}),
                          0.5 * ungated) <= 1e-5
        gated = block(lp)
    assert _scale_gap(gated, reference_swa.attention_block(
        x, lp, SIZES, "full") - x) <= 1e-5
    assert _scale_gap(gated, ungated) > 0.1


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
    """The routed parts that the four shares give (offsets 0, 2, 4, 6) plus
    the shared expert counted once are the uncut reference's whole layer."""
    lp = _whole_layer(params)
    y = jax.random.normal(jax.random.PRNGKey(12), (96, 64))
    whole, _ = reference_swa.moe_mlp(
        y, lp, {**SIZES, "num_experts": 8, "expert_offset": 0})
    shared = experts.swiglu(y, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    total = shared
    with jax.default_matmul_precision("highest"):
        for offset in (0, 2, 4, 6):
            cfg = dataclasses.replace(TINY32, n_held=2, expert_offset=offset)
            share = {**lp, **{k: lp[k][offset:offset + 2]
                              for k in ("w_gate", "w_up", "w_down")}}
            out, stats = windowed.moe_mlp(cfg, y, share)
            want, _ = reference_swa.moe_mlp(
                y, share, {**SIZES, "expert_offset": offset})
            assert _scale_gap(out, want) <= 1e-5, offset
            assert int(stats["dropped"]) == 0
            total = total + (out - shared)
    assert _scale_gap(total, whole) <= 1e-5


def test_weights_are_the_largest_sigmoids_renormalised_and_scaled(params):
    lp = {k: v[0] for k, v in params["full"].items()}
    y = jax.random.normal(jax.random.PRNGKey(12), (96, 64))
    with jax.default_matmul_precision("highest"):
        selected, w = deepseek.route(TINY32, y, lp["router"], 0.0)
        s = np.asarray(jax.nn.sigmoid(y @ lp["router"]))
    top = np.argsort(-s, axis=1)[:, :2]
    assert np.array_equal(np.sort(np.asarray(selected), -1), np.sort(top, -1))
    np.testing.assert_allclose(np.asarray(w.sum(axis=1)), 2.5, rtol=1e-6)
    picked = np.take_along_axis(s, np.asarray(selected), axis=1)
    np.testing.assert_allclose(np.asarray(w), 2.5 * picked / picked.sum(
        axis=1, keepdims=True), rtol=1e-5)


# -- scopes, and what the cell's program holds when lowered for TPU -----------

CELL = dataclasses.replace(PUBLISHED, n_layers=5, vocab_size=12544, n_held=16)


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
    """At the cell's shapes (5 layers, 1 x 8,192 tokens, 16 of 256 experts)
    the program lowered for TPU holds the band kernels (window layers), the
    causal kernels (full layers) and the expert layer's, counts a kernel
    lowering for each kind of layer and no dense attention."""
    traced, text, counts = lowered
    # the band calls' tiles as the traced step shows them: what the
    # benchmark's driver counts the visited pairs from
    fwd, bwd = fa.default_blocks(8192, 512)
    assert fa.band_calls(traced.jaxpr.jaxpr) == {
        ("attn_band_fwd", *fwd), ("attn_band_bwd", *bwd)}
    # one a kind of attention: layer 0 and the full layers share theirs
    assert counts["attn_kernel_lowerings"] == 2
    assert counts["attn_dense_lowerings"] == 0
    found = set(re.findall(r"(attn_band_\w+|attn_flash_\w+|moe_gmm_\w+|"
                           r"moe_rows_\w+)", text))
    assert {"attn_band_fwd", "attn_band_bwd", "attn_flash_fwd",
            "attn_flash_bwd", "moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs",
            "moe_rows_gather", "moe_rows_combine", "moe_rows_pack"} <= found
    assert gm.bound_rows(8192 * 8, 16, gm.choose_tile(8192 * 8, 16)) >= 65536


def test_q_and_k_reach_the_attention_kernels_in_one_pass(lowered):
    """The program lowered for TPU holds the q/k layout kernel's two bodies,
    counted once a kind of layer (64 heads, and 48: layer 0 and the full
    layers share theirs) and the plain form never; under ``*.rope`` no
    float32 [1, 8192, H, 128] is left, and under ``attn.layout`` only v, the
    output and their cotangents are turned: v (8 heads) in the forward pass
    and again in the recomputation of the window layers, the full layers and
    layer 0, dv once each, the output and dO once each."""
    _, text, counts = lowered
    assert {"qk_layout_fwd", "qk_layout_bwd"} <= set(
        re.findall(r"qk_layout_\w+", text))
    assert counts["qk_layout_kernel_lowerings"] == 2
    assert counts["qk_layout_plain_lowerings"] == 0
    assert lowered_step.float32_heads_under_rope(text) == []
    assert lowered_step.layout_transposes(text) == {
        "1x8192x8x128xbf16": 6, "1x8x8192x128xbf16": 3,       # v, dv
        "1x64x8192x128xbf16": 2, "1x8192x64x128xbf16": 1,     # window: o, dO
        "1x48x8192x128xbf16": 4, "1x8192x48x128xbf16": 2}     # full, layer 0


def test_the_steps_pallas_call_sites_are_pinned(lowered):
    """The step held 44 Pallas call sites before the q/k layout kernels and
    holds 49 with them: a forward kernel for the window layers, the full
    layers and layer 0 (their outputs are saved by name, so no recomputation
    runs one) and a backward kernel for the window layers and for the full
    ones, which layer 0 shares. Each is traced and lowered by Mosaic in
    every run's set-up (tests/test_mellum.py has the price of a site)."""
    _, text, _ = lowered
    sites = lowered_step.pallas_sites(text)
    assert len(sites) - sum(s.startswith("qk_layout") for s in sites) == 44
    assert sorted(s for s in sites if s.startswith("qk_layout")) == [
        "qk_layout_bwd"] * 2 + ["qk_layout_fwd"] * 3
    assert len(sites) == 49


def test_the_dense_form_states_no_band_call():
    """Where attention runs in its dense form (heads of 32 here) the traced
    step holds no band call: the driver then reports no visited pairs."""
    assert fa.band_calls(_abstract_step(TINY, 2, 64).jaxpr.jaxpr) == set()


def test_step_names_its_scopes():
    text = _abstract_step(TINY, 2, 64).lower(
        lowering_platforms=("cpu",)).as_text(debug_info=True)
    for scope in ("swa.qkv", "swa.rope", "swa.attn", "swa.out", "full.qkv",
                  "full.rope", "full.attn", "full.out", "dense.mlp",
                  "moe.router", "moe.sort", "moe.experts", "moe.combine",
                  "moe.shared", "windowed.glue"):
        assert scope in text, scope
