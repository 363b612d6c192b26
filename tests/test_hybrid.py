"""models/hybrid.py against the benchmark's plain reference
(benchmark/reference_gdn.py, which imports nothing of the program and runs
the gated delta rule as the per-token recurrence), at a small size on the CPU
with seeded weights: hidden 64, one period of 3 linear + 1 full layer, 2 key
and 4 value heads of 16, 4 query heads over 2 KV heads of 32, 8 experts of
which 2 are held, top-2, vocabulary 256.

Tolerances. With float32 as the compute dtype the program and the reference
do the same arithmetic in another order (the rule in chunks of 64 with an
inverse where the reference walks the tokens; the grouped product sums a
token's experts after the matmuls; the head in chunks): 1e-4 of each array's
scale (the widest leaf, ``dt_bias``, reads 6e-5). In bf16, the dtype the cell
runs, the linear layers are noisy at this size whatever computes the rule:
with a head's decay strong a token's output is about (q.k) beta v, a
unit-vector product over 16 dimensions, and the gated norm after it rescales
that; the exact recurrence on the same bf16 inputs reads the same 8% on ONE
layer's input gradient, and three such layers with their routers' flips
compound to most of a leaf's scale. So the bf16 case runs at a weak decay
(every head's rate 0.05), where a leaf's gradient stays inside 40% of its
scale and far outside the float32 tolerance; what bf16 costs at the cell's
size is read on the chip (PERF.md section 2).
"""

import dataclasses
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

import reference  # noqa: E402
import reference_gdn  # noqa: E402

from brpc_tpu import obs  # noqa: E402
from brpc_tpu.models import deepseek, experts, hybrid  # noqa: E402
from brpc_tpu.ops import causal_conv, gated_delta  # noqa: E402
from brpc_tpu.ops import grouped_matmul as gm  # noqa: E402

SIZES = {
    "hidden_size": 64, "vocab_size": 256, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "rope_theta": 1e7, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts": 2, "router_experts": 8,
    "expert_offset": 0, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "rms_norm_eps": 1e-6,
}
ADAMW = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
         "weight_decay": 1e-4}
SEED = 5
TINY = hybrid.HybridConfig.tiny()
TINY32 = dataclasses.replace(TINY, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: reference_gdn.hybrid_init(k, SIZES))(
        reference.seed_key(SEED))


@pytest.fixture(scope="module")
def tokens():
    return reference.token_batches(SEED, 4, 2, 128, SIZES["vocab_size"])


def _scale_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) /
                 jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def test_tree_is_the_references(params):
    mine = hybrid.init_params(jax.random.PRNGKey(0), TINY)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(mine),
                            jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype, \
            jax.tree_util.keystr(path)
    assert mine["linear"]["w_qkvz"].shape == (1, 3, 64, 2 * 32 + 2 * 64)
    assert mine["full"]["wq"].shape == (1, 64, 2 * 4 * 32)


@pytest.mark.parametrize("interval,layers,kinds", [
    (4, 8, "lllflllf"), (2, 4, "lflf"), (4, 4, "lllf")])
def test_layer_pattern_follows_the_interval(interval, layers, kinds):
    """Layer i is full attention where (i + 1) % interval == 0; the stacks
    hold the kinds apart, a period a row."""
    cfg = dataclasses.replace(TINY, n_layers=layers,
                              full_attention_interval=interval)
    assert "".join(k[0] for k in cfg.layer_kinds) == kinds
    p = jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                       jax.random.PRNGKey(0))
    assert p["linear"]["w_out"].shape[:2] == (layers // interval,
                                              interval - 1)
    assert p["full"]["wo"].shape[0] == layers // interval
    with pytest.raises(ValueError, match="whole periods"):
        hybrid.init_params(jax.random.PRNGKey(0),
                           dataclasses.replace(cfg, n_layers=layers + 1))


def test_two_periods_run_in_the_layers_order():
    """Eight layers: the stats come back a row a layer, and the loss is the
    reference's, which walks the layers by the published rule."""
    sizes = {**SIZES, "num_hidden_layers": 8}
    cfg = dataclasses.replace(TINY32, n_layers=8)
    p = jax.jit(lambda k: reference_gdn.hybrid_init(k, sizes))(
        reference.seed_key(SEED + 1))
    t = reference.token_batches(SEED, 1, 1, 64, 256)[0]
    with jax.default_matmul_precision("highest"):
        loss, stats = jax.jit(lambda p, t: hybrid.loss_fn(p, t, cfg))(p, t)
    want, selected = jax.jit(
        lambda p, t: reference_gdn.hybrid_loss(p, t, sizes))(p, t)
    assert abs(float(loss) - float(want)) <= 2e-6 * float(want)
    assert stats["selected"].shape == selected.shape == (8, 64, 2)
    assert np.array_equal(np.sort(np.asarray(stats["selected"]), -1),
                          np.sort(np.asarray(selected), -1))


@pytest.mark.parametrize("cfg,loss_tol,leaf_tol", [
    (TINY32, 2e-6, 1e-4), (TINY, 5e-3, 0.4)], ids=["float32", "bfloat16"])
def test_loss_stats_and_every_gradient_leaf(params, tokens, cfg, loss_tol,
                                            leaf_tol):
    if cfg is TINY:     # the bf16 case at a weak decay: the docstring says why
        params = {**params, "linear": {
            **params["linear"],
            "a_log": jnp.full_like(params["linear"]["a_log"], np.log(0.05))}}
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            lambda p, t: hybrid.loss_fn(p, t, cfg), has_aux=True))(
                params, tokens[0])
    (want_loss, selected), want = jax.jit(jax.value_and_grad(
        lambda p, t: reference_gdn.hybrid_loss(p, t, SIZES), has_aux=True))(
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
    held = np.asarray(selected) < SIZES["num_experts"]
    if cfg is TINY32:
        assert np.array_equal(np.asarray(stats["routed"]),
                              held.sum(axis=(1, 2)))
        assert np.all(np.asarray(stats["rows_in_use"])
                      >= np.asarray(stats["routed"]))


def test_forward_is_the_loss_s_logits(params, tokens):
    logits = hybrid.forward(params, tokens[0], TINY32)
    nll = (jax.nn.logsumexp(logits[:, :-1], axis=-1) - jnp.take_along_axis(
        logits[:, :-1], tokens[0][:, 1:, None], axis=-1)[..., 0])
    loss, _ = hybrid.loss_fn(params, tokens[0], TINY32)
    assert abs(float(jnp.mean(nll)) - float(loss)) <= 1e-6 * float(loss)


def test_three_adamw_steps_follow_the_reference(params, tokens):
    want = reference_gdn.train_reference(SEED, SIZES, ADAMW, tokens, 3)
    optimizer = optax.adamw(ADAMW["learning_rate"], b1=ADAMW["b1"],
                            b2=ADAMW["b2"], eps=ADAMW["eps"],
                            weight_decay=ADAMW["weight_decay"])
    step = jax.jit(hybrid.make_train_step(TINY32, optimizer))
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


def _loss_gradients(params, tokens):
    return jax.jit(jax.grad(
        lambda p, t: hybrid.loss_fn(p, t, TINY32)[0]))(params, tokens[0])


@pytest.fixture(scope="module")
def gradients_as_it_is(params, tokens):
    return _loss_gradients(params, tokens)


@pytest.mark.parametrize("keeps", ["input", "no_inverse", "rule_too", "all"])
def test_gradients_do_not_depend_on_what_the_recomputation_keeps(
        keeps, monkeypatch, params, tokens, gradients_as_it_is):
    """The model as it is against a bare checkpoint (no name saved), the
    rule's T not saved (``gdn_chunk_prep`` runs again in the recomputation),
    the rule's output and states saved too, and no checkpoint at all: the
    same gradients."""
    inverse, rule = gated_delta.INVERSE_NAME, gated_delta.RESIDUAL_NAMES
    assert inverse in hybrid.SAVED_NAMES
    assert not set(rule) & set(hybrid.SAVED_NAMES)
    if keeps == "all":
        monkeypatch.setattr(jax, "checkpoint", lambda fun, **_: fun)
    else:
        monkeypatch.setattr(hybrid, "SAVED_NAMES", {
            "input": (),
            "no_inverse": tuple(n for n in hybrid.SAVED_NAMES
                                if n != inverse),
            "rule_too": (*hybrid.SAVED_NAMES, *rule)}[keeps])
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(
                _loss_gradients(params, tokens)),
            jax.tree_util.tree_leaves(gradients_as_it_is)):
        assert _scale_gap(g, w) <= 1e-4, jax.tree_util.keystr(path)


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


def _tokens_in():
    return jax.random.normal(jax.random.PRNGKey(12), (96, 64))


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts that the four shares give (offsets 0, 2, 4, 6) plus
    the gated shared expert counted once are the uncut reference's whole
    layer."""
    lp, y = _whole_layer(params), _tokens_in()
    whole, _ = reference_gdn.moe_mlp(
        y, lp, {**SIZES, "num_experts": 8, "expert_offset": 0})
    shared = jax.nn.sigmoid(y @ lp["shared_w"])[:, None] * experts.swiglu(
        y, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    total = shared
    with jax.default_matmul_precision("highest"):
        for offset in (0, 2, 4, 6):
            cfg = dataclasses.replace(TINY32, n_held=2, expert_offset=offset)
            share = {**lp, **{k: lp[k][offset:offset + 2]
                              for k in ("w_gate", "w_up", "w_down")}}
            out, stats = hybrid.moe_mlp(cfg, y, share)
            want, _ = reference_gdn.moe_mlp(
                y, share, {**SIZES, "expert_offset": offset})
            assert _scale_gap(out, want) <= 1e-5, offset
            assert int(stats["dropped"]) == 0
            total = total + (out - shared)
    assert _scale_gap(total, whole) <= 1e-5


def test_weights_are_a_softmax_s_largest_renormalised(params):
    lp, y = {k: v[0] for k, v in params["full"].items()}, _tokens_in()
    with jax.default_matmul_precision("highest"):
        selected, w = experts.softmax_route(TINY32, y, lp["router"])
    p = jax.nn.softmax(y @ lp["router"], axis=-1)
    top = np.argsort(-np.asarray(p), axis=1)[:, :2]
    assert np.array_equal(np.sort(np.asarray(selected), -1), np.sort(top, -1))
    np.testing.assert_allclose(np.asarray(w.sum(axis=1)), 1.0, rtol=1e-6)
    picked = np.take_along_axis(np.asarray(p), np.asarray(selected), axis=1)
    np.testing.assert_allclose(np.asarray(w), picked / picked.sum(
        axis=1, keepdims=True), rtol=1e-5)


def test_convolution_is_causal_and_ends_on_the_current_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 3))
    taps = jnp.array([[0.0] * 3, [0.0] * 3, [0.5] * 3, [2.0] * 3])
    y = causal_conv.causal_conv(x, taps)
    shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    np.testing.assert_allclose(np.asarray(y), np.asarray(2 * x + 0.5 * shifted),
                               rtol=1e-6)


# -- the routed half is one function for both models --------------------------

def test_deepseek_loss_traces_to_the_program_it_was():
    """``deepseek.moe_mlp`` through ``experts.expert_mlp`` against the body
    it held before PR 34, written out here: the same jaxpr, equation for
    equation, value and gradient (at the kanana cell's geometry the builder
    compared the two commits' texts, PERF.md section 6)."""
    cfg = deepseek.DeepseekConfig.tiny()

    def before(cfg, y, lp):
        n, k = y.shape[0], cfg.experts_per_token
        selected, weights = experts.sigmoid_route(cfg, y, lp["router"],
                                                  lp["router_bias"])
        local = selected - cfg.expert_offset
        group_of = jnp.where((local >= 0) & (local < cfg.n_held), local,
                             cfg.n_held).reshape(n * k)
        tile = gm.choose_tile(n * k, cfg.n_held)
        lay = gm.group_layout(group_of, cfg.n_held, tile)
        to_gate, to_up = gm.dispatch(y, lay, copies=2)
        product = lambda a, w: gm.grouped_matmul(  # noqa: E731
            a, w, lay.tile_group, lay.n_tiles)
        hidden = jax.nn.silu(product(to_gate, lp["w_gate"])) * product(
            to_up, lp["w_up"])
        rows = product(hidden, lp["w_down"])
        routed = gm.combine(rows, weights, lay)
        shared = (jax.nn.silu(y @ lp["shared_gate"]) * (y @ lp["shared_up"])
                  ) @ lp["shared_down"]
        n_routed = jnp.sum(lay.held.astype(jnp.int32))
        stats = {
            "routed": n_routed,
            "dropped": n_routed - jnp.sum(lay.row_valid.astype(jnp.int32)),
            "group_max": jnp.max(lay.group_sizes),
            "group_mean": jnp.mean(lay.group_sizes.astype(jnp.float32)),
            "rows_in_use": lay.n_tiles[0] * tile,
            "selected": selected,
        }
        return routed + shared, stats

    params = jax.eval_shape(lambda k: deepseek.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)

    def text():
        return re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(
            jax.value_and_grad(lambda p, t: deepseek.loss_fn(p, t, cfg),
                               has_aux=True))(params, tokens)))

    now = text()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(deepseek, "moe_mlp", before)
        assert text() == now


# -- scopes, and what the cell's program holds when lowered for TPU -----------

CELL = dataclasses.replace(hybrid.HybridConfig(), n_layers=4,
                           vocab_size=18992, n_held=32)


def _abstract_step(cfg, batch, seq):
    optimizer = optax.adamw(1e-4)
    p = jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                       jax.random.PRNGKey(0))
    return jax.jit(hybrid.make_train_step(cfg, optimizer)).trace(
        p, jax.eval_shape(optimizer.init, p),
        jax.ShapeDtypeStruct((batch, seq), jnp.int32))


def test_the_cell_counts_626_million_parameters():
    p = jax.eval_shape(lambda k: hybrid.init_params(k, CELL),
                       jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(p)) == 625_667_136
    assert gm.bound_rows(8192 * 10, 32, gm.choose_tile(8192 * 10, 32)) \
        == 90_112


def test_the_cells_program_lowered_for_tpu_holds_every_kernel():
    """At the cell's shapes (4 layers, 1 x 8,192 tokens, 32 of 512 experts)
    the program lowered for TPU holds the rule's, the convolution's, the
    attention's and the expert layer's kernels, counts one lowering of each
    choice and no dense attention. What XLA:TPU keeps of it is compiled in
    tests/test_ops.py."""
    obs.set_enabled(True)
    names = ("gdn_lowerings", "conv_lowerings", "attn_kernel_lowerings",
             "attn_dense_lowerings")
    before = [obs.counter(n).get_value() for n in names]
    grouped = obs.counter("moe_grouped_lowerings").get_value()
    text = _abstract_step(CELL, 1, 8192).lower(
        lowering_platforms=("tpu",)).as_text()
    assert [obs.counter(n).get_value() - b
            for n, b in zip(names, before)] == [1, 1, 1, 0]
    assert obs.counter("moe_grouped_lowerings").get_value() > grouped
    found = set(re.findall(r"(gdn_chunk_\w+|conv_silu_\w+|attn_flash_\w+|"
                           r"moe_gmm_\w+|moe_rows_\w+)", text))
    assert {"gdn_chunk_prep", "gdn_chunk_fwd", "gdn_chunk_bwd",
            "conv_silu_fwd", "conv_silu_bwd", "attn_flash_fwd",
            "attn_flash_bwd", "moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs",
            "moe_rows_gather", "moe_rows_combine", "moe_rows_pack"} <= found


def test_step_names_its_scopes():
    text = _abstract_step(TINY, 2, 64).lower(
        lowering_platforms=("cpu",)).as_text(debug_info=True)
    for scope in ("gdn.in_proj", "gdn.conv", "gdn.rule", "gdn.out",
                  "gattn.qkv", "gattn.out", "moe.router", "moe.sort",
                  "moe.experts", "moe.combine", "moe.shared"):
        assert scope in text, scope
