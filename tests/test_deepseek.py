"""models/deepseek.py against the benchmark's plain reference
(benchmark/reference_dsv3.py, which imports nothing of the program), at a
small size on the CPU with seeded weights: hidden 64, 8 experts of which 2
are held, top-2, heads of 24 = 16 + 8 and v 16, vocabulary 256, 1 dense + 2
expert layers.

Tolerances. With float32 as the compute dtype the program and the reference
do the same arithmetic in another order (the grouped product sums a token's
experts after the matmuls, the reference before; the head is taken in
chunks), so they agree to float32 rounding through ~10 layers of sums: 1e-5
of each array's scale. In bf16, the dtype the cell runs, every matmul
operand carries 2^-9 of relative rounding, which the stack compounds to a
few percent element by element and to under 3% of a leaf's norm.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

import reference  # noqa: E402
import reference_dsv3  # noqa: E402

from brpc_tpu.models import deepseek, experts  # noqa: E402
from brpc_tpu.ops import flash_attention  # noqa: E402
from brpc_tpu.ops import grouped_matmul as gm  # noqa: E402

SIZES = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "vocab_size": 256, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "n_routed_experts": 2,
    "router_experts": 8, "expert_offset": 0, "n_shared_experts": 2,
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.448,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6,
}
ADAMW = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
         "weight_decay": 1e-4}
SEED = 5
TINY = deepseek.DeepseekConfig.tiny()
TINY32 = dataclasses.replace(TINY, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: reference_dsv3.dsv3_init(k, SIZES))(
        reference.seed_key(SEED))


@pytest.fixture(scope="module")
def tokens():
    return reference.token_batches(SEED, 4, 2, 64, SIZES["vocab_size"])


def _scale_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) /
                 jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def test_tree_is_the_references(params):
    mine = deepseek.init_params(jax.random.PRNGKey(0), TINY)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype


@pytest.mark.parametrize("cfg,loss_tol,leaf_tol", [
    (TINY32, 1e-6, 1e-5), (TINY, 2e-3, 6e-2)], ids=["float32", "bfloat16"])
def test_loss_and_every_gradient_leaf(params, tokens, cfg, loss_tol,
                                      leaf_tol):
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            lambda p, t: deepseek.loss_fn(p, t, cfg), has_aux=True))(
                params, tokens[0])
    (want_loss, selected), want = jax.jit(jax.value_and_grad(
        lambda p, t: reference_dsv3.dsv3_loss(p, t, SIZES), has_aux=True))(
            params, tokens[0])
    assert abs(float(loss) - float(want_loss)) <= loss_tol * float(want_loss)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want)):
        assert _scale_gap(g, w) <= leaf_tol, jax.tree_util.keystr(path)
    assert not np.any(np.asarray(grads["moe"]["router_bias"]))
    agree = np.mean(np.sort(np.asarray(stats["selected"]), -1)
                    == np.sort(np.asarray(selected), -1))
    assert agree == 1.0 if cfg is TINY32 else agree > 0.97
    assert int(stats["dropped"].sum()) == 0


def test_forward_is_the_loss_s_logits(params, tokens):
    logits = deepseek.forward(params, tokens[0], TINY32)
    nll = (jax.nn.logsumexp(logits[:, :-1], axis=-1) - jnp.take_along_axis(
        logits[:, :-1], tokens[0][:, 1:, None], axis=-1)[..., 0])
    loss, _ = deepseek.loss_fn(params, tokens[0], TINY32)
    assert abs(float(jnp.mean(nll)) - float(loss)) <= 1e-6 * float(loss)


def test_three_adamw_steps_follow_the_reference(params, tokens):
    want = reference_dsv3.train_reference(SEED, SIZES, ADAMW, tokens, 3)
    optimizer = optax.adamw(ADAMW["learning_rate"], b1=ADAMW["b1"],
                            b2=ADAMW["b2"], eps=ADAMW["eps"],
                            weight_decay=ADAMW["weight_decay"])
    step = jax.jit(deepseek.make_train_step(TINY32, optimizer))
    p, state, losses = params, optimizer.init(params), []
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            p, state, loss, stats = step(p, state, tokens[i])
            losses.append(float(loss))
    # float32 both sides; Adam's first steps are lr * sign(g) where |g| is
    # far above eps, so the parameters' change repeats to rounding too
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-5)
    delta = {k: float(v) for k, v in reference.leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, p, params)).items()}
    moving = reference.moving_leaves(want["grad_norms"])
    assert reference.worst_leaf_gap({k: delta[k] for k in moving}, {
        k: want["delta_norms"][k] for k in moving}) <= 1e-3
    # the buffer is held fixed, by the program and by the reference
    assert delta["['moe']['router_bias']"] == 0.0
    # (to an ulp of 0.01: its change is taken against the init recomputed
    # inside another program)
    assert want["delta_norms"]["['moe']['router_bias']"] <= 1e-8


# -- what the recomputation keeps changes no gradient -------------------------

# Kernel-eligible and small enough for the Pallas interpreter: 2 heads of
# 128 + 64 / 128 over one tile of 128 tokens, 1 dense + 1 expert layer.
KERNEL = dataclasses.replace(
    TINY, vocab_size=64, n_layers=2, n_heads=2, qk_nope_dim=128,
    qk_rope_dim=64, v_dim=128)


def _gradients(monkeypatch, cfg, params, tokens, keeps):
    """``keeps``: "names" (the model as it is), "input" (a bare
    ``jax.checkpoint``: the policy is given no name) or "all" (no checkpoint
    at all)."""
    with monkeypatch.context() as mp:
        if keeps == "input":
            mp.setattr(deepseek, "SAVED_NAMES", ())
        elif keeps == "all":
            mp.setattr(jax, "checkpoint", lambda fun, **_: fun)
        return jax.jit(jax.grad(
            lambda p, t: deepseek.loss_fn(p, t, cfg)[0]))(params, tokens)


@pytest.mark.parametrize("cfg,interpreted", [
    (TINY, False), (TINY32, False), (KERNEL, True)],
    ids=["dense_bfloat16", "dense_float32", "kernels_interpreted"])
def test_gradients_do_not_depend_on_what_the_recomputation_keeps(
        monkeypatch, cfg, interpreted):
    """Every leaf under the policy, under a bare checkpoint and with no
    checkpoint at all, bit for bit: on the CPU attention is the dense form
    and nothing has a name; through the interpreted kernels the policy saves
    their output and log-sum-exp, which are what the recomputation would
    have produced."""
    if interpreted:
        monkeypatch.setattr(deepseek, "attention", functools.partial(
            flash_attention, interpret=True))
    params = deepseek.init_params(jax.random.PRNGKey(1), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 128), 0,
                                cfg.vocab_size)
    want = _gradients(monkeypatch, cfg, params, tokens, "all")
    for keeps in ("names", "input"):
        got = _gradients(monkeypatch, cfg, params, tokens, keeps)
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree_util.tree_leaves(want)):
            assert np.array_equal(np.asarray(g), np.asarray(w)), (
                keeps, jax.tree_util.keystr(path))


# -- one expert layer ---------------------------------------------------------

def _layer(params, i=0):
    return {k: v[i] for k, v in params["moe"].items()}


def _tokens_in(n=96, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 64), jnp.float32)


def _whole_layer(params):
    """One expert layer with all 8 experts: the held layer's own 2 and six
    more, seeded."""
    lp = dict(_layer(params))
    for name, shape, fan_in in (("w_gate", (8, 64, 32), 64),
                                ("w_up", (8, 64, 32), 64),
                                ("w_down", (8, 32, 64), 32)):
        lp[name] = jax.random.normal(jax.random.PRNGKey(len(name)), shape,
                                     jnp.float32) * fan_in ** -0.5
    return lp


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts that the four shares give (offsets 0, 2, 4, 6) plus
    the shared experts counted once are the uncut reference's whole layer."""
    lp, y = _whole_layer(params), _tokens_in()
    whole, _ = reference_dsv3.moe_mlp(
        y, lp, {**SIZES, "n_routed_experts": 8, "expert_offset": 0})
    shared = deepseek._swiglu(y, lp["shared_gate"], lp["shared_up"],
                              lp["shared_down"])
    total = shared
    with jax.default_matmul_precision("highest"):
        for offset in (0, 2, 4, 6):
            cfg = dataclasses.replace(TINY32, n_held=2, expert_offset=offset)
            share = {**lp, **{k: lp[k][offset:offset + 2]
                              for k in ("w_gate", "w_up", "w_down")}}
            out, stats = deepseek.moe_mlp(cfg, y, share)
            want, _ = reference_dsv3.moe_mlp(
                y, share, {**SIZES, "expert_offset": offset})
            assert _scale_gap(out, want) <= 1e-5, offset
            assert int(stats["dropped"]) == 0
            total = total + (out - shared)
    assert _scale_gap(total, whole) <= 1e-5


def test_no_token_is_dropped_when_all_choose_one_held_expert(params):
    lp, y = dict(_layer(params)), _tokens_in()
    lp["router_bias"] = lp["router_bias"].at[0].add(10.0)
    with jax.default_matmul_precision("highest"):
        out, stats = deepseek.moe_mlp(TINY32, y, lp)
    want, selected = reference_dsv3.moe_mlp(y, lp, SIZES)
    assert np.all(np.asarray(selected)[:, 0] == 0)
    assert int(stats["group_max"]) == y.shape[0]
    assert int(stats["dropped"]) == 0
    assert int(stats["routed"]) >= y.shape[0]
    assert _scale_gap(out, want) <= 1e-5


def test_rows_in_use_are_the_whole_tiles_of_the_layout(params):
    """``stats["rows_in_use"]``: every held expert's rows rounded up to a
    tile (an expert nobody chose keeps one), which is the layout's
    ``n_tiles`` x tile and what the row movement works over."""
    lp, y = dict(_layer(params)), _tokens_in()
    _, stats = deepseek.moe_mlp(TINY32, y, lp)
    selected = np.asarray(stats["selected"])
    held = selected < TINY32.n_held
    tile = gm.choose_tile(selected.size, TINY32.n_held)
    lay = gm.group_layout(
        jnp.asarray(np.where(held, selected, TINY32.n_held).reshape(-1)),
        TINY32.n_held, tile)
    sizes = np.bincount(selected[held], minlength=TINY32.n_held)
    assert int(stats["rows_in_use"]) == int(lay.n_tiles[0]) * tile == sum(
        max(-(-int(s) // tile), 1) for s in sizes) * tile
    assert int(stats["routed"]) <= int(stats["rows_in_use"]) < len(
        np.asarray(lay.row_valid))


def test_selection_sees_the_bias_and_weights_do_not(params):
    lp, y = _layer(params), _tokens_in()
    cfg = dataclasses.replace(TINY32, experts_per_token=3)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(y, lp["router"],
                                          precision="highest")))
    plain, _ = experts.sigmoid_route(cfg, y, lp["router"], jnp.zeros(8))
    bias = np.zeros(8, np.float32)
    bias[5] = 0.2                       # lifts expert 5 over some third picks
    selected, weights = experts.sigmoid_route(cfg, y, lp["router"],
                                              jnp.asarray(bias))
    selected, weights = np.asarray(selected), np.asarray(weights)
    assert np.array_equal(np.sort(selected, 1),
                          np.sort(np.argsort(-(s + bias), 1)[:, :3], 1))
    changed = np.any(np.sort(selected, 1) != np.sort(np.asarray(plain), 1), 1)
    assert 0 < changed.sum() < len(changed)
    picked = np.take_along_axis(s, selected, 1)         # s, without the bias
    np.testing.assert_allclose(
        weights, picked / picked.sum(1, keepdims=True) * 2.448, rtol=1e-6)
