"""models/looped.py against the benchmark's plain reference
(benchmark/reference_looped.py, which imports nothing of the program), at a
small size on the CPU with seeded weights: hidden 64, 4 heads of 16,
intermediate 128, vocabulary 256, 2 layers run 3 times.

Tolerances. With float32 as the compute dtype the program and the reference
do the same arithmetic in another order (a scan over the shared stack where
the reference unrolls, the head in chunks of the flattened positions where
the reference takes blocks of the counted ones, the exit distribution in log
space where the reference multiplies), so they agree to float32 rounding
through 6 layer applications of sums: 2e-5 of each array's scale, 1e-6 on
the loss. In bf16, the dtype the cell runs, every matmul operand carries 2^-9
of relative rounding, which six applications compound to a few percent
element by element: bf16 for float32 fails the float32 tolerance by three
orders of magnitude (asserted below), so a lower precision than the
configuration states cannot pass for it.
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
import reference_looped  # noqa: E402

from brpc_tpu import obs  # noqa: E402
from brpc_tpu.models import chunked_loss, deepseek, looped  # noqa: E402

SIZES = {
    "hidden_size": 64, "intermediate_size": 128, "vocab_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "total_ut_steps": 3,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "exit_beta": 0.1,
}
ADAMW = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
         "weight_decay": 1e-4}
SEED = 5
TINY = looped.LoopedConfig.tiny()
TINY32 = dataclasses.replace(TINY, dtype=jnp.float32)
F32_LOSS_TOL, F32_LEAF_TOL = 1e-6, 2e-5


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: reference_looped.looped_init(k, SIZES))(
        reference.seed_key(SEED))


@pytest.fixture(scope="module")
def tokens():
    return reference.token_batches(SEED, 4, 2, 64, SIZES["vocab_size"])


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's (loss, stats) and gradients on the first batch."""
    return jax.jit(jax.value_and_grad(
        lambda p, t: reference_looped.looped_loss(p, t, SIZES),
        has_aux=True))(params, tokens[0])


def _grad(cfg, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, t: looped.loss_fn(p, t, cfg), has_aux=True))(
                params, batch)


def _scale_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) /
                 jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def _worst_leaf(grads, want):
    return max(_scale_gap(g, w) for g, w in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)))


def test_tree_is_the_references(params):
    mine = looped.init_params(jax.random.PRNGKey(0), TINY)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype
    # the gate does something from the first step on
    assert float(jnp.std(mine["exit_gate"]["w"])) > 0


@pytest.mark.parametrize("cfg,loss_tol,leaf_tol,stat_tol", [
    (TINY32, F32_LOSS_TOL, F32_LEAF_TOL, 1e-6),
    # bf16: 2^-9 an operand through 6 applications; the gate's bias, a
    # scalar whose gradient is a hundredth of the others', is the worst leaf
    (TINY, 1e-3, 0.25, 3e-3)], ids=["float32", "bfloat16"])
def test_loss_stats_and_every_gradient_leaf(params, tokens, want, cfg,
                                            loss_tol, leaf_tol, stat_tol):
    (loss, stats), grads = _grad(cfg, params, tokens[0])
    (want_loss, want_stats), want_grads = want
    assert abs(float(loss) - float(want_loss)) <= loss_tol * float(want_loss)
    assert set(stats) == {"pass_loss", "exit_mass", "exit_entropy"}
    for key in stats:
        np.testing.assert_allclose(stats[key], want_stats[key],
                                   rtol=stat_tol, atol=stat_tol, err_msg=key)
    assert stats["pass_loss"].shape == stats["exit_mass"].shape == (3,)
    assert abs(float(jnp.sum(stats["exit_mass"])) - 1.0) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want_grads)):
        assert _scale_gap(g, w) <= leaf_tol, jax.tree_util.keystr(path)


def test_bfloat16_for_float32_fails_the_float32_tolerance(params, tokens,
                                                         want):
    (loss, _), grads = _grad(TINY, params, tokens[0])
    (want_loss, _), want_grads = want
    assert abs(float(loss) - float(want_loss)) > \
        F32_LOSS_TOL * float(want_loss)
    assert _worst_leaf(grads, want_grads) > 100 * F32_LEAF_TOL


def test_three_adamw_steps_follow_the_reference(params, tokens):
    ref = reference_looped.train_reference(SEED, SIZES, ADAMW, tokens, 3)
    optimizer = optax.adamw(ADAMW["learning_rate"], b1=ADAMW["b1"],
                            b2=ADAMW["b2"], eps=ADAMW["eps"],
                            weight_decay=ADAMW["weight_decay"])
    step = jax.jit(looped.make_train_step(TINY32, optimizer))
    p, state, losses = params, optimizer.init(params), []
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            p, state, loss, stats = step(p, state, tokens[i])
            losses.append(float(loss))
            if i == 0:
                first = stats
    # float32 both sides; Adam's first steps are lr * sign(g) where |g| is
    # far above eps, so the parameters' change repeats to rounding too
    np.testing.assert_allclose(losses, ref["losses"], rtol=2e-5)
    np.testing.assert_allclose(first["pass_loss"], ref["pass_loss"],
                               rtol=1e-5)
    np.testing.assert_allclose(first["exit_mass"], ref["exit_mass"],
                               atol=1e-6)
    delta = jax.jit(reference.leaf_norms)(jax.tree_util.tree_map(
        lambda a, b: a - b, p, params))
    for k, v in ref["delta_norms"].items():
        assert abs(float(delta[k]) - v) <= 1e-3 * v, k


def test_shared_gradient_is_the_sum_over_untied_copies(params, tokens):
    """The stack run R times on one set of weights: its gradient is the sum
    of the gradients of R untied copies, one a pass, at the shared point."""
    passes = TINY32.total_ut_steps
    positions = jnp.broadcast_to(jnp.arange(64), (2, 64))

    def untied_loss(copies, rest, batch):
        x = rest["embed"][batch]
        states = []
        for layers in copies:                    # a pass on its own copy
            for i in range(TINY32.n_layers):
                x = looped._layer(TINY32, x, jax.tree_util.tree_map(
                    lambda w: w[i], layers), positions)
            x = looped.rms_norm(x, rest["final_norm"], TINY32.norm_eps)
            states.append(x)
        return looped.loss_of_states(rest, jnp.stack(states), batch,
                                     TINY32)[0]

    rest = {k: v for k, v in params.items() if k != "layers"}
    with jax.default_matmul_precision("highest"):
        loss, per_copy = jax.jit(jax.value_and_grad(untied_loss))(
            [params["layers"]] * passes, rest, tokens[0])
    (tied_loss, _), tied = _grad(TINY32, params, tokens[0])
    assert abs(float(loss) - float(tied_loss)) <= 1e-6 * float(tied_loss)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_copy)
    for key, g in tied["layers"].items():
        assert _scale_gap(g, summed[key]) <= F32_LEAF_TOL, key
        # and no single pass's gradient is the whole of it
        assert _scale_gap(g, per_copy[0][key]) > 0.05, key


def test_one_pass_without_entropy_is_plain_cross_entropy(params, tokens):
    """R = 1, beta = 0: the exit distribution is all on the one pass and the
    loss is the next-token cross-entropy of a one-pass stack."""
    cfg = dataclasses.replace(TINY32, total_ut_steps=1, exit_beta=0.0)
    with jax.default_matmul_precision("highest"):
        loss, stats = looped.loss_fn(params, tokens[0], cfg)
        (state,) = looped.hidden_states(params, tokens[0], cfg)
        logits = jnp.dot(state, params["lm_head"])[:, :-1]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, tokens[0][:, 1:, None], axis=-1)[..., 0]
    assert abs(float(loss) - float(jnp.mean(nll))) <= 1e-6 * float(loss)
    assert stats["exit_mass"].tolist() == [1.0]
    assert float(stats["exit_entropy"]) == 0.0
    assert float(stats["pass_loss"][0]) == pytest.approx(float(loss),
                                                         rel=1e-6)


def test_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest():
    z = jnp.array([[0.3, -1.2, 2.0, 9.0], [-30.0, 40.0, 0.0, -5.0]])
    p = jnp.exp(looped.exit_log_probs(z))
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(jnp.sum(p, axis=-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[:, 0], lam[:, 0], rtol=1e-6)
    np.testing.assert_allclose(
        p[:, 3], (1 - lam[:, 0]) * (1 - lam[:, 1]) * (1 - lam[:, 2]),
        rtol=1e-5, atol=1e-12)     # 1 - sigmoid(40) is 0 in float32
    np.testing.assert_allclose(p, reference_looped.exit_distribution(lam),
                               rtol=1e-5, atol=1e-12)


# -- the shared chunked loss -------------------------------------------------------

@pytest.mark.parametrize("passes", [1, 2])
def test_chunked_loss_is_the_whole_logits_loss(passes):
    """Several chunks (1,536 positions: chunks of 768 at one pass, 512 at
    two), the last position of each sequence uncounted."""
    b, t, h, v = 2, 768, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(passes), 4)
    states = tuple(jax.random.normal(k, (b, t, h)) for k in
                   jax.random.split(ks[0], passes))
    head = jax.random.normal(ks[1], (h, v)) * h ** -0.5
    tokens = jax.random.randint(ks[2], (b, t), 0, v)
    weights = jax.random.uniform(ks[3], (b, t, passes))
    assert b * t // chunked_loss.chunk_size(b * t, passes) == passes + 1

    def position_loss(nlls, w_c):
        nll = jnp.stack(nlls, axis=-1)
        return {"loss": jnp.sum(w_c * nll, axis=-1), "each": nll}

    def chunked(states, head):
        out = chunked_loss.chunked_next_token_loss(
            states, head, tokens, position_loss, extras=(weights,))
        return out["loss"], out["each"]

    def whole(states, head):
        logits = jnp.stack([s @ head for s in states], axis=-1)[:, :-1]
        nll = jax.nn.logsumexp(logits, axis=2) - jnp.take_along_axis(
            logits, tokens[:, 1:, None, None], axis=2)[:, :, 0]
        return (jnp.mean(jnp.sum(weights[:, :-1] * nll, axis=-1)),
                jnp.mean(nll, axis=(0, 1)))

    with jax.default_matmul_precision("highest"):
        (got, each), g = jax.value_and_grad(chunked, (0, 1), has_aux=True)(
            states, head)
        (ref, ref_each), w = jax.value_and_grad(whole, (0, 1), has_aux=True)(
            states, head)
    assert abs(float(got) - float(ref)) <= 1e-6 * float(ref)
    np.testing.assert_allclose(each, ref_each, rtol=1e-6)
    assert _worst_leaf(g, w) <= 1e-5


def test_chunk_size_divides_and_shrinks_with_the_passes():
    assert chunked_loss.chunk_size(8192) == 1024
    assert chunked_loss.chunk_size(8192, 4) == 256
    assert chunked_loss.chunk_size(1536, 2) == 512
    assert chunked_loss.chunk_size(126) == 126
    assert chunked_loss.chunk_size(2 * 1031) == 2      # a prime sequence


def test_deepseek_loss_traces_to_the_program_it_was():
    """``deepseek.loss_fn`` through the shared function against the loop it
    held before PR 32, written out here: the same jaxpr, equation for
    equation (at the kanana cell's geometry the builder compared the two
    commits' texts, PERF.md section 6)."""
    cfg = deepseek.DeepseekConfig.tiny()

    def before(params, tokens):
        x, stats = deepseek.hidden_states(params, tokens, cfg)
        b, t, h = x.shape
        head = params["lm_head"].astype(cfg.dtype)
        targets = jnp.roll(tokens, -1, axis=1).reshape(b * t)
        counts = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t)).reshape(b * t)
        chunk = next(c for c in range(min(b * t, 1024), 0, -1)
                     if (b * t) % c == 0)

        @jax.checkpoint
        def piece(total, args):
            x_c, target_c, counts_c = args
            logits = jnp.dot(x_c, head, preferred_element_type=jnp.float32)
            gold = jnp.take_along_axis(logits, target_c[:, None], axis=1)[:, 0]
            nll = jax.nn.logsumexp(logits, axis=-1) - gold
            return total + jnp.sum(jnp.where(counts_c, nll, 0.0)), None

        total, _ = jax.lax.scan(piece, jnp.zeros((), jnp.float32), (
            x.reshape(-1, chunk, h), targets.reshape(-1, chunk),
            counts.reshape(-1, chunk)))
        return total / (b * (t - 1)), stats

    params = jax.eval_shape(lambda k: deepseek.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((3, 1024), jnp.int32)   # three chunks

    def text(f):
        return re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(
            jax.value_and_grad(f, has_aux=True))(params, tokens)))

    assert text(lambda p, t: deepseek.loss_fn(p, t, cfg)) == text(before)


# -- scopes, and which attention the cell's program holds -----------------------------

CELL = dataclasses.replace(looped.LoopedConfig(), n_layers=8)


def _abstract_step(cfg, batch, seq):
    optimizer = optax.adamw(1e-4)
    p = jax.eval_shape(lambda k: looped.init_params(k, cfg),
                       jax.random.PRNGKey(0))
    return jax.jit(looped.make_train_step(cfg, optimizer)).trace(
        p, jax.eval_shape(optimizer.init, p),
        jax.ShapeDtypeStruct((batch, seq), jnp.int32))


def test_the_cells_program_lowered_for_tpu_holds_the_kernels():
    """At the cell's shapes (8 layers, 2 x 4,096 tokens, a query group of
    one) ``supported`` admits the kernels and the program lowered for TPU
    holds them and counts no dense attention; the choice is counted once
    though three places hold it (forward scan, its recomputation,
    backward). What XLA:TPU keeps of it is compiled in tests/test_ops.py."""
    obs.set_enabled(True)
    names = ("attn_kernel_lowerings", "attn_dense_lowerings")
    before = [obs.counter(n).get_value() for n in names]
    text = _abstract_step(CELL, 2, 4096).lower(
        lowering_platforms=("tpu",)).as_text()
    assert [obs.counter(n).get_value() - b
            for n, b in zip(names, before)] == [1, 0]
    assert "tpu_custom_call" in text
    assert {"attn_flash_fwd", "attn_flash_bwd"} <= set(
        re.findall(r"attn_flash_\w+", text))


def test_step_names_its_scopes():
    text = _abstract_step(TINY, 2, 64).lower(
        lowering_platforms=("cpu",)).as_text(debug_info=True)
    for scope in ("loop.layer.attn", "loop.layer.mlp", "loop.pass_norm",
                  "loop.exit_gate", "loop.head", "loop.exit_loss"):
        assert scope in text, scope


def test_pass_loop_is_one_scan_around_the_layer_scan():
    """Not R copies of the program: the differentiated loss holds one
    forward scan of R iterations whose body holds the scan over layers."""
    jaxpr = jax.make_jaxpr(lambda p, t: looped.loss_fn(p, t, TINY)[0])(
        jax.eval_shape(lambda k: looped.init_params(k, TINY),
                       jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((2, 64), jnp.int32)).jaxpr
    outer = [e for e in jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == TINY.total_ut_steps]
    assert len(outer) == 1
    inner = [e for e in outer[0].params["jaxpr"].jaxpr.eqns
             if e.primitive.name == "scan"]
    assert [e.params["length"] for e in inner] == [TINY.n_layers]
