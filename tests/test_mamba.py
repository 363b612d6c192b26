"""models/mamba.py against the benchmark's plain reference
(benchmark/reference_mamba.py, which imports nothing of the program and runs
the state-space scan as the per-token recurrence), at a small size on the
CPU with seeded weights: one period of the published table (five Mamba-2
layers, the attention layer, four Mamba-2 layers), hidden 64, 4 SSM heads of
16 over a state of 16, 4 query heads over 2 KV heads of 16, an MLP of 128,
a tied vocabulary of 256, 64 tokens.

Tolerances. With float32 as the compute dtype the program and the reference
do the same arithmetic in another order (the scan in chunks where the
reference walks the tokens, the head in chunks): 1e-4 of each array's scale
(the widest leaf, ``a_log``, reads 4e-6). What bf16, the dtype the cell
runs, costs is read on the chip at the cell's size (PERF.md section 2) and
at the dry run's by the benchmark's own tests.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

import reference  # noqa: E402
import reference_mamba  # noqa: E402
import work_mamba  # noqa: E402

from brpc_tpu.models import mamba  # noqa: E402

SIZES = {
    "hidden_size": 64, "vocab_size": 256, "num_hidden_layers": 10,
    "layer_types": list(mamba.MambaConfig().layer_types),
    "num_attention_heads": 4, "num_key_value_heads": 2, "mamba_n_heads": 4,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "shared_intermediate_size": 128,
    "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8,
}
TINY = mamba.MambaConfig.tiny()
TINY32 = dataclasses.replace(TINY, dtype=jnp.float32)


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _gap(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


@pytest.fixture(scope="module")
def weights():
    """The reference's weights from a seed (the tree the program takes),
    tokens, and the reference's logits, loss and gradients, once."""
    params = jax.jit(lambda k: reference_mamba.mamba_init(k, SIZES))(
        reference.seed_key(5))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    with jax.default_matmul_precision("highest"):
        logits, (loss, grads) = jax.jit(lambda p: (
            reference_mamba.mamba_logits(p, tokens, SIZES),
            jax.value_and_grad(lambda p: reference_mamba.mamba_loss(
                p, tokens, SIZES))(p)))(params)
    return params, tokens, (logits, float(loss), grads)


@pytest.fixture(scope="module")
def program(weights):
    """The program's logits, loss and gradients with float32 as the
    compute dtype."""
    params, tokens, _ = weights
    with jax.default_matmul_precision("highest"):
        logits, ((loss, _), grads) = jax.jit(lambda p: (
            mamba.forward(p, tokens, TINY32),
            jax.value_and_grad(lambda p: mamba.loss_fn(p, tokens, TINY32),
                               has_aux=True)(p)))(params)
    return logits, float(loss), grads


def test_the_layout_is_read_from_layer_types():
    assert mamba.MambaConfig().layout == (4, 9, 5)
    assert TINY.layout == (1, 9, 5)
    assert dataclasses.replace(TINY, n_layers=20).layout == (2, 9, 5)
    period = ("mamba",) * 3 + ("attention",)
    assert dataclasses.replace(TINY, n_layers=8,
                               layer_types=period * 2).layout == (2, 3, 3)
    assert dataclasses.replace(TINY, layer_types=("attention", "mamba") * 5
                               ).layout == (5, 1, 0)
    for bad in (("mamba",) * 10,
                ("mamba", "attention", "mamba", "mamba", "attention") * 2,
                ("mamba", "attention", "attention", "mamba") + ("mamba",) * 6,
                ("mamba", "mlp") * 5):
        with pytest.raises(ValueError):
            _ = dataclasses.replace(TINY, layer_types=bad).layout
    with pytest.raises(ValueError):      # a table shorter than the depth
        _ = dataclasses.replace(TINY, n_layers=50).layout


def test_the_cell_holds_797_850_560_parameters():
    cfg = mamba.MambaConfig(vocab_size=25088, n_layers=10)
    tree = jax.eval_shape(lambda k: mamba.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(tree)) == \
        797_850_560
    assert tree["mamba"]["in_proj"].shape == (1, 9, 2048, 4352 + 4096)
    assert tree["attention"]["wq"].shape == (1, 2048, 2048)
    assert "lm_head" not in tree                         # tied
    # the reference lays the same tree out, leaf for leaf
    m = dict(SIZES, hidden_size=2048, vocab_size=25088, num_attention_heads=32,
             num_key_value_heads=8, mamba_n_heads=64, mamba_d_head=64,
             mamba_d_state=128, shared_intermediate_size=8192)
    ref = jax.eval_shape(lambda k: reference_mamba.mamba_init(k, m),
                         jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, ref) == \
        jax.tree_util.tree_map(lambda a: a.shape, tree)
    assert work_mamba.parameters(m) == 797_850_560


def test_the_program_in_float32_is_the_reference(weights, program):
    want, loss, grads = weights[2]
    logits, got_loss, got = program
    assert abs(got_loss - loss) <= 1e-5 * abs(loss)
    assert float(jnp.max(jnp.abs(logits - want))) <= 1e-4 * float(
        jnp.max(jnp.abs(want)))
    want_leaves = _leaves(grads)
    for name, g in _leaves(got).items():
        assert _gap(g, want_leaves[name]) <= 1e-4, name


def test_the_tied_leaf_gets_the_gradient_of_both_uses(weights, program):
    """The embedding's gradient is what the gather and the head each give
    it: the reference with the two uses on separate matrices."""
    params, tokens, _ = weights
    got = program[2]

    def loss(embed, head):
        return reference_mamba.mamba_loss(dict(params, embed=embed), tokens,
                                          SIZES, head=head)

    with jax.default_matmul_precision("highest"):
        gather, head = jax.jit(jax.grad(loss, argnums=(0, 1)))(
            params["embed"], params["embed"])
    assert _gap(got["embed"], gather + head) <= 1e-4
    # both parts are there: neither alone is the whole
    assert _gap(got["embed"], head) > 1e-2
    assert _gap(got["embed"], gather) > 1e-2

