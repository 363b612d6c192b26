import jax
import jax.numpy as jnp
import numpy as np
import optax

from brpc_tpu.models import llama
from brpc_tpu.parallel import make_mesh, shard_batch, shard_params


def test_forward_shapes_and_finite():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = jax.jit(lambda p, t: llama.forward(p, t, cfg))(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality():
    """Changing a future token must not change past logits."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, -1].set(9)
    l1 = llama.forward(params, t1, cfg)
    l2 = llama.forward(params, t2, cfg)
    np.testing.assert_allclose(l1[:, :-1], l2[:, :-1], rtol=1e-5, atol=1e-5)


def test_train_step_reduces_loss():
    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(3e-3)
    opt_state = opt.init(params)
    step = jax.jit(llama.make_train_step(cfg, opt))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size)
    _, _, loss0 = step(params, opt_state, tokens)
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, tokens)
    assert float(loss) < float(loss0)


def test_sharded_train_step_matches_single_device():
    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.sgd(1e-2)
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, cfg.vocab_size)
    )
    step = jax.jit(llama.make_train_step(cfg, opt))

    # single device
    p1, _, loss1 = step(params, opt.init(params), jnp.asarray(tokens))

    # dp=4 × tp=2 mesh
    mesh = make_mesh({"tp": 2})
    sp = shard_params(params, llama.param_specs(cfg), mesh)
    st = shard_batch(tokens, llama.batch_specs(), mesh)
    p2, _, loss2 = step(sp, opt.init(sp), st)

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-4)
    flat1 = jax.tree_util.tree_leaves(p1)
    flat2 = jax.tree_util.tree_leaves(p2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-2, atol=2e-2
        )


def test_dryrun_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)
