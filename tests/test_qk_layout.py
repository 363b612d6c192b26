"""ops/qk_layout.py: q and k from their projections to the attention kernels
in one pass (norm, rope and the turn to head-major), by the Pallas
interpreter on the CPU against the plain form models/windowed.py ran before
it and still runs wherever the kernels do not: ``llama.rms_norm`` ->
``llama.rope`` / ``windowed.yarn_rope`` -> transpose.

``ops/qk_layout.py`` is the two kernels alone; their VJP and the choice
between them and the plain form are ``windowed.qk_head_major``'s.

The kernels round once, at their output; the plain form rounds to bf16 after
the norm, after its weight and after the rope. So against the plain form in
bf16 they agree to bf16's step, and against the plain form in float32 they
are held to be no further off than the plain form in bf16 is.
"""

import dataclasses
import importlib
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu import obs
from brpc_tpu.models import deepseek, hybrid, llama, looped, windowed
from brpc_tpu.ops import qk_layout as ql

fa = importlib.import_module("brpc_tpu.ops.flash_attention")

LAGUNA = windowed.WindowedConfig()
MELLUM = windowed.WindowedConfig.mellum2()
# Three blocks of 256 positions, a batch row each side of Mellum2's original
# context: no position is 0.
B, T = 2, 768
POSITIONS = jnp.arange(T)[None] + jnp.array([[37], [7901]])

GEOMETRIES = {
    # 64 heads over 8, the whole head turned at theta 10,000, no norm
    "laguna_window": (LAGUNA, "window"),
    # 48 over 8, the leading 64 of 128 lanes by yarn at a factor of 64
    "laguna_full": (LAGUNA, "full"),
    # 32 over 4, q/k norms, the whole head turned at theta 500,000
    "mellum_window": (MELLUM, "window"),
    # 32 over 4, q/k norms, the whole head by yarn at a factor of 16
    "mellum_full": (MELLUM, "full"),
}


def _heads(cfg, kind):
    return cfg.full_heads if kind == "full" else cfg.window_heads


def _operands(cfg, kind, dtype=jnp.bfloat16, t=T, head_dim=None):
    d = head_dim or cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    q = jax.random.normal(keys[0], (B, t, _heads(cfg, kind) * d)).astype(dtype)
    k = jax.random.normal(keys[1], (B, t, cfg.n_kv_heads * d)).astype(dtype)
    norms = None
    if cfg.qk_norm:
        norms = tuple((1 + 0.3 * jax.random.normal(key, (d,))).astype(dtype)
                      for key in keys[2:4])
    # what the attention kernels' backward hands back, head-major
    cotangents = (jax.random.normal(keys[4], (B, _heads(cfg, kind), t, d)),
                  jax.random.normal(keys[5], (B, cfg.n_kv_heads, t, d)))
    return q, k, norms, cotangents


def _plain(cfg, kind, q, k, norms, positions=POSITIONS):
    """What ``attention_block`` ran before the kernels."""
    b, t, _ = q.shape
    q, k = (x.reshape(b, t, -1, cfg.head_dim) for x in (q, k))
    if norms is not None:
        q = llama.rms_norm(q, norms[0], cfg.norm_eps)
        k = llama.rms_norm(k, norms[1], cfg.norm_eps)
    if kind == "full":
        q, k = (windowed.yarn_rope(cfg, x, positions) for x in (q, k))
    else:
        q, k = (llama.rope(x, positions, cfg.window_rope_theta)
                for x in (q, k))
    return q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)


def _kernels(cfg, kind, q, k, norms, cotangents):
    """(q and k head-major, the gradients to q's and k's inputs and to the
    norms' weights) of the two kernels, interpreted."""
    rot, inv_freq, scale = windowed._rotary(cfg, kind == "full")
    cos, sin = ql.rotation_tables(POSITIONS, inv_freq, cfg.head_dim, scale)
    weights = None if norms is None else jnp.stack(norms).astype(jnp.float32)
    out = ql.forward(q, k, cos, sin, weights, rot // 2, cfg.norm_eps, True)
    dq, dk, dw = ql.backward(
        *(c.astype(q.dtype) for c in cotangents), q, k, cos, sin, weights,
        rot // 2, cfg.norm_eps, True)
    return [*out, dq, dk, *([] if dw is None else dw.astype(q.dtype))]


def _plain_form(cfg, kind, q, k, norms, cotangents):
    """The same of the plain form under JAX's own transposes."""
    out, vjp = jax.vjp(lambda q, k, norms: _plain(cfg, kind, q, k, norms),
                       q, k, norms)
    grads = vjp(tuple(c.astype(q.dtype) for c in cotangents))
    return jax.tree_util.tree_leaves((out, grads))


def _gap(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("name", GEOMETRIES)
def test_kernels_follow_the_plain_form(name):
    """Forward values and every gradient — to q's and k's inputs and, with
    norms, to ``q_norm`` and ``k_norm`` — at the two models' geometries, over
    three blocks of positions that do not start at 0."""
    cfg, kind = GEOMETRIES[name]
    q, k, norms, cotangents = _operands(cfg, kind)
    assert ql.supported(q.shape, k.shape, q.dtype, cfg.head_dim,
                        windowed._rotary(cfg, kind == "full")[0])
    assert ql._block(T) == 256 and T // ql._block(T) == 3
    got = jax.jit(_kernels, static_argnums=(0, 1))(
        cfg, kind, q, k, norms, cotangents)
    plain = jax.jit(_plain_form, static_argnums=(0, 1))(
        cfg, kind, q, k, norms, cotangents)
    exact = jax.jit(_plain_form, static_argnums=(0, 1))(
        cfg, kind, *jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), (q, k, norms)), cotangents)
    assert len(got) == len(plain) == (6 if cfg.qk_norm else 4)
    assert got[0].shape == (B, _heads(cfg, kind), T, cfg.head_dim)
    assert got[1].shape == (B, cfg.n_kv_heads, T, cfg.head_dim)
    for i, (mine, bf16, f32) in enumerate(zip(got, plain, exact)):
        assert mine.shape == bf16.shape and mine.dtype == bf16.dtype, i
        # the norms' weights' gradients are sums over B x T x heads terms of
        # either sign, where the plain form's two roundings of each term
        # show: up to 7% there, which the kernel's float32 sums do not have
        assert _gap(mine, bf16) <= (0.1 if i >= 4 else 0.012), i
        assert _gap(mine, f32) <= max(_gap(bf16, f32), 1e-3) * 1.05, i
        assert _gap(mine, f32) <= 0.008, i
    if kind == "full" and cfg.full_rotary_factor < 1:   # the lanes that pass
        rot = int(cfg.head_dim * cfg.full_rotary_factor)
        want = q.reshape(B, T, -1, cfg.head_dim).transpose(0, 2, 1, 3)
        assert np.array_equal(np.asarray(got[0][..., rot:], np.float32),
                              np.asarray(want[..., rot:], np.float32))


def test_tables_are_the_ropes_own_cosines_and_sines():
    """``x · C + partner(x) · S`` written out: C is cos, cos, ones and S is
    -sin, sin, zeros, of ``llama.rope``'s own angles, times the scale."""
    rot, inv_freq, scale = windowed._rotary(LAGUNA, True)
    cos, sin = ql.rotation_tables(POSITIONS, inv_freq, 128, scale)
    assert cos.shape == sin.shape == (B, T, 128) and cos.dtype == jnp.float32
    angles = np.asarray(POSITIONS, np.float32)[..., None] * inv_freq
    half = rot // 2
    np.testing.assert_allclose(cos[..., :half], np.cos(angles) * scale,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(cos[..., :half], cos[..., half:rot])
    np.testing.assert_array_equal(sin[..., :half], -sin[..., half:rot])
    np.testing.assert_allclose(sin[..., half:rot], np.sin(angles) * scale,
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(cos[..., rot:]) == 1.0)
    assert np.all(np.asarray(sin[..., rot:]) == 0.0)
    rot, inv_freq, scale = windowed._rotary(MELLUM, False)
    assert (rot, scale) == (128, 1.0)
    np.testing.assert_array_equal(
        inv_freq, 500000.0 ** (-jnp.arange(0, 64, dtype=jnp.float32) / 64))


def _lowerings():
    return [obs.counter(n).get_value() for n in
            ("qk_layout_kernel_lowerings", "qk_layout_plain_lowerings",
             "attn_kernel_lowerings", "attn_dense_lowerings")]


def _plain_attention(cfg, kind, q, k, v, norms, positions):
    q, k = (x.transpose(0, 2, 1, 3)
            for x in _plain(cfg, kind, q, k, norms, positions))
    return llama.dense_attention(
        q, k, v, window=None if kind == "full" else cfg.window)


@pytest.mark.parametrize("refused", ["float32", "head_of_64", "odd_length"])
def test_a_shape_the_kernels_refuse_runs_the_plain_form(refused):
    """``supported`` is a test of the shapes: float32 compute, a head that
    is no whole row of 128 lanes, a sequence no block divides. The layer then
    runs the plain form, counted, and gives what the plain form gives."""
    cfg = dataclasses.replace(
        MELLUM, **{"float32": {"dtype": jnp.float32},
                   "head_of_64": {"head_dim": 64},
                   "odd_length": {}}[refused])
    t = 200 if refused == "odd_length" else 256
    q, k, norms, _ = _operands(cfg, "full", cfg.dtype, t)
    v = k.reshape(B, t, cfg.n_kv_heads, cfg.head_dim)
    assert not ql.supported(q.shape, k.shape, q.dtype, cfg.head_dim,
                            cfg.head_dim)
    positions = POSITIONS[:, :t]
    obs.set_enabled(True)
    before = _lowerings()
    got = windowed.attend(cfg, "full", q, k, v, norms, positions)
    assert [a - b for a, b in zip(_lowerings(), before)] == [0, 1, 0, 1]
    want = _plain_attention(cfg, "full", q, k, v, norms, positions)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _gap(got, want) <= 1e-6


def test_a_program_lowered_for_the_cpu_holds_the_plain_form():
    """Operands the kernels take, lowered for a platform that has none: the
    plain branch in the forward pass and its own VJP in the backward pass,
    counted once, with the plain form's values and gradients (q's, k's,
    v's and the two norms')."""
    q, k, norms, _ = _operands(MELLUM, "window", t=256)
    v = k.reshape(B, 256, MELLUM.n_kv_heads, MELLUM.head_dim)
    positions = POSITIONS[:, :256]

    def weighed(form):
        def loss(q, k, v, norms):
            out = form(MELLUM, "window", q, k, v, norms, positions)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                          has_aux=True))

    obs.set_enabled(True)
    before = _lowerings()
    (_, got), grads = weighed(windowed.attend)(q, k, v, norms)
    assert [a - b for a, b in zip(_lowerings(), before)] == [0, 1, 0, 1]
    (_, want), want_grads = weighed(_plain_attention)(q, k, v, norms)
    # q and k turned as one array, compiled: XLA fuses the float32 rotation
    # another way and a bf16 result rounds to its neighbour here and there
    assert _gap(got, want) <= 2 ** -7
    for mine, plain in zip(jax.tree_util.tree_leaves(grads),
                           jax.tree_util.tree_leaves(want_grads)):
        assert mine.shape == plain.shape and mine.dtype == plain.dtype
        assert _gap(mine, plain) <= 0.02


# -- the models this file must not reach --------------------------------------

def _todays_wrapper(q, k, v, *, causal=True, block_q=None, block_k=None,
                    interpret=False, window=None):
    """``flash_attention`` as it stood before the head-major entry: kept
    here, so that a change to what token-major callers trace to shows."""
    b, t, hq, _ = q.shape
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"a window of {window} needs causal attention and "
                         f"at least one position")
    blocks = fa.default_blocks(t, window, block_q, block_k)
    if any(t % block for pair in blocks for block in pair):
        raise ValueError(f"seq {t} must divide blocks {blocks}")
    with jax.named_scope("attn.layout"):                    # [B, H, T, D]
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = fa._attend(q, k, v, causal, blocks, interpret, window)
    with jax.named_scope("attn.layout"):
        return out.transpose(0, 2, 1, 3).reshape(b, t, hq * v.shape[3])


@pytest.mark.parametrize("window", [None, 512], ids=["causal", "window"])
def test_token_major_callers_trace_to_the_jaxpr_they_did(window):
    """Forward and backward: the Mistral, Ouro, hybrid and kanana steps call
    ``flash_attention`` with token-major operands, and what that traces to
    is what it traced to."""
    q = jax.ShapeDtypeStruct((1, 1024, 8, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16)

    def traced(attend):
        def loss(q, k, v):
            return attend(q, k, v, window=window).astype(jnp.float32).sum()
        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            q, kv, kv))

    mine = traced(fa.flash_attention)
    assert mine == traced(jax.jit(_todays_wrapper, static_argnames=(
        "causal", "block_q", "block_k", "interpret", "window"))).replace(
            "_todays_wrapper", "flash_attention")
    assert mine.count("transpose") > 0 and "pallas_call" in mine


@pytest.mark.parametrize("module", [llama, looped, hybrid, deepseek],
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_the_other_models_cannot_reach_the_new_kernels(module):
    """None of them imports ``ops/qk_layout.py`` or the head-major entry:
    their steps trace what they traced."""
    source = inspect.getsource(module)
    assert "qk_layout" not in source and "head_major" not in source
    assert not any(getattr(module, name, None) is ql for name in dir(module))
