"""The gated delta rule (ops/gated_delta.py): the chunked form in plain
``jax.numpy`` and the same mathematics through the interpreted Pallas kernels
against the published per-token recurrence — output and every gradient,
float32 tight and bf16 loose, with bf16 failing the float32 tolerance —, its
invariance to the chunk size, strong and no decay over several chunks, and
which form a program lowered for TPU holds. That Mosaic and XLA:TPU take the
kernels at the benchmark cell's size is compiled in tests/test_ops.py, beside
the other kernels (one file loads libtpu)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from brpc_tpu import obs
from brpc_tpu.ops import gated_delta
from brpc_tpu.ops.gated_delta import gated_delta_rule


def recurrence(q, k, v, g, beta):
    """S <- exp(g_t) S; delta = beta_t (v_t - S^T k_t); S <- S + k_t
    delta^T; o_t = S^T q_t, a token at a time, float32."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))

    def step(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[..., None, None] * s
        delta = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * delta[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, hv, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def _inputs(seed=0, b=2, t=256, hk=2, hv=4, dk=32, dv=32, decay="mixed",
            dtype=jnp.float32):
    """q, k unit vectors a head (q scaled by dk^-1/2), as the model hands
    them over; ``decay``: the heads' rates up to 0.2 (``weak``), up to 16 a
    token (``strong``: the state is forgotten inside a chunk), a head of
    each (``mixed``), or none at all (g = 0)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)  # noqa
    q = unit(jax.random.normal(ks[0], (b, t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    top = {"weak": 0.2, "strong": 16.0, "mixed": 16.0, "none": 0.0}[decay]
    rate = jax.random.uniform(ks[3], (hv,), minval=0.0, maxval=top)
    if decay == "mixed":
        rate = rate.at[::2].set(0.05)
    g = -rate * jax.nn.softplus(jax.random.normal(ks[4], (b, t, hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, t, hv)))
    w = jax.random.normal(ks[6], (b, t, hv, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), w


def _value_and_grads(rule, args, w):
    def loss(*a):
        o = rule(*a)
        return jnp.sum(o.astype(jnp.float32) * w), o
    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(*args)
    return (o, *grads)


def _worst(got, want):
    """The widest gap of (o, dq, dk, dv, dg, dbeta) against each array's own
    largest entry."""
    return max(float(jnp.max(jnp.abs(g.astype(jnp.float32) - r))
                     / jnp.max(jnp.abs(r))) for g, r in zip(got, want))


_F32_TOL = 2e-5
_BF16_TOL = 4e-2
FORMS = [pytest.param(None, id="plain"), pytest.param(True, id="interpreted")]


@pytest.mark.parametrize("interpret", FORMS)
@pytest.mark.parametrize("decay", ["weak", "mixed", "strong", "none"])
def test_chunked_form_matches_the_recurrence_in_float32(decay, interpret):
    """Four chunks of 64, two value heads a key head: output and all five
    gradients, whatever the decay (a head that forgets inside a chunk
    underflows its exponents to 0, never overflows)."""
    args, w = _inputs(decay=decay)
    want = _value_and_grads(recurrence, args, w)
    got = _value_and_grads(
        lambda *a: gated_delta_rule(*a, interpret=interpret), args, w)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got)
    assert _worst(got, want) <= _F32_TOL


# (Hk, Hv): a grid step of the sequential kernels walks G = Hv / Hk heads
GROUPS = [pytest.param(2, 2, id="one_a_key_head"),
          pytest.param(2, 4, id="two_a_key_head"),
          pytest.param(1, 4, id="four_a_key_head")]


@pytest.mark.parametrize("hk,hv", GROUPS)
def test_interpreted_kernels_walk_a_key_heads_value_heads(hk, hv):
    """The kernels through the interpreter at groups of 1, 2 and 4 value
    heads a key head: output and all five gradients against the
    recurrence at the float32 tolerance, and against the plain form, which
    does the same float32 arithmetic a value head at a time, tighter."""
    args, w = _inputs(hk=hk, hv=hv)
    want = _value_and_grads(recurrence, args, w)
    plain = _value_and_grads(gated_delta_rule, args, w)
    got = _value_and_grads(
        lambda *a: gated_delta_rule(*a, interpret=True), args, w)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got)
    assert _worst(got, want) <= _F32_TOL
    assert _worst(got, plain) <= 1e-6


def _head_major(dtype, hk, hv):
    """The backward kernel's operands at dk = dv = 128: head-major q, k, v,
    gamma and beta as rows, T and the entry states by the plain form, and a
    cotangent."""
    (q, k, v, g, beta), _ = _inputs(seed=6, b=1, t=256, hk=hk, hv=hv,
                                    dk=128, dv=128, dtype=dtype)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    g, beta = (x.transpose(0, 2, 1) for x in (g, beta))
    gamma = jnp.cumsum(gated_delta._rows(g, 64), axis=-1)
    b_rows = gated_delta._rows(beta, 64)
    t_all = gated_delta._prep_plain(k, gamma, b_rows, chunk=64)
    _, states = gated_delta._fwd_plain(q, k, v, gamma, b_rows, t_all,
                                       chunk=64)
    do = jax.random.normal(jax.random.PRNGKey(9), v.shape, dtype)
    return q, k, v, gamma, b_rows, do, states, t_all


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2.0 ** -8)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("hk,hv", GROUPS)
def test_backward_kernel_sums_dq_and_dk_over_the_group(hk, hv, dtype, tol):
    """dq and dk leave the backward kernel as the key heads' [B, Hk, T,
    dk] in q's and k's dtype — each value head's float32 terms summed in
    the kernel and rounded once — and are what the plain form's float32
    group sum gives; dv stays a value head's."""
    operands = _head_major(dtype, hk, hv)
    q, k, v = operands[:3]
    got = gated_delta._bwd_kernels(*operands, chunk=64, interpret=True)
    want = gated_delta._bwd_plain(*operands, chunk=64)
    assert (got[0].shape, got[1].shape, got[2].shape) == (
        q.shape, k.shape, v.shape)
    assert got[0].dtype == got[1].dtype == got[2].dtype == dtype
    for a, b in zip(got[:2], want[:2]):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(a - b))) <= tol * float(
            jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("interpret", FORMS)
def test_bf16_is_loose_and_fails_the_float32_tolerance(interpret):
    """bf16 operands, float32 state and accumulators: inside the loose
    tolerance, outside the tight one (so the tight one does see a
    precision)."""
    args, w = _inputs(seed=1)
    want = _value_and_grads(recurrence, args, w)
    bf16, _ = _inputs(seed=1, dtype=jnp.bfloat16)
    got = _value_and_grads(
        lambda *a: gated_delta_rule(*a, interpret=interpret), bf16, w)
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    assert got[4].dtype == got[5].dtype == jnp.float32
    worst = _worst(got, want)
    assert _F32_TOL < worst <= _BF16_TOL


@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_result_does_not_depend_on_the_chunk(chunk):
    """The chunk is how the sequence is walked, not what is computed: a
    single diagonal block (16), two blocks (32: the off-diagonal inverse is
    I - N) and eight (128) against the kernels' 64."""
    args, w = _inputs(seed=2, decay="weak")
    want = _value_and_grads(gated_delta_rule, args, w)
    got = _value_and_grads(
        lambda *a: gated_delta_rule(*a, chunk=chunk), args, w)
    assert _worst(got, want) <= _F32_TOL


def test_no_decay_and_full_strength_is_the_plain_delta_rule():
    """g = 0 and beta = 1: after a token's write the state returns that
    token's value for its key (S^T k_t = v_t), the defining property of the
    delta rule, through eight chunks."""
    (q, k, v, g, beta), _ = _inputs(seed=3, b=1, t=512, hk=1, hv=1,
                                    decay="none")
    o = gated_delta_rule(k, k, v, g, jnp.ones_like(beta))
    np.testing.assert_allclose(np.asarray(o), np.asarray(v), atol=2e-4)


def test_sequence_must_be_whole_chunks():
    (q, k, v, g, beta), _ = _inputs(t=96)
    with pytest.raises(ValueError, match="whole chunks"):
        gated_delta_rule(q, k, v, g, beta)
    with pytest.raises(ValueError, match="multiple"):
        gated_delta_rule(q, k, v, g, beta, chunk=24)


def test_kernels_take_bf16_whole_lanes_and_whole_groups():
    take = gated_delta.kernels_take
    bf16 = jnp.bfloat16
    assert take((1, 8192, 16, 128), (1, 8192, 32, 128), bf16)
    assert not take((1, 8192, 16, 128), (1, 8192, 32, 128), jnp.float32)
    assert not take((1, 8192, 16, 64), (1, 8192, 32, 128), bf16)
    assert not take((1, 8192, 16, 128), (1, 8192, 24, 128), bf16)
    assert not take((1, 8200, 16, 128), (1, 8200, 32, 128), bf16)


def _lowered(dtype, platform, dk=128, hk=1, hv=2):
    args = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in _inputs(
        b=1, t=128, hk=hk, hv=hv, dk=dk, dv=128, dtype=dtype)[0])
    grad = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)))
    return grad.trace(*args).lower(lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("dtype,platform,dk,kernels", [
    (jnp.bfloat16, "tpu", 128, True), (jnp.bfloat16, "cpu", 128, False),
    (jnp.float32, "tpu", 128, False), (jnp.bfloat16, "tpu", 64, False)])
def test_program_holds_the_kernels_only_on_tpu_at_shapes_they_take(
        dtype, platform, dk, kernels):
    """One traced function for every platform; the choice is settled when
    the program is lowered, and ``gdn_lowerings`` counts the programs that
    kept the kernels (once: the backward pass counts nothing), and
    ``gdn_value_heads_a_step`` the value heads a grid step of theirs walks
    (a key head's two)."""
    obs.set_enabled(True)
    before = obs.counter("gdn_lowerings").get_value()
    obs.maxer("gdn_value_heads_a_step").reset()
    text = _lowered(dtype, platform, dk)
    assert obs.counter("gdn_lowerings").get_value() - before == int(kernels)
    assert ("tpu_custom_call" in text) is kernels
    if kernels:
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 3
        assert sorted(set(re.findall(r"gdn_chunk_\w+", text))) == [
            "gdn_chunk_bwd", "gdn_chunk_fwd", "gdn_chunk_prep"]
        assert obs.maxer("gdn_value_heads_a_step").get_value() == 2


@pytest.mark.parametrize("hk,hv", GROUPS)
def test_a_grid_step_walks_the_value_heads_of_one_key_head(hk, hv):
    """Lowered for TPU: still the three kernels; a grid step of the
    sequential ones walks G = Hv / Hk value heads (one where Hk = Hv, the
    grid and body of one head a step), and the backward kernel gives dq and
    dk as the key heads' [B, Hk, T, dk], dv as the value heads'."""
    obs.set_enabled(True)
    obs.maxer("gdn_value_heads_a_step").reset()
    text = _lowered(jnp.bfloat16, "tpu", hk=hk, hv=hv)
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 3
    assert obs.maxer("gdn_value_heads_a_step").get_value() == hv // hk
    (results,) = re.findall(
        r'kernel_name = "gdn_chunk_bwd".*?-> \(([^)]*)\)', text)
    keys, values = (f"tensor<1x{h}x128x128xbf16>" for h in (hk, hv))
    assert results.split(", ")[:3] == [keys, keys, values]


@pytest.mark.parametrize("t", [64, 128], ids=["one_chunk", "a_pair"])
def test_results_the_backward_pass_keeps_have_names(t):
    """The forward rule names the output, the chunks' entry states and the
    chunks' T, so that a checkpoint policy can save them."""
    args, _ = _inputs(b=1, t=t, hk=1, hv=1)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a))))(*args)
    names = set()

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "name":
                names.add(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert {*gated_delta.RESIDUAL_NAMES, gated_delta.INVERSE_NAME} <= names


# -- T = (I + A)^-1, made once a chunk by ``_chunk_prep`` ---------------------

def _hard_case(w, c=64, dk=32):
    """w chunks' keys in which positions 16..31 of each chunk are one key
    (a diagonal block of A whose entries are all beta: the Neumann products'
    terms grow to thousands before they cancel), beta near 1, no decay; and
    (I + A) of each chunk as float64, built entry by entry."""
    k = np.array(jax.random.normal(jax.random.PRNGKey(4), (w * c, dk)))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    for j in range(w):
        k[j * c + 16:j * c + 32] = k[j * c + 16 + j]
    beta = np.full((w * c,), 0.999, np.float32)
    beta[::7] = 0.97
    k32 = k.astype(np.float32)
    i_plus_a = [np.eye(c) + np.tril(
        beta[j * c:(j + 1) * c, None].astype(np.float64)
        * (k32[j * c:(j + 1) * c].astype(np.float64)
           @ k32[j * c:(j + 1) * c].astype(np.float64).T), -1)
        for j in range(w)]
    return jnp.asarray(k32), jnp.asarray(beta)[None], i_plus_a


@pytest.mark.parametrize("w", [1, 2], ids=["one_chunk", "two_side_by_side"])
def test_inverse_of_a_block_of_like_keys_is_a_triangular_solve(w):
    """The planted hard case: T (I + A) is the identity to float32 accuracy
    and T is what a triangular solve gives, for one chunk and for two side
    by side in the lanes (the second's like keys are another key). The test
    a cheaper inverse has to pass before it is taken (PERF.md section 7)."""
    c = 64
    k, b_row, i_plus_a = _hard_case(w)
    t = np.asarray(gated_delta._chunk_prep(
        gated_delta._nt(k, k), jnp.zeros_like(b_row), b_row, c, jnp.float32),
        np.float64)
    assert t.shape == (c, w * c)
    for j, m in enumerate(i_plus_a):
        t_j = t[:, j * c:(j + 1) * c]
        # not a gentle case: the series' eighth term alone is in the
        # thousands, and float32 carries the sum to that term's last bit (a
        # product in one bf16 pass would be off by tens)
        term = np.max(np.abs(np.linalg.matrix_power(m - np.eye(c), 8)))
        assert term > 1e3
        solved = np.linalg.solve(m, np.eye(c))        # m is unit lower
        assert np.max(np.abs(t_j @ m - np.eye(c))) <= term * 2.0 ** -23
        assert np.max(np.abs(t_j - solved)) <= term * 2.0 ** -23
        assert np.all(np.triu(t_j, 1) == 0.0)


def _prep_operands(dtype, t=256, hk=2, hv=4, dk=32):
    (q, k, v, g, beta), _ = _inputs(seed=5, b=1, t=t, hk=hk, hv=hv, dk=dk,
                                    dtype=dtype)
    rows = lambda x: gated_delta._rows(x.transpose(0, 2, 1), 64)  # noqa: E731
    return (k.transpose(0, 2, 1, 3), jnp.cumsum(rows(g), axis=-1),
            rows(beta))


@pytest.mark.parametrize("t,lanes", [(256, 128), (192, 64)],
                         ids=["pairs", "odd_count"])
def test_interpreted_kernel_and_plain_form_make_the_same_inverse(t, lanes):
    """Float32 operands, so nothing is cast: ``gdn_chunk_prep`` through the
    interpreter against the plain form, stored [B, Hv, T/(w C), C, w C] —
    two chunks side by side where the chunks come in pairs — and each
    chunk's T what one chunk alone gives."""
    k, gamma, b_rows = _prep_operands(jnp.float32, t=t)
    plain = gated_delta._prep_plain(k, gamma, b_rows, chunk=64)
    kernel = gated_delta._prep_kernels(k, gamma, b_rows, chunk=64,
                                       interpret=True)
    assert plain.shape == kernel.shape == (1, 4, t // lanes, 64, lanes)
    assert plain.dtype == kernel.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(plain)))
    assert float(jnp.max(jnp.abs(kernel - plain))) <= 1e-6 * scale
    each = gated_delta._each_chunks(plain, 64)
    assert each.shape == (1, 4, t // 64, 64, 64)
    k_heads = jnp.repeat(k, 2, axis=1)
    for h, ci in ((0, 0), (3, t // 64 - 1)):
        k_c = k_heads[0, h, ci * 64:(ci + 1) * 64]
        alone = gated_delta._chunk_prep(
            gated_delta._nt(k_c, k_c), gamma[0, h, ci], b_rows[0, h, ci], 64,
            jnp.float32)
        assert float(jnp.max(jnp.abs(each[0, h, ci] - alone))) <= 1e-6 * scale


def test_bf16_inverse_is_the_float32_one_rounded_once():
    """bf16 keys: k k^T, A and the inverse stay float32 and T is cast at
    the end, alike in the interpreted kernel and the plain form."""
    k, gamma, b_rows = _prep_operands(jnp.bfloat16, dk=128)
    plain = gated_delta._prep_plain(k, gamma, b_rows, chunk=64)
    kernel = gated_delta._prep_kernels(k, gamma, b_rows, chunk=64,
                                       interpret=True)
    assert plain.dtype == kernel.dtype == jnp.bfloat16
    exact = gated_delta._prep_plain(k.astype(jnp.float32), gamma, b_rows,
                                    chunk=64)
    scale = float(jnp.max(jnp.abs(exact)))
    for got in (plain, kernel):
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - exact))) \
            <= 2 ** -8 * scale
