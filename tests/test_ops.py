"""Pallas kernel tests. Numerics run in interpret mode on the CPU; that the
same kernels reach the TPU compiler is checked without a chip, by lowering
for the TPU platform and by compiling ahead of time for a v5e topology
(libtpu compiles without devices). The compiled kernels' numerics on the
chip are chip_smoke.py's ``kernel`` phase. Last, the choice
``flash_attention.attention`` makes between the kernels and the dense
form."""

import collections
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from brpc_tpu import obs
from brpc_tpu.models import llama
from brpc_tpu.ops import flash_attention
from brpc_tpu.ops.flash_attention import (attention, choose_block,
                                          dense_attention)


def _inputs(key, b=2, t=128, hq=4, hkv=2, d=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, hq, d), dtype)
    k = jax.random.normal(kk, (b, t, hkv, d), dtype)
    v = jax.random.normal(kv, (b, t, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = _inputs(jax.random.PRNGKey(0))
    want = attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_uneven_blocks():
    q, k, v = _inputs(jax.random.PRNGKey(1), t=64)
    want = attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, block_q=16, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# The shapes of the numeric tests above, then Llama-3-8B's head geometry.
_TPU_SHAPES = [
    dict(t=128, block_q=32, block_k=32),
    dict(t=64, block_q=16, block_k=64),
    dict(t=64, block_q=32, block_k=32, dtype=jnp.bfloat16),
    dict(b=1, t=2048, hq=32, hkv=8, d=128, dtype=jnp.bfloat16),
]


def _abstract_inputs(sharding=None, b=2, t=128, hq=4, hkv=2, d=32,
                     dtype=jnp.float32, **blocks):
    q = jax.ShapeDtypeStruct((b, t, hq, d), dtype, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, t, hkv, d), dtype, sharding=sharding)
    return (q, kv, kv), blocks


@pytest.mark.parametrize("shape", _TPU_SHAPES)
def test_flash_lowers_for_tpu(shape):
    """interpret=False must get past Pallas' TPU block-shape rules (the
    last two block dimensions divisible by 8 and 128, or whole) and become
    a Mosaic custom call."""
    args, blocks = _abstract_inputs(**shape)
    lowered = flash_attention.trace(*args, interpret=False, **blocks).lower(
        lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies

    # Compiling ahead of time opens no device, so this process need not be
    # libtpu's only one on the host: left alone, libtpu takes
    # /tmp/libtpu_lockfile while it loads and a concurrent loader (another
    # test run) aborts on it.
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    return topo.devices[0]


@pytest.mark.parametrize("shape", _TPU_SHAPES)
def test_flash_compiles_for_v5e(v5e_device, shape):
    """XLA:TPU and Mosaic accept the kernel for the chip the fabric runs
    on."""
    args, blocks = _abstract_inputs(
        jax.sharding.SingleDeviceSharding(v5e_device), **shape)
    compiled = flash_attention.trace(*args, interpret=False,
                                     **blocks).lower().compile()
    assert "custom-call" in compiled.as_text()


def test_flash_bf16():
    q, k, v = _inputs(jax.random.PRNGKey(2), t=64, dtype=jnp.bfloat16)
    want = attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2)


# -- the backward pass ------------------------------------------------------

def _weighted(attn, w):
    """A scalar of attention whose cotangent is not constant."""
    return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)


@pytest.mark.parametrize("blocks", [(32, 32), (16, 64)],
                         ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("group", [4, 1])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
def test_flash_grad_matches_dense(dtype, tol, group, causal, blocks):
    """dQ, dK, dV of the kernels against autodiff of the dense form. The
    bf16 tolerance is relative to each gradient's largest element: both
    sides round p and dS to 8 mantissa bits, at different places."""
    q, k, v = _inputs(jax.random.PRNGKey(3), b=1, hq=4, hkv=4 // group,
                      dtype=dtype)
    w = jax.random.normal(jax.random.PRNGKey(4), (1, 128, 4 * 32))
    got = jax.grad(_weighted(functools.partial(
        flash_attention, causal=causal, block_q=blocks[0], block_k=blocks[1],
        interpret=True), w), (0, 1, 2))(q, k, v)
    want = jax.grad(_weighted(functools.partial(
        dense_attention, causal=causal), w), (0, 1, 2))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        assert g.dtype == r.dtype == dtype and g.shape == r.shape, name
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.max(np.abs(g - r)) <= tol * np.max(np.abs(r)), name


# The attention kernels' custom calls in a compiled program's text, and what
# one forward and one backward pass hold: dQ, dK and dV come from one kernel.
_ATTN_CALLS = re.compile(r"%(attn_flash_\w+?)(?:\.\d+)? = [^\n]*custom-call\(")
_ONE_OF_EACH = ["attn_flash_bwd", "attn_flash_fwd"]


# For the compiled backward kernel, whose query tile runs along the lanes of
# dQ's accumulator and so is a multiple of 128: even tiles, a key tile of two
# query tiles, bf16, then Llama-3-8B's head geometry.
_TPU_BACKWARD_SHAPES = [
    dict(t=256, block_q=128, block_k=128),
    dict(t=256, block_q=128, block_k=256),
    dict(t=256, block_q=128, block_k=128, dtype=jnp.bfloat16),
    _TPU_SHAPES[-1],
]


def _grad_of_sum(**blocks):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=False, **blocks)
                       .astype(jnp.float32))
    return jax.jit(jax.grad(loss, (0, 1, 2)))


@pytest.mark.parametrize("shape", _TPU_BACKWARD_SHAPES)
def test_flash_backward_lowers_for_tpu(shape):
    """The forward kernel and the one backward kernel: two Mosaic custom
    calls."""
    args, blocks = _abstract_inputs(**shape)
    lowered = _grad_of_sum(**blocks).trace(*args).lower(
        lowering_platforms=("tpu",))
    assert lowered.as_text().count("stablehlo.custom_call @tpu_custom_call") \
        == 2


@pytest.mark.parametrize("shape", _TPU_BACKWARD_SHAPES)
def test_flash_backward_compiles_for_v5e(v5e_device, shape):
    args, blocks = _abstract_inputs(
        jax.sharding.SingleDeviceSharding(v5e_device), **shape)
    compiled = _grad_of_sum(**blocks).trace(*args).lower().compile()
    assert sorted(_ATTN_CALLS.findall(compiled.as_text())) == _ONE_OF_EACH


# -- the choice flash_attention.attention makes -----------------------------

# Kernel-eligible and small: 4 heads of 128 over 2 KV heads, bf16, and a
# sequence (three tiles of 128) that is no weight's dimension, so that an
# array with two trailing dimensions of T can only be a score matrix.
_T = 384
_ELIGIBLE = llama.LlamaConfig(vocab_size=1024, hidden=512, n_layers=2,
                              n_heads=4, n_kv_heads=2, head_dim=128,
                              intermediate=1024)


def _abstract_step(cfg, sharding=None, model=llama):
    optimizer = optax.adamw(1e-4)
    params = jax.eval_shape(lambda k: model.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    state = (params, jax.eval_shape(optimizer.init, params),
             jax.ShapeDtypeStruct((1, _T), jnp.int32))
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        state)
    return jax.jit(model.make_train_step(cfg, optimizer)).trace(*state)


@pytest.fixture
def lowerings():
    """Reads (kernel, dense) lowerings counted since the test began."""
    obs.set_enabled(True)       # other modules' tests leave it off
    names = ("attn_kernel_lowerings", "attn_dense_lowerings")
    before = [obs.counter(n).get_value() for n in names]
    return lambda: tuple(obs.counter(n).get_value() - b
                         for n, b in zip(names, before))


def test_train_step_takes_the_kernel_on_tpu(v5e_device, lowerings):
    text = _abstract_step(
        _ELIGIBLE, jax.sharding.SingleDeviceSharding(v5e_device)
    ).lower().compile().as_text()
    # one call of each kernel and no other: the names the forward kernel
    # gives its results (RESIDUAL_NAMES) are identities where no checkpoint
    # saves by name
    assert sorted(_ATTN_CALLS.findall(text)) == _ONE_OF_EACH
    assert text.count("tpu_custom_call") == 2
    assert re.findall(rf"\w+\[[\d,]*{_T},{_T}\]", text) == []
    assert lowerings() == (1, 0)


def test_the_step_compiled_for_v5e_names_its_scopes(v5e_device):
    """What the benchmark's table of device time reads: the instructions of
    the compiled step carry the program's scopes in their ``op_name``, the
    layout work around the kernels (``attn.layout``) among them."""
    text = _abstract_step(
        _ELIGIBLE, jax.sharding.SingleDeviceSharding(v5e_device)
    ).lower().compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("embed", "llama.qkv", "llama.attn_out", "llama.mlp",
                  "llama.head_loss", "attn.layout", "attn.flash_fwd",
                  "attn.flash_bwd", "opt.update"):
        assert any(re.search(rf"[/(]{re.escape(scope)}[/)]", name)
                   for name in op_names), scope
    assert not any("attn.dense" in name for name in op_names)


def test_train_step_stays_dense_on_cpu(lowerings):
    text = _abstract_step(_ELIGIBLE).lower(
        lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in text
    assert re.search(rf"tensor<[\dx]*{_T}x{_T}xf32>", text)
    assert lowerings() == (0, 1)


@pytest.mark.parametrize("change", [dict(dtype=jnp.float32),
                                    dict(head_dim=32)],
                         ids=["float32", "head_dim32"])
def test_ineligible_operands_stay_dense_on_tpu(change, lowerings):
    text = _abstract_step(dataclasses.replace(_ELIGIBLE, **change)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text
    assert lowerings() == (0, 1)


def test_one_step_through_the_kernel_matches_dense():
    """Loss and every gradient leaf's norm of the small model, the kernels
    (interpreted) against the dense form, to what bf16 allows: the two round
    the scores and the probabilities at different places."""
    params = llama.init_params(jax.random.PRNGKey(5), _ELIGIBLE)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, _T), 0,
                                _ELIGIBLE.vocab_size)
    grad = jax.jit(jax.value_and_grad(llama.loss_fn), static_argnums=(2, 3))
    loss, grads = grad(params, tokens, _ELIGIBLE,
                       functools.partial(flash_attention, interpret=True))
    want_loss, want = grad(params, tokens, _ELIGIBLE, dense_attention)
    assert abs(float(loss) - float(want_loss)) <= 2e-3 * float(want_loss)
    norms = jax.tree_util.tree_map(
        lambda g, w: (float(jnp.linalg.norm(g)), float(jnp.linalg.norm(w))),
        grads, want)
    for path, (g, w) in jax.tree_util.tree_leaves_with_path(
            norms, is_leaf=lambda x: isinstance(x, tuple)):
        assert abs(g - w) <= 2e-2 * w, (jax.tree_util.keystr(path), g, w)


# -- two widths: latent attention's q/k of 192 and v of 128 -----------------

def _latent_inputs(key, t=128, h=2, d_qk=192, d_v=128, dtype=jnp.float32,
                   sharding=None, abstract=False):
    shapes = ((1, t, h, d_qk), (1, t, h, d_qk), (1, t, h, d_v))
    if abstract:
        return tuple(jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
                     for s in shapes)
    return tuple(jax.random.normal(k, s, dtype)
                 for k, s in zip(jax.random.split(key, 3), shapes))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("widths", [(192, 128), (24, 16)],
                         ids=lambda w: "x".join(map(str, w)))
def test_flash_two_widths_match_dense(widths, dtype, tol):
    """Output, dQ, dK and dV at d_qk != d_v against the dense form, which
    scales by the q/k width and returns heads of the v width."""
    q, k, v = _latent_inputs(jax.random.PRNGKey(7), d_qk=widths[0],
                             d_v=widths[1], dtype=dtype)
    kernel = functools.partial(flash_attention, block_q=64, block_k=32,
                               interpret=True)
    got, want = kernel(q, k, v), dense_attention(q, k, v)
    assert got.shape == want.shape == (1, 128, 2 * widths[1])
    w = jax.random.normal(jax.random.PRNGKey(8), want.shape)
    grads = jax.grad(_weighted(kernel, w), (0, 1, 2))(q, k, v)
    wants = jax.grad(_weighted(dense_attention, w), (0, 1, 2))(q, k, v)
    for name, g, r in zip(("o", "dq", "dk", "dv"), (got, *grads),
                          (want, *wants)):
        assert g.dtype == r.dtype == dtype and g.shape == r.shape, name
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.max(np.abs(g - r)) <= tol * np.max(np.abs(r)), name


def test_supported_states_the_rule_for_two_widths():
    from brpc_tpu.ops.flash_attention import supported
    bf16 = jnp.bfloat16
    assert supported((1, 8192, 32, 192), (1, 8192, 32, 192), bf16,
                     (1, 8192, 32, 128))
    assert supported((1, 2048, 32, 128), (1, 2048, 8, 128), bf16)
    # the backward kernel holds a query group's Q and dO, dQ transposed in
    # float32 and dQ's output block: 3,328 B a token at 192 / 128, 27.3 MB at
    # 8,192 tokens, past half the kernels' VMEM after 19 tiles of 512; a
    # group of 4 at 128 / 128 (8,192 B a token) fits 4,096 tokens
    assert supported((1, 9728, 32, 192), (1, 9728, 32, 192), bf16,
                     (1, 9728, 32, 128))
    assert not supported((1, 10240, 32, 192), (1, 10240, 32, 192), bf16,
                         (1, 10240, 32, 128))
    assert supported((1, 4096, 32, 128), (1, 4096, 8, 128), bf16)
    # past that the group no longer stays whole in VMEM and the backward
    # kernel takes a query head at a time (PR 34): what bounds the sequence
    # is one head's residents, 4,096 B a token at 256 / 256
    from brpc_tpu.ops.flash_attention import heads_together
    assert heads_together(4, 4096, 128, 128, bf16)
    assert not heads_together(4, 4608, 128, 128, bf16)
    assert supported((1, 4608, 32, 128), (1, 4608, 8, 128), bf16)
    assert not heads_together(8, 8192, 256, 256, bf16)
    assert supported((1, 8192, 16, 256), (1, 8192, 2, 256), bf16)
    assert not supported((1, 8320, 16, 256), (1, 8320, 2, 256), bf16)
    # v of 64, half a lane (granite-4.0-h-micro's heads, PR 47): a block as
    # wide as its array; narrower than that, no
    assert supported((1, 8192, 32, 192), (1, 8192, 32, 192), bf16,
                     (1, 8192, 32, 64))
    assert supported((1, 4096, 32, 64), (1, 4096, 8, 64), bf16)
    assert not supported((1, 8192, 32, 192), (1, 8192, 32, 192), bf16,
                         (1, 8192, 32, 32))         # v narrower still
    assert not supported((1, 8192, 32, 96), (1, 8192, 32, 96), bf16,
                         (1, 8192, 32, 128))
    assert not supported((1, 32768, 32, 192), (1, 32768, 32, 192), bf16,
                         (1, 32768, 32, 128))       # K, V past the VMEM room


# The attention of the benchmark's train cells: Mistral-7B's grouped heads
# at 2,048 tokens, kanana-2's latent heads at 8,192, Ouro's 16 heads at
# 4,096, Qwen3-Next's 8 query heads a KV head of 256 at 8,192 (the one whose
# group does not stay whole in VMEM). (T, query heads, KV heads, q/k width,
# v width, the backward kernel's tile.)
_CELLS = {"mistral7b": (2048, 32, 8, 128, 128, 512),
          "kanana2": (8192, 32, 32, 192, 128, 1024),
          "ouro": (4096, 16, 16, 128, 128, 512),
          "qwen3next": (8192, 16, 2, 256, 256, 1024)}


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_flash_backward_is_one_kernel_at_the_cells_geometries(v5e_device,
                                                              cell):
    """The backward pass alone, compiled for a v5e: one Mosaic call, named
    ``attn_flash_bwd``, returns dQ, dK and dV, and what it holds in VMEM
    (Q, dO, float32 dQ and dQ's block of the query group, with the tiles) is
    what ``supported`` reckons and inside the kernels' limit."""
    from brpc_tpu.ops.flash_attention import _backward, supported
    from brpc_tpu.ops.lowered import VMEM_LIMIT
    t, hq, hkv, d_qk, d_v, block = _CELLS[cell]
    assert supported((1, t, hq, d_qk), (1, t, hkv, d_qk), jnp.bfloat16,
                     (1, t, hkv, d_v))
    assert choose_block(t, backward=True) == block
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)
    rows = s(1, hq, t // block, 1, block, dtype=jnp.float32)
    text = jax.jit(functools.partial(
        _backward, causal=True, blocks=(block, block), interpret=False)
    ).lower(s(1, hq, t, d_qk), s(1, hkv, t, d_qk), s(1, hkv, t, d_v), None,
            s(1, hq, t, d_v), rows, s(1, hq, t, d_v)).compile().as_text()
    assert _ATTN_CALLS.findall(text) == ["attn_flash_bwd"]
    assert text.count("tpu_custom_call") == 1
    (call,) = (line for line in text.splitlines() if "custom-call(" in line)
    (used,) = re.findall(
        r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call)
    assert int(used) <= VMEM_LIMIT


def test_compiled_backward_kernel_wants_a_query_tile_of_whole_lanes():
    """The interpreter takes any tile (the numeric tests above); lowering
    for the chip says why 32 will not do, before Mosaic would."""
    args, blocks = _abstract_inputs(**_TPU_SHAPES[0])
    with pytest.raises(ValueError, match="multiple of 128"):
        _grad_of_sum(**blocks).trace(*args)


def test_flash_two_widths_compile_for_v5e(v5e_device):
    """Forward and backward at the kanana cell's geometry (8,192 x 32
    heads, 192 / 128): Mosaic takes the 192-wide blocks whole."""
    args = _latent_inputs(None, t=8192, h=32, dtype=jnp.bfloat16,
                          sharding=jax.sharding.SingleDeviceSharding(
                              v5e_device), abstract=True)
    compiled = _grad_of_sum().trace(*args).lower().compile()
    assert sorted(_ATTN_CALLS.findall(compiled.as_text())) == _ONE_OF_EACH


# -- the grouped product over the experts held -------------------------------

from brpc_tpu.models import deepseek  # noqa: E402
from brpc_tpu.ops import grouped_matmul as gm  # noqa: E402


def _grouped_case(seed=0, n=40, k=2, groups=3, h=32, f=16, tile=8):
    """Assignments over 3 held groups and 2 absent ones; group 1 is chosen
    by nobody, group 0 by far the most."""
    rng = np.random.default_rng(seed)
    group_of = np.minimum(rng.integers(0, groups + 2, n * k), groups)
    group_of[group_of == 1] = 0
    lay = gm.group_layout(jnp.asarray(group_of, jnp.int32), groups, tile)
    arrays = [rng.standard_normal(s).astype(np.float32) for s in (
        (n, h), (groups, h, f), (groups, f, h))]
    weights = rng.uniform(0.1, 1, (n, k)).astype(np.float32)
    return group_of.reshape(n, k), lay, (*map(jnp.asarray, arrays),
                                         jnp.asarray(weights))


def _routed(lay, interpret):
    def fn(x, w_in, w_out, weights):
        rows = gm.dispatch(x, lay, interpret)
        hidden = jax.nn.silu(gm.grouped_matmul(
            rows, w_in, lay.tile_group, lay.n_tiles, interpret))
        rows = gm.grouped_matmul(hidden, w_out, lay.tile_group, lay.n_tiles,
                                 interpret)
        return gm.combine(rows, weights, lay, interpret)
    return fn


def _routed_plainly(group_of):
    def fn(x, w_in, w_out, weights):
        out = 0.0
        for g in range(w_in.shape[0]):
            weight = jnp.sum(jnp.where(group_of == g, weights, 0.0), axis=1)
            out = out + weight[:, None] * (jax.nn.silu(x @ w_in[g]) @ w_out[g])
        return out
    return fn


def test_group_layout_places_every_held_assignment_once():
    group_of, lay, _ = _grouped_case()
    flat = group_of.reshape(-1)
    held = flat < 3
    assert np.array_equal(np.asarray(lay.held), held)
    assert np.array_equal(np.asarray(lay.group_sizes),
                          np.bincount(flat[held], minlength=3))
    assert int(lay.group_sizes[1]) == 0
    dest = np.asarray(lay.dest)[held]
    assert len(set(dest)) == held.sum() == np.asarray(lay.row_valid).sum()
    # a row's source is the assignment that was sent there, and its tile
    # belongs to that assignment's group
    assert np.array_equal(np.asarray(lay.row_source)[dest],
                          np.flatnonzero(held))
    assert np.array_equal(np.asarray(lay.tile_group)[dest // 8], flat[held])
    # every group has a tile, an empty one too; the bound has idle tiles
    used = np.asarray(lay.tile_group)[:int(lay.n_tiles[0])]
    assert set(used) == {0, 1, 2} and int(lay.n_tiles[0]) < len(
        np.asarray(lay.tile_group))


def _group_layout_by_lookups(group_of, n_groups, tile):
    """``gm.group_layout`` as it stood before PR 40, the plain reference of
    the one that looks nothing up: an argsort, then every field read by a
    gather of scalars over the assignments or the rows."""
    (a,) = group_of.shape
    m = gm.bound_rows(a, n_groups, tile)
    held = group_of < n_groups
    order = jnp.argsort(group_of, stable=True).astype(jnp.int32)
    one_hot = group_of[:, None] == jnp.arange(n_groups, dtype=jnp.int32)
    rank = jnp.cumsum(one_hot.astype(jnp.int32), axis=0) - 1
    sizes = rank[-1] + 1
    tiles = jnp.maximum(-(-sizes // tile), 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile
    first = jnp.cumsum(sizes) - sizes
    safe = jnp.minimum(group_of, n_groups - 1)
    dest = row_start[safe] + jnp.take_along_axis(
        rank, safe[:, None], axis=1)[:, 0]
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(m // tile, dtype=jnp.int32),
                         side="right"), n_groups - 1).astype(jnp.int32)
    row = jnp.arange(m, dtype=jnp.int32)
    g = tile_group[row // tile]
    in_group = row - row_start[g]
    row_valid = (in_group < sizes[g]) & (row // tile < tile_end[-1])
    row_source = order[jnp.clip(first[g] + in_group, 0, a - 1)]
    return gm.GroupLayout(dest.astype(jnp.int32), held, row_source, row_valid,
                          tile_group, tile_end[-1:].astype(jnp.int32), sizes)


# (k, the router's width, experts held) of the three expert cells
_EXPERT_CELLS = {"qwen3_next": (10, 512, 32), "laguna_xs2": (8, 256, 16),
                 "kanana2": (6, 128, 16)}


def _layout_case(name):
    """(group_of [A], groups, tile) of a seeded case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name in _EXPERT_CELLS:       # the cell's routing at 512 tokens
        k, width, groups = _EXPERT_CELLS[name]
        selected = np.argsort(rng.random((512, width)), axis=1)[:, :k]
        tile = gm.choose_tile(512 * k, groups)
        return np.minimum(selected, groups).reshape(-1), groups, tile
    groups, tile, a = 3, 8, 80
    group_of = {
        "an_empty_held_group": lambda: _grouped_case()[0].reshape(-1),
        "none_held": lambda: np.full(a, groups),
        "all_in_one_group": lambda: np.full(a, 1),
        # group 0 has a whole tile and group 2 two, to the row
        "groups_of_whole_tiles": lambda: rng.permutation(
            np.repeat([0, 1, 2, 3], [8, 5, 16, a - 29])),
        "every_assignment_held": lambda: rng.integers(0, groups, a),
    }[name]()
    return group_of, groups, tile


@pytest.mark.parametrize("case", [
    "an_empty_held_group", "none_held", "all_in_one_group",
    "groups_of_whole_tiles", "every_assignment_held", *_EXPERT_CELLS])
def test_group_layout_is_the_one_made_by_lookups(case):
    """Field for field: ``dest`` where held and ``row_source`` where the row
    holds an assignment (elsewhere any row will do), the rest everywhere;
    and what the new one promises besides: ``dest`` with the spare rows, and
    ``row_source``, are permutations of the bound's rows, each the other's
    inverse over the assignments."""
    group_of, groups, tile = _layout_case(case)
    group_of = jnp.asarray(group_of, jnp.int32)
    got = jax.tree_util.tree_map(np.asarray,
                                 gm.group_layout(group_of, groups, tile))
    want = jax.tree_util.tree_map(
        np.asarray, _group_layout_by_lookups(group_of, groups, tile))
    for field in ("held", "row_valid", "tile_group", "n_tiles",
                  "group_sizes"):
        assert np.array_equal(getattr(got, field), getattr(want, field)), field
        assert getattr(got, field).dtype == getattr(want, field).dtype, field
    assert np.array_equal(got.dest[want.held], want.dest[want.held])
    assert np.array_equal(got.row_source[want.row_valid],
                          want.row_source[want.row_valid])
    assert got.dest.dtype == got.row_source.dtype == np.int32
    a, m = group_of.shape[0], got.row_valid.shape[0]
    assert np.array_equal(np.sort(got.row_source), np.arange(m))
    assert np.array_equal(got.row_source[got.dest], np.arange(a))
    if case == "groups_of_whole_tiles":
        assert list(got.group_sizes) == [tile, 5, 2 * tile]
    # what rides the sort: a value an assignment to the rows and back
    lay = gm.group_layout(group_of, groups, tile)
    values = jnp.asarray(np.random.default_rng(0).standard_normal(a),
                         jnp.float32)
    by_row = gm.rows_of(lay, values)
    assert np.array_equal(np.asarray(by_row)[want.row_valid],
                          np.asarray(values)[want.row_source[want.row_valid]])
    assert np.array_equal(np.asarray(gm.assignments_of(lay, by_row)), values)


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["einsum", "kernels_interpreted"])
def test_grouped_product_matches_a_loop_over_the_experts(interpret):
    """Forward and the gradients of rows, both weight stacks and the router
    weights, with an expert nobody chose (zero gradient, not garbage) and
    idle tiles past the rows present."""
    group_of, lay, args = _grouped_case()
    with jax.default_matmul_precision("highest"):
        fn, plain = _routed(lay, interpret), _routed_plainly(group_of)
        w = jax.random.normal(jax.random.PRNGKey(1), (40, 32))
        loss = lambda f: (lambda *a: jnp.sum(f(*a) * w))  # noqa: E731
        got = (fn(*args), *jax.grad(loss(fn), (0, 1, 2, 3))(*args))
        want = (plain(*args), *jax.grad(loss(plain), (0, 1, 2, 3))(*args))
    for name, g, r in zip(("out", "dx", "dw_in", "dw_out", "dweights"), got,
                          want):
        g, r = np.asarray(g), np.asarray(r)
        assert np.max(np.abs(g - r)) <= 2e-5 * np.max(np.abs(r)), name
    assert not np.any(np.asarray(got[2])[1]) and not np.any(
        np.asarray(got[3])[1])


def _rows_case(name):
    """(lay, k, x [N,H], weights [N,k]) over 3 held groups, tile 8: the
    layout of ``_grouped_case``; the same with token 0 holding nothing; with
    every assignment held (the bound's tiles all but in use); and k = 1."""
    rng = np.random.default_rng(7)
    n, k, groups, h, tile = 40, 1 if name == "k_is_1" else 2, 3, 32, 8
    if name == "idle_tiles":
        group_of, lay, (x, _, _, weights) = _grouped_case()
        return lay, k, x, weights
    if name == "bound_full":
        group_of = rng.integers(0, groups, (n, k))
    else:
        group_of = np.minimum(rng.integers(0, groups + 2, (n, k)), groups)
        group_of[0] = groups
    lay = gm.group_layout(jnp.asarray(group_of.reshape(-1), jnp.int32),
                          groups, tile)
    return (lay, k, jnp.asarray(rng.standard_normal((n, h)), jnp.float32),
            jnp.asarray(rng.uniform(0.1, 1, (n, k)), jnp.float32))


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["gathers", "kernels_interpreted"])
@pytest.mark.parametrize("case", ["idle_tiles", "token_without_a_row",
                                  "bound_full", "k_is_1"])
def test_rows_move_as_the_layout_says(case, interpret):
    """``dispatch``, ``combine`` and their gradients (dx, d_rows, d_weights)
    against the movement written out as matrices, with NaN in every row of
    the idle tiles: nothing reads them."""
    lay, k, x, weights = _rows_case(case)
    (n, h), m = x.shape, lay.row_valid.shape[0]
    in_use = int(lay.n_tiles[0]) * 8
    held = np.asarray(lay.held).reshape(n, k)
    if case == "token_without_a_row":
        assert not held[0].any()
    if case == "bound_full":
        assert held.all() and in_use >= n * k
    else:
        assert in_use < m
    # place[t, j, r]: assignment (t, j) is held and lies in row r
    place = np.zeros((n, k, m), np.float32)
    t, j = np.nonzero(held)
    place[t, j, np.asarray(lay.dest).reshape(n, k)[t, j]] = 1
    place = jnp.asarray(place)
    idle = (jnp.arange(m) >= in_use)[:, None]
    rng = np.random.default_rng(3)
    rows, d_rows, d_more = (jnp.asarray(rng.standard_normal((m, h)),
                                        jnp.float32) for _ in range(3))
    dy = jnp.asarray(rng.standard_normal((n, h)), jnp.float32)
    clean = lambda a: jnp.where(idle, 0, a)       # noqa: E731
    dirty = lambda a: jnp.where(idle, jnp.nan, a)   # noqa: E731
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(lambda x: gm.dispatch(x, lay, interpret), x)
        got = {"rows": clean(out), "dx": pull(dirty(d_rows))[0]}
        # the same rows for two consumers: their cotangents are added
        (out, same), pull = jax.vjp(
            lambda x: gm.dispatch(x, lay, interpret, 2), x)
        assert same is out or np.array_equal(clean(same), clean(out))
        got["dx_of_two"] = pull((dirty(d_rows), dirty(d_more)))[0]
        out, pull = jax.vjp(lambda r, w: gm.combine(r, w, lay, interpret),
                            dirty(rows), weights)
        got["out"] = out
        got["d_rows"], got["d_weights"] = pull(dy)
        got["d_rows"] = clean(got["d_rows"])
        want = {
            "rows": jnp.einsum("tjr,th->rh", place, x),
            "dx": jnp.einsum("tjr,rh->th", place, clean(d_rows)),
            "dx_of_two": jnp.einsum("tjr,rh->th", place,
                                    clean(d_rows + d_more)),
            "out": jnp.einsum("tj,tjr,rh->th", weights, place, clean(rows)),
            "d_rows": jnp.einsum("tj,tjr,th->rh", weights, place, dy),
            "d_weights": jnp.einsum("tjr,rh,th->tj", place, clean(rows), dy)}
    for name, w in want.items():
        g, w = np.asarray(got[name]), np.asarray(w)
        assert np.max(np.abs(g - w)) <= 2e-5 * np.max(np.abs(w)), name


def test_bf16_rows_move_bit_for_bit_through_the_interpreted_kernels():
    """bf16, the type the compiled kernels take: a row is copied, not
    computed, so ``dispatch`` and its transpose of one-row tokens agree with
    the gathers to the bit, and the float32 sums to their rounding."""
    lay, k, x, weights = _rows_case("token_without_a_row")
    x = x.astype(jnp.bfloat16)
    rows = jax.random.normal(jax.random.PRNGKey(0),
                             (lay.row_valid.shape[0], x.shape[1]),
                             jnp.bfloat16)
    plain, kernels = (
        (gm.dispatch(x, lay, i), gm.combine(rows, weights, lay, i),
         *jax.vjp(lambda r, w: gm.combine(r, w, lay, i), rows, weights)[1](x))
        for i in (None, True))
    in_use = int(lay.n_tiles[0]) * 8
    for name, p, q in zip(("rows", "out", "d_rows", "d_weights"), plain,
                          kernels):
        p, q = (np.asarray(a, np.float32)[:in_use] for a in (p, q))
        if name in ("rows", "d_rows"):
            assert np.array_equal(p, q), name
        else:
            assert np.max(np.abs(p - q)) <= 1e-2 * np.max(np.abs(p)), name


# the cells' expert layers that differ in kind: (assignments, hidden, expert
# width, bound). kanana's powers of two; Mellum2's 2,304 = 18 x 128 and 896 =
# 7 x 128, at which PR 41's step did not compile until a packed row was
# whole sublane tiles (``gm._row_major``)
_CELL_EXPERTS = {"kanana": (8192 * 6, 2048, 768, 53248),
                 "mellum": (8192 * 8, 2304, 896, 69632)}


@pytest.mark.parametrize("cell", sorted(_CELL_EXPERTS))
def test_grouped_kernels_compile_for_v5e(v5e_device, cell):
    """The three kernels at a cell's size: 49,152 or 65,536 assignments of
    which any number may be held, 16 experts of 2,048 x 768 or 2,304 x
    896."""
    a, h, f, bound = _CELL_EXPERTS[cell]
    tile = gm.choose_tile(a, 16)
    m = gm.bound_rows(a, 16, tile)
    assert (tile, m) == (256, bound)
    assert gm.kernels_take((16, h, f), tile, jnp.bfloat16)
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)

    def grads(x, w_in, w_out, tile_group, n_tiles):
        def loss(x, w_in, w_out):
            hidden = gm.grouped_matmul(x, w_in, tile_group, n_tiles)
            return jnp.sum(gm.grouped_matmul(
                hidden, w_out, tile_group, n_tiles).astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2))(x, w_in, w_out)

    text = jax.jit(grads).lower(
        s((m, h), jnp.bfloat16), s((16, h, f), jnp.bfloat16),
        s((16, f, h), jnp.bfloat16), s((m // tile,), jnp.int32),
        s((1,), jnp.int32)).compile().as_text()
    assert {"moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"} <= set(
        re.findall(r"%(moe_gmm_\w+?)(?:\.\d+)? =", text))


@pytest.mark.parametrize("cell", sorted(_CELL_EXPERTS))
def test_rows_kernels_compile_for_v5e(v5e_device, cell):
    """``dispatch``, ``combine`` and their transposes at a cell's size:
    8,192 tokens of 6 or 8 assignments, 16 experts held, rows of 2,048 (16
    planes of 128 lanes) or 2,304 (18, packed as 24) in tiles of 256 inside
    a bound of 53,248 or 69,632."""
    a, h, _, bound = _CELL_EXPERTS[cell]
    n, groups = 8192, 16
    k = a // n
    tile = gm.choose_tile(n * k, groups)
    m = gm.bound_rows(n * k, groups, tile)
    assert (tile, m) == (256, bound)
    assert gm.rows_kernels_take(n, h, tile, jnp.bfloat16)
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)

    def grads(x, weights, group_of, through):
        lay = gm.group_layout(group_of, groups, tile)

        def loss(x, weights):       # ``through`` stands for the products
            to_gate, to_up = gm.dispatch(x, lay, copies=2)
            rows = to_gate * through + to_up
            return jnp.sum(gm.combine(rows, weights, lay).astype(
                jnp.float32))
        return jax.value_and_grad(loss, (0, 1))(x, weights)

    text = jax.jit(grads).lower(
        s((n, h), jnp.bfloat16), s((n, k), jnp.float32),
        s((n * k,), jnp.int32), s((m, h), jnp.bfloat16)).compile().as_text()
    # forward and backward: x and dy gathered into rows; rows, and the sum
    # of the two consumers' d_rows, packed and summed by token; d_weights
    # from the second gather's dots
    assert _rows_kernels_in(text) == {
        "moe_rows_gather": 2, "moe_rows_pack": 2, "moe_rows_combine": 2}
    assert _bound_sized_gathers(text, (m, n * k, f"{n},{k}"), h) == []


def _rows_kernels_in(text: str) -> dict:
    """How many calls of each ``moe_rows_*`` kernel a compiled program
    holds."""
    calls = re.findall(r"%(moe_rows_\w+?)(?:\.\d+)? = [^\n]*custom-call\(",
                       text)
    return {name: calls.count(name) for name in set(calls)}


def _bound_sized_gathers(text: str, row_counts, width: int):
    """The gathers of a compiled program whose result has one of
    ``row_counts`` as its leading dimensions and ``width`` as its last."""
    counts = "|".join(str(c) for c in row_counts)
    return re.findall(rf"= \w+\[(?:{counts}),{width}\]\S* gather\(", text)


def _scalar_moves_compiled(text: str, least: int):
    """The gathers and scatters of a compiled program that move single
    elements at ``least`` indices or more: [(op, indices)], as
    ``_scalar_moves`` finds them in a jaxpr."""
    shapes = {name: [int(d) for d in dims.split(",") if d]
              for name, dims in re.findall(
                  r"%([\w.\-]+) = \w+\[([\d,]*)\]", text)}
    found = []
    for name, dims, op, operands, rest in re.findall(
            r"%([\w.\-]+) = \w+\[([\d,]*)\]\S* (gather|scatter)"
            r"\(([^)]*)\)([^\n]*)", text):
        if op == "gather":
            sizes = re.search(r"slice_sizes=\{([\d,]*)\}", rest).group(1)
            single = all(size == "1" for size in sizes.split(","))
            count = int(np.prod([int(d) for d in dims.split(",") if d]))
        else:
            single = "update_window_dims={}" in rest
            updates = operands.split(",")[-1].strip().lstrip("%")
            count = int(np.prod(shapes[updates]))
        if single and count >= least:
            found.append((op, count))
    return found


# Kernel-eligible and small, as _ELIGIBLE is: 4 heads of 128 + 64 / 128, 8
# experts of which 2 are held, each [256, 128]; 384 tokens, top-2.
_LATENT = deepseek.DeepseekConfig(
    vocab_size=1024, hidden=256, n_layers=2, n_dense_layers=1, n_heads=4,
    kv_lora_rank=128, intermediate=512, moe_intermediate=128,
    n_routed_experts=8, n_shared_experts=1, experts_per_token=2, n_held=2)


def _compiled_latent_step(device) -> str:
    """The text of _LATENT's train step compiled for ``device``."""
    return _abstract_step(_LATENT, jax.sharding.SingleDeviceSharding(device),
                          deepseek).lower().compile().as_text()


def test_deepseek_step_takes_both_kernels_on_tpu(v5e_device, lowerings):
    """The step compiled for a v5e holds the attention kernels and the
    grouped product's, and no array shaped like the scores. The attention
    choice is counted once although four places hold it (two scans, each
    with its recomputation, which keeps the choice and not the forward
    kernel): JAX lowers a repeated sub-program once and calls it."""
    obs.set_enabled(True)
    grouped = obs.counter("moe_grouped_lowerings")
    before = grouped.get_value()
    text = _compiled_latent_step(v5e_device)
    assert {"attn_flash_fwd", "attn_flash_bwd", "moe_gmm_fwd", "moe_gmm_dlhs",
            "moe_gmm_drhs"} == set(
                re.findall(r"%((?:attn_flash|moe_gmm)_\w+?)(?:\.\d+)? =",
                           text))
    assert re.findall(rf"\w+\[[\d,]*{_T},{_T}\]", text) == []
    kernel, dense = lowerings()
    assert (kernel, dense) == (1, 0)
    assert grouped.get_value() - before >= 1


def test_deepseek_step_moves_rows_by_the_tiles_in_use_on_tpu(v5e_device):
    """The same step holds the row kernels where the gathers over the bound
    were — x, its recomputation and dy into rows (3), rows and d_rows by
    token (2, each packed first) — and counts a program that keeps them."""
    obs.set_enabled(True)
    counter = obs.counter("moe_rows_lowerings")
    before = counter.get_value()
    text = _compiled_latent_step(v5e_device)
    assert _rows_kernels_in(text) == {
        "moe_rows_gather": 3, "moe_rows_pack": 2, "moe_rows_combine": 2}
    assert counter.get_value() - before >= 1
    k = _LATENT.experts_per_token
    bound = gm.bound_rows(_T * k, _LATENT.n_held,
                          gm.choose_tile(_T * k, _LATENT.n_held))
    assert _bound_sized_gathers(text, (bound, _T * k, f"{_T},{k}"),
                                _LATENT.hidden) == []
    # nor are the two products' cotangents of the rows added over the bound
    assert re.findall(rf"= \w+\[{bound},{_LATENT.hidden}\]\S* add\(",
                      text) == []
    # and no scalar is moved by a gather or a scatter at as many indices as
    # the layer has assignments; the walk sees the loss's pick of a target
    # logit a token, the one gather of scalars the step has
    assert _scalar_moves_compiled(text, _T * k) == []
    assert _scalar_moves_compiled(text, _T) == [("gather", _T)]


# -- what the deepseek step keeps across its recomputation -------------------

def _kernels_in(jaxpr):
    """Names of the Pallas calls in a jaxpr, nested ones too."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _kernels_in(sub)
    return names


def _attention_scans(monkeypatch, saved: bool):
    """The scans of the differentiated loss of _LATENT that hold an
    attention kernel, in program order (dense forward, expert forward, then
    the backward scans): [(scan equation, {kernel name: count})].
    ``saved`` False: the layers under a bare ``jax.checkpoint``, by giving
    the policy no name to save."""
    if not saved:
        monkeypatch.setattr(deepseek, "SAVED_NAMES", ())
    params = jax.eval_shape(lambda k: deepseek.init_params(k, _LATENT),
                            jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, t: deepseek.loss_fn(p, t, _LATENT)[0]))(
            params, jax.ShapeDtypeStruct((1, _T), jnp.int32)).jaxpr
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            names = [n for n in _kernels_in(eqn.params["jaxpr"].jaxpr)
                     if n.startswith("attn_flash")]
            if names:
                found.append((eqn, {n: names.count(n) for n in set(names)}))
    return found


_FWD_ONLY = {"attn_flash_fwd": 1}
_BWD_ONLY = {"attn_flash_bwd": 1}


@pytest.mark.parametrize("saved,backward", [
    (True, _BWD_ONLY), (False, {**_FWD_ONLY, **_BWD_ONLY})],
    ids=["names_saved", "bare_checkpoint"])
def test_forward_kernel_runs_once_a_scan(monkeypatch, saved, backward):
    """With the kernel's output and log-sum-exp saved by name, the two
    backward scans hold the backward kernels alone, 2 forward kernels in
    all; under a bare checkpoint each runs the forward kernel again, 4."""
    counts = [c for _, c in _attention_scans(monkeypatch, saved)]
    assert counts == [_FWD_ONLY, _FWD_ONLY, backward, backward]


def test_compiled_step_holds_the_forward_kernel_once_a_scan(v5e_device):
    """What XLA:TPU keeps of it: two forward kernels (dense scan, expert
    scan), none in the backward scans' recomputation."""
    text = _compiled_latent_step(v5e_device)
    assert sorted(_ATTN_CALLS.findall(text)) == ["attn_flash_bwd"] * 2 + [
        "attn_flash_fwd"] * 2


def test_expert_layer_saves_output_lse_and_layout_and_no_q_or_k(monkeypatch):
    """The expert scan's stacked residuals: the layer's input, the kernel's
    head-major output and its log-sum-exp, and the integer routing layout
    the backward pass reads; nothing 192 wide (q, k)."""
    (_, (scan, _), *_) = _attention_scans(monkeypatch, True)
    heads, width = _LATENT.n_heads, _LATENT.qk_nope_dim + _LATENT.qk_rope_dim
    block = choose_block(_T)
    stacked = [(str(v.aval.dtype), v.aval.shape[1:])
               for v in scan.outvars[scan.params["num_carry"]:]]
    assert sorted(s for s in stacked if "float" in s[0] and len(s[1]) > 1) \
        == sorted([("bfloat16", (1, _T, _LATENT.hidden)),
                   ("bfloat16", (1, heads, _T, _LATENT.v_dim)),
                   ("float32", (1, heads, _T // block, 1, block))])
    k = _LATENT.experts_per_token
    tile = gm.choose_tile(_T * k, _LATENT.n_held)
    rows = gm.bound_rows(_T * k, _LATENT.n_held, tile)
    assert {("int32", (_T, k)),                              # selected
            ("int32", (_T * k,)), ("bool", (_T * k,)),       # dest, held
            ("int32", (rows,)), ("bool", (rows,)),   # row_source, row_valid
            ("int32", (rows // tile,)), ("int32", (1,))      # tiles
            } <= set(stacked)
    assert not any(width in shape for _, shape in stacked)


# -- the looped step (models/looped.py): shared weights under two scans ---------------

from brpc_tpu.models import looped  # noqa: E402

_LOOPED = looped.LoopedConfig(vocab_size=1024, hidden=256, n_layers=2,
                              n_heads=2, n_kv_heads=2, head_dim=128,
                              intermediate=512, total_ut_steps=3)


def test_looped_step_takes_the_kernels_once_a_scan_on_tpu(v5e_device,
                                                          lowerings):
    """A query group of one (as many KV heads as heads), a batch of two:
    the step compiled for a v5e holds one forward kernel (the layer scan
    inside the pass scan) and one backward kernel, none in the
    recomputation (output and log-sum-exp are saved by name), and no array
    shaped like the scores."""
    optimizer = optax.adamw(1e-4)
    params = jax.eval_shape(lambda k: looped.init_params(k, _LOOPED),
                            jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=jax.sharding.SingleDeviceSharding(v5e_device)),
        (params, jax.eval_shape(optimizer.init, params),
         jax.ShapeDtypeStruct((2, _T), jnp.int32)))
    text = jax.jit(looped.make_train_step(_LOOPED, optimizer)).trace(
        *state).lower().compile().as_text()
    assert sorted(_ATTN_CALLS.findall(text)) == _ONE_OF_EACH
    assert re.findall(rf"\w+\[[\d,]*{_T},{_T}\]", text) == []
    assert lowerings() == (1, 0)


# -- PR 34: a query group too large for VMEM, and the gated delta rule --------

from brpc_tpu.models import hybrid  # noqa: E402
from brpc_tpu.ops import causal_conv, gated_delta  # noqa: E402


@pytest.mark.parametrize("cell", ["mistral7b", "kanana2", "ouro"])
def test_held_geometries_keep_their_groups_whole(cell):
    """The three held cells' attention takes the branch it took before
    PR 34: the whole query group a KV head, grid over KV heads, dK and dV in
    the operands' dtype and no sum after the kernel (the builder compared
    the two commits' jaxprs at these geometries: identical, PERF.md)."""
    from brpc_tpu.ops.flash_attention import heads_together
    t, hq, hkv, d_qk, d_v, block = _CELLS[cell]
    assert heads_together(hq // hkv, t, d_qk, d_v, jnp.bfloat16)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2)))(s(1, t, hq, d_qk), s(1, t, hkv, d_qk),
                            s(1, t, hkv, d_v))

    def calls(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (bwd,) = [e for e in calls(jaxpr.jaxpr)
              if e.params["name"] == "attn_flash_bwd"]
    assert bwd.params["grid_mapping"].grid == (1, hkv, t // block)
    assert [v.aval.dtype for v in bwd.outvars] == [jnp.bfloat16] * 3
    assert bwd.outvars[1].aval.shape == (1, hkv, t, d_qk)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_a_query_head_at_a_time_matches_dense(monkeypatch, dtype, tol):
    """8 query heads a KV head of 256 / 256, the room made so small that the
    group does not fit: the interpreted kernels a query head at a time, dK
    and dV summed over the group afterwards, against the dense form."""
    import importlib
    fa = importlib.import_module("brpc_tpu.ops.flash_attention")
    q, k, v = _inputs(jax.random.PRNGKey(7), b=1, t=128, hq=16, hkv=2, d=256,
                      dtype=dtype)
    w = jax.random.normal(jax.random.PRNGKey(8), (1, 128, 16 * 256),
                          jnp.float32)
    wants = jax.grad(_weighted(functools.partial(dense_attention), w),
                     (0, 1, 2))(q, k, v)
    monkeypatch.setattr(fa, "VMEM_LIMIT", 2 * fa._resident(
        1, 128, 256, 256, dtype))
    assert not fa.heads_together(8, 128, 256, 256, dtype)
    assert fa.supported(q.shape, k.shape, dtype)
    grads = jax.grad(_weighted(functools.partial(
        flash_attention, block_q=64, block_k=64, interpret=True), w),
        (0, 1, 2))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), grads, wants):
        assert g.dtype == r.dtype == dtype and g.shape == r.shape, name
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.max(np.abs(g - r)) <= tol * np.max(np.abs(r)), name


def test_gated_attention_geometry_compiles_for_v5e(v5e_device):
    """Forward and backward at the Qwen3-Next cell's geometry (8,192 x 16
    query heads over 2 KV heads of 256): one call of each kernel, no array
    shaped like the scores."""
    args, _ = _abstract_inputs(jax.sharding.SingleDeviceSharding(v5e_device),
                               b=1, t=8192, hq=16, hkv=2, d=256,
                               dtype=jnp.bfloat16)
    text = _grad_of_sum().trace(*args).lower().compile().as_text()
    assert sorted(_ATTN_CALLS.findall(text)) == _ONE_OF_EACH
    assert re.findall(r"\w+\[[\d,]*8192,8192\]", text) == []


_GDN_CALLS = re.compile(r"%(gdn_chunk_\w+?)(?:\.\d+)? = [^\n]*custom-call\(")


def test_gated_delta_kernels_compile_for_v5e_at_the_cells_size(v5e_device):
    """1 x 8,192 tokens, 16 key and 32 value heads of 128, bf16: Mosaic and
    XLA:TPU take all three kernels; the backward pass keeps the chunks'
    entry states (128 of them a head), every chunk's T (two chunks side by
    side in the 128 lanes: 33.5 MB, where [64, 64] tiles would be padded to
    twice that) and no per-token state."""
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)
    args = (s(1, 8192, 16, 128), s(1, 8192, 16, 128), s(1, 8192, 32, 128),
            s(1, 8192, 32, dtype=jnp.float32),
            s(1, 8192, 32, dtype=jnp.float32))
    grad = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta.gated_delta_rule(*a)
                           .astype(jnp.float32)), argnums=(0, 1, 2, 3, 4)))
    text = grad.trace(*args).lower().compile().as_text()
    assert sorted(_GDN_CALLS.findall(text)) == [
        "gdn_chunk_bwd", "gdn_chunk_fwd", "gdn_chunk_prep"]
    assert "bf16[1,32,128,128,128]" in text           # the entry states
    assert "bf16[1,32,64,64,128]" in text             # T, a pair of chunks
    assert "bf16[1,32,128,64,64]" not in text
    assert re.findall(r"\w+\[[\d,]*8192,128,128\]", text) == []


_CONV_CALLS = re.compile(r"%(conv_silu_\w+?)(?:\.\d+)? = [^\n]*custom-call\(")


def test_conv_silu_kernels_compile_for_v5e_at_the_cells_size(v5e_device):
    """1 x 8,192 positions of the layer's whole projection (12,288 wide, of
    which q | k | v are the first 8,192), four float32 taps, bf16: Mosaic
    and XLA:TPU take both kernels; what XLA keeps beside them is no float32
    array of the channels' size and no copy of the convolved slice — the
    kernels reach it by block index."""
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)

    @jax.jit
    def both(x, taps, dy):
        y, vjp = jax.vjp(causal_conv.conv_silu, x, taps)
        return (y, *vjp(dy))

    text = both.trace(s(1, 8192, 12288), s(4, 8192, dtype=jnp.float32),
                      s(1, 8192, 8192)).lower().compile().as_text()
    assert sorted(_CONV_CALLS.findall(text)) == [
        "conv_silu_bwd", "conv_silu_fwd"]
    assert re.findall(r"f32\[1,819\d,8192\]", text) == []
    assert "bf16[1,8195,8192]" not in text


# Kernel-eligible and small: one period, 1 key and 2 value heads of 128, 2
# query heads over 1 KV head of 128, 8 experts of which 2 are held.
_HYBRID = hybrid.HybridConfig(
    vocab_size=1024, hidden=256, n_layers=4, n_heads=2, n_kv_heads=1,
    head_dim=128, linear_key_heads=1, linear_value_heads=2, n_experts=8,
    experts_per_token=2, moe_intermediate=128, shared_intermediate=128,
    n_held=2)


def test_hybrid_step_takes_every_kernel_on_tpu(v5e_device, lowerings):
    """The step compiled for a v5e holds the rule's kernels (the forward one
    twice: a layer's recomputation runs it again, its results are not
    saved; ``gdn_chunk_prep`` once, like the backward one: its T is saved
    by name and a recomputation that made it again would count two), the
    convolution's (the forward one twice likewise: y is not saved), the
    attention kernels, the grouped products and the row kernels, and no
    array shaped like the scores."""
    obs.set_enabled(True)
    gdn, conv = obs.counter("gdn_lowerings"), obs.counter("conv_lowerings")
    before, conv_before = gdn.get_value(), conv.get_value()
    text = _abstract_step(_HYBRID, jax.sharding.SingleDeviceSharding(
        v5e_device), hybrid).lower().compile().as_text()
    found = set(re.findall(
        r"%((?:gdn_chunk|conv_silu|attn_flash|moe_gmm|moe_rows)_\w+?)"
        r"(?:\.\d+)? =", text))
    assert found == {"gdn_chunk_prep", "gdn_chunk_fwd", "gdn_chunk_bwd",
                     "conv_silu_fwd", "conv_silu_bwd", "attn_flash_fwd",
                     "attn_flash_bwd", "moe_gmm_fwd",
                     "moe_gmm_dlhs", "moe_gmm_drhs", "moe_rows_gather",
                     "moe_rows_pack", "moe_rows_combine"}
    calls = collections.Counter(_GDN_CALLS.findall(text))
    assert calls["gdn_chunk_bwd"] >= 1
    assert calls["gdn_chunk_prep"] == calls["gdn_chunk_bwd"]
    assert calls["gdn_chunk_fwd"] == 2 * calls["gdn_chunk_bwd"]
    conv_calls = collections.Counter(_CONV_CALLS.findall(text))
    assert conv_calls["conv_silu_bwd"] == calls["gdn_chunk_bwd"]
    assert conv_calls["conv_silu_fwd"] == 2 * conv_calls["conv_silu_bwd"]
    assert re.findall(rf"\w+\[[\d,]*{_T},{_T}\]", text) == []
    assert lowerings() == (1, 0)
    assert gdn.get_value() - before == 1
    assert conv.get_value() - conv_before == 1


# -- PR 40: the expert layer reads no scalar through a gather over its bound --

from brpc_tpu.models import experts, windowed  # noqa: E402

_WINDOWED = windowed.WindowedConfig.tiny()
_ROUTES = {
    "hybrid": lambda y, r: experts.softmax_route(_HYBRID, y, r),
    "deepseek_biased": lambda y, r: experts.sigmoid_route(
        _LATENT, y, r, jnp.linspace(-0.3, 0.3, r.shape[1])),
    "deepseek_unbiased": lambda y, r: experts.sigmoid_route(
        _WINDOWED, y, r, 0.0),
}


@pytest.mark.parametrize("case", sorted(_ROUTES))
def test_router_weights_are_take_along_axis_to_the_bit(monkeypatch, case):
    """``experts.chosen`` in both routers against the gather it took the
    place of: the weights, and the gradients through them of the tokens and
    of the router's matrix, bit for bit, with ties in the scores (two
    experts with one column, a token of zeros)."""
    route = _ROUTES[case]
    rng = np.random.default_rng(5)
    router = rng.standard_normal((64, 8)).astype(np.float32)
    router[:, 5] = router[:, 2]
    y = rng.standard_normal((96, 64)).astype(np.float32)
    y[7] = 0
    y, router = jnp.asarray(y, jnp.bfloat16), jnp.asarray(router)
    cotangent = jnp.asarray(rng.standard_normal((96, 2)), jnp.float32)

    def run():
        def loss(y, router):
            selected, weights = route(y, router)
            return jnp.sum(weights * cotangent), (selected, weights)
        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            y, router)
        return jax.tree_util.tree_map(np.asarray, (*out, *grads))

    got = run()
    monkeypatch.setattr(experts, "chosen", lambda scores, selected:
                        jnp.take_along_axis(scores, selected, axis=1))
    want = run()
    for name, g, w in zip(("selected", "weights", "dy", "drouter"), got,
                          want):
        assert g.dtype == w.dtype and np.array_equal(g, w), name
    assert np.any(got[2]) and np.any(got[3])


def _scalar_moves(jaxpr, least: int):
    """The gathers and scatters of a jaxpr, nested ones too, that move
    single elements at ``least`` indices or more: [(primitive, indices)]."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            single = all(size == 1 for size in eqn.params["slice_sizes"])
            count = int(np.prod(eqn.invars[1].aval.shape[:-1]))
        elif name.startswith("scatter"):
            single = not eqn.params["dimension_numbers"].update_window_dims
            count = int(np.prod(eqn.invars[2].aval.shape))
        else:
            single, count = False, 0
        if single and count >= least:
            found.append((name, count))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scalar_moves(sub, least)
    return found


_EXPERT_LAYERS = {"hybrid": (hybrid, _HYBRID), "deepseek": (deepseek, _LATENT),
                  "windowed": (windowed, _WINDOWED)}


def _expert_layer_jaxpr(model: str, monkeypatch, interpret, n=64):
    """The jaxpr of value and gradient of ``model``'s ``moe_mlp`` on n
    tokens under the models' checkpoint (the layout saved by name, the rest
    run again), and N * k. ``interpret`` True: the kernels' path."""
    module, cfg = _EXPERT_LAYERS[model]
    if interpret:
        choose = gm.choose
        monkeypatch.setattr(
            gm, "choose", lambda kernel, plain, taken, counter, _, *operands:
            choose(kernel, plain, taken, counter, True, *operands))
    h, e, held = cfg.hidden, getattr(
        cfg, "n_experts", getattr(cfg, "n_routed_experts", 0)), cfg.n_held
    f = cfg.moe_intermediate
    shapes = {"router": (h, e), "router_bias": (e,), "shared_w": (h,),
              "w_gate": (held, h, f), "w_up": (held, h, f),
              "w_down": (held, f, h), "shared_gate": (h, f),
              "shared_up": (h, f), "shared_down": (f, h)}
    lp = {name: jax.ShapeDtypeStruct(shape, jnp.float32)
          for name, shape in shapes.items()}

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(gm.LAYOUT_NAME))
    def layer(y, lp):
        return module.moe_mlp(cfg, y, lp)[0]

    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda y, lp: jnp.sum(layer(y, lp)), (0, 1)))(
            jax.ShapeDtypeStruct((n, h), jnp.float32), lp)
    return jaxpr.jaxpr, n * cfg.experts_per_token


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["plain", "kernels_interpreted"])
@pytest.mark.parametrize("model", sorted(_EXPERT_LAYERS))
def test_expert_layer_moves_no_scalar_by_gather_over_its_bound(
        monkeypatch, model, interpret):
    """Forward, recomputed and backward, in every model that has the layer:
    no gather, scatter or scatter-add of single elements with as many
    indices as the layer has assignments (XLA:TPU moves them one at a time).
    Rows are fetched whole (the plain path's ``x[row_token]``), tables of a
    tile's worth are read by tile."""
    jaxpr, assignments = _expert_layer_jaxpr(model, monkeypatch, interpret)
    assert _scalar_moves(jaxpr, assignments) == []
    kernels = _kernels_in(jaxpr)
    assert bool(kernels) == bool(interpret)


def test_the_pin_sees_a_gather_of_scalars(monkeypatch):
    """The same walk finds the router's gather when it is put back, in the
    forward pass, the recomputation and (as a scatter-add) the backward."""
    monkeypatch.setattr(experts, "chosen", lambda scores, selected:
                        jnp.take_along_axis(scores, selected, axis=1))
    jaxpr, assignments = _expert_layer_jaxpr("hybrid", monkeypatch, None)
    found = _scalar_moves(jaxpr, assignments)
    assert sorted({name for name, _ in found}) == ["gather", "scatter-add"]
    assert {count for _, count in found} == {assignments}


# -- PR 43: q and k from the projections to the attention kernels in one pass -

from brpc_tpu.ops import qk_layout  # noqa: E402

_QK_CALLS = re.compile(r"%(qk_layout_\w+?)(?:\.\d+)? = [^\n]*custom-call\(")


@pytest.mark.parametrize("heads,kv_heads,rot,norm", [
    (64, 8, 128, False), (48, 8, 64, False), (32, 4, 128, True)],
    ids=["laguna_window", "laguna_full", "mellum"])
def test_qk_layout_kernels_compile_for_v5e_at_the_cells_sizes(
        v5e_device, heads, kv_heads, rot, norm):
    """1 x 8,192 positions of a layer's q and k, heads of 128, bf16: Mosaic
    and XLA:TPU take both kernels, one call each for q and k together; what
    XLA keeps beside them is no float32 array of q's size."""
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)

    @jax.jit
    def both(q, k, cos, sin, weights, dq, dk):
        return (*qk_layout.forward(q, k, cos, sin, weights, rot // 2, 1e-6),
                *qk_layout.backward(dq, dk, q, k, cos, sin, weights, rot // 2,
                                    1e-6))

    table = s(1, 8192, 128, dtype=jnp.float32)
    text = both.trace(
        s(1, 8192, heads * 128), s(1, 8192, kv_heads * 128), table, table,
        s(2, 128, dtype=jnp.float32) if norm else None,
        s(1, heads, 8192, 128), s(1, kv_heads, 8192, 128)
    ).lower().compile().as_text()
    assert sorted(_QK_CALLS.findall(text)) == ["qk_layout_bwd",
                                               "qk_layout_fwd"]
    assert re.findall(rf"f32\[1,(8192,{heads}|{heads},8192),128\]", text) == []


# -- PR 44: a per-head output gate in the attention kernels' epilogue ---------

import importlib  # noqa: E402

# the module: ``brpc_tpu.ops.flash_attention`` the attribute is the function
fa = importlib.import_module("brpc_tpu.ops.flash_attention")

_GATES = {"normal": lambda g: 3 * g, "zero": jnp.zeros_like,
          "positive": lambda g: g + 20, "negative": lambda g: g - 20}


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("window", [None, 64], ids=["causal", "band"])
@pytest.mark.parametrize("case", [
    ("normal", jnp.float32, True), ("zero", jnp.float32, True),
    ("positive", jnp.float32, True), ("negative", jnp.float32, True),
    ("normal", jnp.bfloat16, True), ("negative", jnp.bfloat16, True),
    ("normal", jnp.float32, False), ("normal", jnp.bfloat16, False)],
    ids=lambda c: f"{c[0]}-{jnp.dtype(c[1]).name}"
                  f"{'' if c[2] else '-a_head_at_a_time'}")
def test_gated_kernels_match_the_plain_form(monkeypatch, case, window):
    """``flash_attention_head_major(..., gate=g)`` interpreted against dense
    attention and then ``o * sigmoid(g)`` in float32: the output and the
    cotangents of q, k, v and the logits, 16 query heads over 2 KV heads
    (groups of 8, as Laguna's 64 and 48 over 8), three tiles of 128, logits
    that make the gate about a half (0), about 1 (+20) and about 0 (-20),
    and with each query head through the backward kernel on its own. The
    tolerance is of each array's largest element, as above."""
    logits, dtype, together = case
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    b, t, hq, hkv, d = 1, 384, 16, 2, 128
    q, k, v = _inputs(jax.random.PRNGKey(9), b=b, t=t, hq=hq, hkv=hkv, d=d,
                      dtype=dtype)
    g = _GATES[logits](jax.random.normal(jax.random.PRNGKey(10), (b, t, hq)))
    w = jax.random.normal(jax.random.PRNGKey(11), (b, t, hq * d))
    if not together:
        monkeypatch.setattr(fa, "VMEM_LIMIT", 2 * fa._resident(
            1, t, d, d, dtype))
    assert fa.heads_together(hq // hkv, t, d, d, dtype) == together

    def gated(q, k, v, g):
        return fa.flash_attention_head_major(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v, g,
            window=window, interpret=True).astype(jnp.float32)

    def plain(q, k, v, g):
        o = dense_attention(q, k, v, window=window)
        return (o.reshape(b, t, hq, d).astype(jnp.float32)
                * jax.nn.sigmoid(g)[..., None]).reshape(b, t, hq * d)

    for f in (gated, plain):
        assert f(q, k, v, g).shape == (b, t, hq * d)
    got, want = gated(q, k, v, g), plain(q, k, v, g)
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * w), (0, 1, 2, 3))(q, k, v, g)
             for f in (gated, plain)]
    for name, mine, ref in zip(("dq", "dk", "dv", "dg"), *grads):
        assert mine.dtype == ref.dtype and mine.shape == ref.shape, name
        mine, ref = np.asarray(mine, np.float32), np.asarray(ref, np.float32)
        assert np.max(np.abs(mine - ref)) <= tol * np.max(np.abs(ref)), name


@pytest.mark.parametrize("gated", [False, True], ids=["no_gate", "gate"])
@pytest.mark.parametrize("window", [None, 512], ids=["causal", "band"])
def test_without_a_gate_the_calls_are_the_parents(gated, window):
    """Forward and backward traced: without a gate the forward kernel takes
    q, k and v and the backward kernel its six operands, and no sigmoid is
    taken anywhere (the parent's program; tests/test_qk_layout.py pins the
    token-major callers' jaxpr); with one, the forward kernel takes the
    logits as a fourth operand, a row a query tile, and the backward kernel
    is the same call."""
    s = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype)
    gate = (s(1, 1024, 16, dtype=jnp.float32),) if gated else ()

    def loss(q, k, v, *gate):
        return jnp.sum(fa.flash_attention_head_major(
            q, k, v, *gate, window=window).astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(3 + gated))))(
        s(1, 16, 1024, 128), s(1, 2, 1024, 128), s(1, 1024, 2, 128), *gate)
    eqns = list(_pallas_calls(jaxpr.jaxpr))
    kind = "flash" if window is None else "band"
    calls = {e.params["name"]: e for e in eqns
             if e.primitive.name == "pallas_call"}
    assert sorted(calls) == [f"attn_{kind}_bwd", f"attn_{kind}_fwd"]
    fwd, bwd = calls[f"attn_{kind}_fwd"], calls[f"attn_{kind}_bwd"]
    assert len(fwd.invars) == 3 + gated and len(bwd.invars) == 6
    if gated:
        assert fwd.invars[3].aval.shape == (1, 16, 2, 1, 512)
    assert any(e.primitive.name == "logistic" for e in eqns) == gated


@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)],
                         ids=["laguna_window", "laguna_full"])
def test_gated_kernels_compile_for_v5e_at_the_cells_geometry(v5e_device,
                                                              heads, window):
    """1 x 8,192 positions, Laguna's heads over 8 KV heads of 128, bf16, the
    gate's logits float32: Mosaic and XLA:TPU take the forward kernel with
    its epilogue and the backward kernel, one call each."""
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)
    text = jax.jit(jax.grad(lambda q, k, v, g: jnp.sum(
        fa.flash_attention_head_major(q, k, v, g, window=window).astype(
            jnp.float32)), (0, 1, 2, 3))).trace(
        s(1, heads, 8192, 128), s(1, 8, 8192, 128), s(1, 8192, 8, 128),
        s(1, 8192, heads, dtype=jnp.float32)).lower().compile().as_text()
    kind = "flash" if window is None else "band"
    assert sorted(re.findall(r"%(attn_\w+?)(?:\.\d+)? = [^\n]*custom-call\(",
                             text)) == [f"attn_{kind}_bwd", f"attn_{kind}_fwd"]


# -- the hybrid Mamba-2 model: the scan's kernels, the convolution's bias and
# attention at heads of 64 ------------------------------------------------------

from brpc_tpu.models import mamba  # noqa: E402

_SSD_CALLS = re.compile(r"%(ssd_chunk_\w+?)(?:\.\d+)? = [^\n]*custom-call\(")


def test_ssd_kernels_compile_for_v5e_at_the_cells_size(v5e_device):
    """1 x 4,096 positions of xBC (64 heads of 64, then B and C of 128),
    float32 steps and decays, bf16: Mosaic and XLA:TPU take both kernels,
    one call each; B and C are reached by block index (no bf16 slice of
    them is made) and no head's [C, C] leaves a kernel."""
    from brpc_tpu.ops import ssd

    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)

    @jax.jit
    def both(xbc, dt, a, dy):
        y, vjp = jax.vjp(lambda *z: ssd.ssd(*z, head_dim=64), xbc, dt, a)
        return (y, *vjp(dy))

    text = both.trace(s(1, 4096, 4352), s(1, 4096, 64, dtype=jnp.float32),
                      s(1, 4096, 64, dtype=jnp.float32),
                      s(1, 4096, 4096)).lower().compile().as_text()
    assert sorted(_SSD_CALLS.findall(text)) == ["ssd_chunk_bwd",
                                                 "ssd_chunk_fwd"]
    # the forward kernel reads x, B and C from xbc itself, three times
    assert re.search(r"ssd_chunk_fwd[^\n]*custom-call\((%[\w.]+), \1, \1,",
                     text)
    assert not re.search(r"= bf16\[1,4096,128\][^\n]* slice\(", text)
    # of [.., 128, 128] arrays only the chunks' entry states, a run of two
    # heads each
    assert set(re.findall(r"\w+\[[\d,]*,128,128\]", text)) == {
        "bf16[1,32,32,128,128]"}


def test_conv_silu_with_a_bias_compiles_for_v5e_at_the_mamba_cell_size(
        v5e_device):
    """1 x 4,096 positions of the Mamba layer's projection (8,448 wide, of
    which xBC are the first 4,352 = 17 blocks of 256), four float32 taps and
    a float32 bias: both kernels, one call each."""
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)

    @jax.jit
    def both(x, taps, bias, dy):
        y, vjp = jax.vjp(causal_conv.conv_silu, x, taps, bias)
        return (y, *vjp(dy))

    text = both.trace(s(1, 4096, 8448), s(4, 4352, dtype=jnp.float32),
                      s(4352, dtype=jnp.float32),
                      s(1, 4096, 4352)).lower().compile().as_text()
    assert sorted(_CONV_CALLS.findall(text)) == [
        "conv_silu_bwd", "conv_silu_fwd"]
    assert re.findall(r"f32\[1,409\d,4352\]", text) == []


def test_attention_kernels_compile_for_v5e_at_heads_of_64(v5e_device):
    """granite-4.0-h-micro's attention layer: 32 query heads over 8 KV heads
    of 64 at 4,096 positions, both kernels, no [T, T] array."""
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,  # noqa: E731
                                            sharding=sharding)
    text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v).astype(jnp.float32)), (0, 1, 2))).trace(
        s(1, 4096, 32, 64), s(1, 4096, 8, 64),
        s(1, 4096, 8, 64)).lower().compile().as_text()
    assert sorted(_ATTN_CALLS.findall(text)) == _ONE_OF_EACH
    assert re.findall(r"\w+\[[\d,]*4096,4096\]", text) == []


# Kernel-eligible and small: one period of the published table, 4 SSM heads
# of 64 over a state of 128, 4 query heads over 1 KV head of 64.
_MAMBA = mamba.MambaConfig(vocab_size=1024, hidden=256, n_layers=10,
                           n_heads=4, n_kv_heads=1, ssm_heads=4,
                           ssm_head_dim=64, ssm_state=128, intermediate=512)


def test_mamba_step_takes_every_kernel_on_tpu(v5e_device, lowerings):
    """The step compiled for a v5e holds the scan's kernels (the forward
    one twice: a layer's recomputation runs it again), the convolution's
    (likewise) and the attention kernels at heads of 64, and no array shaped
    like the scores; each program counted once."""
    obs.set_enabled(True)
    ssd_n, conv = obs.counter("ssd_lowerings"), obs.counter("conv_lowerings")
    before, conv_before = ssd_n.get_value(), conv.get_value()
    text = _abstract_step(_MAMBA, jax.sharding.SingleDeviceSharding(
        v5e_device), mamba).lower().compile().as_text()
    calls = collections.Counter(re.findall(
        r"%((?:ssd_chunk|conv_silu|attn_flash)_\w+?)(?:\.\d+)? = [^\n]*"
        r"custom-call\(", text))
    assert calls == {"ssd_chunk_fwd": 2, "ssd_chunk_bwd": 1,
                     "conv_silu_fwd": 2, "conv_silu_bwd": 1,
                     "attn_flash_fwd": 1, "attn_flash_bwd": 1}
    assert re.findall(rf"\w+\[[\d,]*{_T},{_T}\]", text) == []
    assert lowerings() == (1, 0)
    assert ssd_n.get_value() - before == 1
    assert conv.get_value() - conv_before == 1
