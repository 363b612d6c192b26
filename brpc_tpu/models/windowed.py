"""Window-and-full-attention sparse-expert transformers: sliding-window
attention layers and full-attention layers in one stack, each kind with a
rope of its own, sparse experts after every attention block. One model file
for two published configurations, told apart by what their ``config.json``
states and never by a name:

- ``model_type: laguna`` (poolside/Laguna-XS.2, 33B-A3B; the defaults of
  ``WindowedConfig``): full, window, window, window ten times over, 48 query
  heads on full layers and 64 on window layers over 8 KV heads of 128, a
  window of 512, yarn on the leading half of a full layer's heads, a
  per-head sigmoid gate on every head's output, layer 0's MLP dense, then
  256 sigmoid-routed experts (top-8) beside one shared expert.
- ``model_type: mellum`` (JetBrains/Mellum2-12B-A2.5B-Instruct;
  ``WindowedConfig.mellum2()``): window, window, window, full seven times
  over, 32 query heads over 4 KV heads of 128 on every layer, a window of
  1,024, a per-head RMSNorm on q and on k before the rope, yarn on the whole
  head of the full layers, no output gate, every layer's MLP sparse: 64
  softmax-routed experts (top-8) and no shared one.

TPU-first functional JAX with the entry points of the other models — a
frozen config with a ``tiny()`` preset, ``init_params``, ``hidden_states``,
``forward``, ``loss_fn`` and ``make_train_step``.

Which published key sets which switch of ``WindowedConfig``:
``layer_types`` / ``mlp_layer_types`` -> the fields of those names (``full``
/ ``window``, ``dense`` / ``sparse``), read up to ``n_layers``: the stack is
laid out from them (``layout``); ``num_attention_heads_per_layer`` (or
``num_attention_heads`` where every layer has as many) -> ``full_heads``,
``window_heads``; ``gating`` -> ``attn_gate``; the family's q/k norm
(Qwen3-MoE's keys, which Mellum2's are) -> ``qk_norm``;
``moe_routed_scaling_factor`` with sigmoid scores -> ``router="sigmoid"``
and ``routed_scaling``, ``norm_topk_prob`` with softmax scores ->
``router="softmax"``; ``shared_expert_intermediate_size`` ->
``shared_intermediate`` (0: no shared expert); ``rope_parameters``'
``partial_rotary_factor`` of the full layers -> ``full_rotary_factor`` (1
where the group has none).

The layers. Every layer is ``x = x + attn(norm(x)); x = x + mlp(norm(x))``
with RMSNorm (``llama.rms_norm``); a layer's MLP is a dense SwiGLU of
``intermediate`` where ``mlp_layer_types`` says ``dense``, else the experts.

- **Attention** (H = ``full_heads`` or ``window_heads`` query heads, d =
  ``head_dim``). ``q = y·W_q`` [T, H, d], ``k = y·W_k``, ``v = y·W_v`` [T,
  ``n_kv_heads``, d]; with ``attn_gate``, ``g = y·W_g`` [T, H]; with
  ``qk_norm``, ``q <- rmsnorm_d(q)·w_q`` and ``k <- rmsnorm_d(k)·w_k`` per
  head, weights of d, before the rope. Rope, halves rotated
  (``llama.rope``'s convention). Window layers: plain rope of
  ``window_rope_theta`` on the whole head. Full layers: yarn on the leading
  ``full_rotary_factor`` of the head (``yarn_inv_freq``: interpolated and
  extrapolated frequencies blended by a linear ramp between the dimensions
  that turn ``beta_fast`` and ``beta_slow`` times over the original context,
  cos and sin times ``attention_factor``), the rest unrotated. Scores
  ``q·k / sqrt(d)`` masked to ``0 <= i - j`` (full) or ``0 <= i - j <
  window`` (window), float32 softmax (``attend``): on a TPU the fused
  kernels, for a window the band kernels that skip the tiles outside it
  (``ops/flash_attention.py``), which read q and k head-major as one pass of
  ``ops/qk_layout.py`` leaves them — norm, rope and the turn from the
  projections' token-major layout done in float32 in registers; elsewhere
  the norm and the rope as float32 passes and ``llama.attention``.
  With ``attn_gate``, ``o_h <- sigmoid(g_h) · o_h`` per head; ``·W_o``. No
  biases.
- **Experts.** ``router="sigmoid"``: ``s = sigmoid(y·W_r)`` in float32 over
  ALL ``n_experts``; the ``experts_per_token`` largest; weights ``s_sel /
  sum s_sel · routed_scaling`` (``deepseek.route``, with no selection bias).
  ``router="softmax"``: ``p = softmax(y·W_r)`` in float32 over all; the
  largest; weights ``p_sel / sum p_sel`` (``hybrid.route``). Experts SwiGLU
  of ``moe_intermediate``; plus, where ``shared_intermediate`` is not 0, one
  unweighted shared SwiGLU of that width. What follows the routing is
  ``models/experts.py``, and so is the contract of a chip's share: told
  ``n_held`` and ``expert_offset`` it routes over all, normalises over all
  the selected wherever they live, computes what its own give, drops
  nothing.

What the published configs leave to convention (the benchmark's
configuration files list each under ``assumed``): SwiGLU with silu; the gate
per head, read from the layer's normed input; the routers as above; the
rotated lanes as two halves; no auxiliary balance loss; no
multi-token-prediction head.

How it runs (``layout``, from the two tables). A leading layer whose MLP is
dense is a tree of its own (``first``; Laguna's layer 0, none in Mellum2).
The layers after it are periods of window layers ended by a full layer,
stacked by kind — ``window`` [periods, windows a period, ...], ``full``
[periods, ...] — and run under ONE ``lax.scan`` over periods whose body scans
the period's window layers and then runs its full layer; window layers left
over at the end (Laguna's published 40 layers end in three) are ``tail``,
scanned after. So the published full layer stands first in Laguna's period
of four and last in Mellum2's, and both are the same scan. One compiled body
of each kind whatever the depth. Each layer is recomputed in the backward
pass (``jax.checkpoint``) from its input and what ``SAVED_NAMES`` names: the
attention kernel's output and log-sum-exp, its head-major q and k (0.3 GB
of Mellum2's four layers at 8,192 tokens, 0.75 GB of Laguna's five: the
recomputation then runs no layout pass, and without q/k norms no ``y·W_q``
either), and the experts' integer routing layout. bf16 compute; float32
master weights, router, norms' arithmetic, softmax and loss
(``models/chunked_loss.py``).

``make_train_step``'s step also returns ``stats``, a row an expert layer in
the layers' order, of what ``experts.expert_mlp`` counts. Named scopes:
``swa.qkv``, ``swa.qknorm`` (with ``qk_norm``), ``swa.rope``, ``swa.attn``
(around the kernels' own ``attn.*``), ``swa.out`` and ``full.*`` likewise,
``dense.mlp`` (a dense layer), ``moe.router``, ``moe.sort``,
``moe.experts``, ``moe.combine``, ``moe.shared`` (a shared expert);
``windowed.glue`` around the norms, residuals and reshapes between them;
``embed``, ``weights.cast``, ``loss.chunk`` and ``opt.update`` as in every
model.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from brpc_tpu.models import deepseek, hybrid
from brpc_tpu.models.chunked_loss import chunked_next_token_loss
from brpc_tpu.models.experts import expert_mlp, swiglu
from brpc_tpu.models.llama import (_dense_init, attention, dense_attention,
                                   rms_norm, rope)
from brpc_tpu.models.train_step import apply_updates
from brpc_tpu.ops import grouped_matmul as gm
from brpc_tpu.ops import qk_layout
from brpc_tpu.ops.flash_attention import (RESIDUAL_NAMES,
                                          flash_attention_head_major,
                                          supported as flash_supported)
from brpc_tpu.ops.lowered import count_lowering

Params = Dict[str, Any]

_FLOAT32_LEAVES = ("router",)       # never cast to the compute dtype

# ``checkpoint_name``s of the head-major q and k that ``qk_head_major``
# returns: the attention kernels' residuals, so a layer that keeps them runs
# no layout pass in its recomputation.
QK_NAMES = ("qk_head_major_q", "qk_head_major_k")
# What a layer keeps across its recomputation beside its input.
SAVED_NAMES = (*RESIDUAL_NAMES, *QK_NAMES, gm.LAYOUT_NAME)


_LAGUNA_LAYERS = ("full", "window", "window", "window") * 10
_MELLUM_LAYERS = ("window", "window", "window", "full") * 7


@dataclasses.dataclass(frozen=True)
class WindowedConfig:
    """The defaults are Laguna-XS.2 as published, every expert held."""
    vocab_size: int = 100352
    hidden: int = 2048
    n_layers: int = 40
    layer_types: tuple = _LAGUNA_LAYERS       # "full" / "window" a layer ...
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 39  # "dense"/"sparse"
    full_heads: int = 48
    window_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512
    attn_gate: bool = True            # a per-head sigmoid gate on the output
    qk_norm: bool = False             # a per-head RMSNorm on q and on k
    window_rope_theta: float = 10000.0
    full_rope_theta: float = 500000.0         # the full layers' yarn ...
    full_rotary_factor: float = 0.5
    yarn_factor: float = 64.0
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672   # 0.1 ln 64 + 1
    intermediate: int = 8192          # a dense layer's SwiGLU
    n_experts: int = 256              # the router's width, always whole
    experts_per_token: int = 8
    moe_intermediate: int = 512       # one expert's SwiGLU
    shared_intermediate: int = 512    # the shared expert's; 0: there is none
    router: str = "sigmoid"           # or "softmax"
    routed_scaling: float = 2.5       # the sigmoid router's
    norm_eps: float = 1e-6
    n_held: int = 256                 # experts this chip holds ...
    expert_offset: int = 0            # ... from this one on
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def tiny(vocab_size: int = 256, n_held: int = 2,
             expert_offset: int = 0) -> "WindowedConfig":
        """A toy config for tests / dry runs: the dense full layer and one
        period (3 window + 1 full layer), 6 and 4 query heads over 2 KV
        heads, a window of 16, 8 experts of which ``n_held`` live here,
        top-2."""
        return WindowedConfig(
            vocab_size=vocab_size, hidden=64, n_layers=5,
            full_heads=4, window_heads=6, n_kv_heads=2, head_dim=32,
            window=16, yarn_original_positions=32, intermediate=128,
            n_experts=8, experts_per_token=2, moe_intermediate=32,
            shared_intermediate=32, n_held=n_held,
            expert_offset=expert_offset)

    @staticmethod
    def mellum2(**changed) -> "WindowedConfig":
        """Mellum2-12B-A2.5B-Instruct as published, every expert held."""
        return dataclasses.replace(WindowedConfig(
            vocab_size=98304, hidden=2304, n_layers=28,
            layer_types=_MELLUM_LAYERS, mlp_layer_types=("sparse",) * 28,
            full_heads=32, window_heads=32, n_kv_heads=4, window=1024,
            attn_gate=False, qk_norm=True, window_rope_theta=500000.0,
            full_rotary_factor=1.0, yarn_factor=16.0,
            yarn_original_positions=8192, yarn_beta_fast=32.0,
            yarn_attention_factor=1.2772588722239782,   # 0.1 ln 16 + 1
            intermediate=7168, n_experts=64, moe_intermediate=896,
            shared_intermediate=0, router="softmax", n_held=64), **changed)

    @staticmethod
    def tiny_mellum2(n_held: int = 2, expert_offset: int = 0
                     ) -> "WindowedConfig":
        """``tiny()``'s sizes laid out as Mellum2: one period, 3 window
        layers and then the full one, 4 query heads over 2 KV heads on every
        layer, q/k norms, no gate, no dense layer, no shared expert."""
        return WindowedConfig.mellum2(
            vocab_size=256, hidden=64, n_layers=4, full_heads=4,
            window_heads=4, n_kv_heads=2, head_dim=32, window=16,
            n_experts=8, experts_per_token=2, moe_intermediate=32,
            n_held=n_held, expert_offset=expert_offset)

    @property
    def layer_kinds(self) -> tuple:
        """``"full"`` or ``"window"`` for every layer held, by the published
        table."""
        return tuple(self.layer_types[:self.n_layers])

    @property
    def layout(self) -> tuple:
        """(leading dense layers, window layers a period, whole periods,
        window layers left over), from the two per-layer tables: after the
        leading layers whose MLP is dense, periods of window layers ended by
        a full layer, then window layers alone."""
        kinds = self.layer_kinds
        mlps = tuple(self.mlp_layer_types[:self.n_layers])
        if len(kinds) < self.n_layers or len(mlps) < self.n_layers:
            raise ValueError(f"the per-layer tables hold {len(kinds)} and "
                             f"{len(mlps)} of {self.n_layers} layers")
        n_first = mlps.index("sparse") if "sparse" in mlps else len(mlps)
        rest = kinds[n_first:]
        if n_first > 1 or "dense" in mlps[n_first:] \
                or kinds[:n_first] != ("full",) * n_first or "full" not in rest:
            raise ValueError(
                "the stack this file lays out is at most one leading full "
                "layer with a dense MLP and then sparse layers of which one "
                f"at least is full; the tables say {kinds}, {mlps}")
        per = rest.index("full")
        periods, n_tail = divmod(len(rest), per + 1)
        if rest != (("window",) * per + ("full",)) * periods \
                + ("window",) * n_tail:
            raise ValueError(f"the layers after the dense ones are not "
                             f"periods of {per} window layers and a full "
                             f"one, then window layers: {rest}")
        return n_first, per, periods, n_tail

    @property
    def stacks(self) -> tuple:
        """(whole periods after the leading dense layers, window layers left
        over)."""
        return self.layout[2:]


def yarn_inv_freq(cfg: WindowedConfig) -> np.ndarray:
    """The full layers' rotary frequencies, one a pair of lanes: ``theta^(-2i
    / rot)`` as it is (extrapolated) where a dimension turns more than
    ``beta_fast`` times over the original context, divided by ``factor``
    (interpolated) where it turns fewer than ``beta_slow`` times, a linear
    ramp between (arXiv:2309.00071; HF ``_compute_yarn_parameters``)."""
    rot = int(cfg.head_dim * cfg.full_rotary_factor)
    pos = cfg.full_rope_theta ** (np.arange(0, rot, 2, dtype=np.float64)
                                  / rot)

    def turns_at(r):        # the dimension that turns r times over the context
        return rot * math.log(cfg.yarn_original_positions / (2 * math.pi * r)
                              ) / (2 * math.log(cfg.full_rope_theta))

    low = max(math.floor(turns_at(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.yarn_beta_slow)), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3), 0, 1)
    return ((1 / (cfg.yarn_factor * pos)) * ramp
            + (1 / pos) * (1 - ramp)).astype(np.float32)


def yarn_rope(cfg: WindowedConfig, x: jax.Array,
              positions: jax.Array) -> jax.Array:
    """The full layers' rope. x: [B, T, H, D]: the leading ``rot`` lanes
    rotated as two halves by ``yarn_inv_freq``, cos and sin scaled by
    ``attention_factor``; the other lanes, where there are any, pass."""
    rot = int(cfg.head_dim * cfg.full_rotary_factor)
    angles = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    cos = (jnp.cos(angles) * cfg.yarn_attention_factor)[:, :, None, :]
    sin = (jnp.sin(angles) * cfg.yarn_attention_factor)[:, :, None, :]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    parts = [(x1 * cos - x2 * sin).astype(x.dtype),
             (x1 * sin + x2 * cos).astype(x.dtype)]
    if rot < x.shape[-1]:
        parts.append(x[..., rot:])
    return jnp.concatenate(parts, axis=-1)


def init_params(key: jax.Array, cfg: WindowedConfig) -> Params:
    """Matrices normal(0, fan_in^-1/2), norms 1. ``first`` and ``tail`` are
    there where the layout has such layers."""
    h, pd, d = cfg.hidden, cfg.param_dtype, cfg.head_dim
    n_first, per, periods, n_tail = cfg.layout
    kv_out = cfg.n_kv_heads * d
    k_emb, k_first, k_win, k_full, k_tail, k_out = jax.random.split(key, 6)

    def stack(key, lead, heads, mlp):
        ks = iter(jax.random.split(key, 16))
        gate = (("wg", (h, heads), h),) if cfg.attn_gate else ()
        layers = {name: _dense_init(next(ks), lead + shape, pd, fan_in)
                  for name, shape, fan_in in (
                      ("wq", (h, heads * d), h), ("wk", (h, kv_out), h),
                      ("wv", (h, kv_out), h), *gate,
                      ("wo", (heads * d, h), heads * d), *mlp)}
        layers["attn_norm"] = jnp.ones(lead + (h,), pd)
        layers["mlp_norm"] = jnp.ones(lead + (h,), pd)
        if cfg.qk_norm:
            layers["q_norm"] = jnp.ones(lead + (d,), pd)
            layers["k_norm"] = jnp.ones(lead + (d,), pd)
        return layers

    f, fs = cfg.moe_intermediate, cfg.shared_intermediate
    dense = (("w_gate", (h, cfg.intermediate), h),
             ("w_up", (h, cfg.intermediate), h),
             ("w_down", (cfg.intermediate, h), cfg.intermediate))
    shared = (("shared_gate", (h, fs), h), ("shared_up", (h, fs), h),
              ("shared_down", (fs, h), fs)) if fs else ()
    sparse = (("router", (h, cfg.n_experts), h),
              ("w_gate", (cfg.n_held, h, f), h),
              ("w_up", (cfg.n_held, h, f), h),
              ("w_down", (cfg.n_held, f, h), f), *shared)
    params = {
        "embed": _dense_init(k_emb, (cfg.vocab_size, h), pd, 1.0),
        "window": stack(k_win, (periods, per), cfg.window_heads, sparse),
        "full": stack(k_full, (periods,), cfg.full_heads, sparse),
        "final_norm": jnp.ones((h,), pd),
        "lm_head": _dense_init(k_out, (h, cfg.vocab_size), pd, h),
    }
    if n_first:
        params["first"] = stack(k_first, (), cfg.full_heads, dense)
    if n_tail:
        params["tail"] = stack(k_tail, (n_tail,), cfg.window_heads, sparse)
    return params


def _rotary(cfg: WindowedConfig, full: bool) -> tuple:
    """A kind of layer's rope: (the lanes of a head it turns, their ``rot /
    2`` inverse frequencies, what its cos and sin are scaled by)."""
    if full:
        return (int(cfg.head_dim * cfg.full_rotary_factor),
                yarn_inv_freq(cfg), cfg.yarn_attention_factor)
    half = cfg.head_dim // 2        # ``llama.rope``'s, by its own arithmetic
    return cfg.head_dim, cfg.window_rope_theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half), 1.0


def _normed_and_turned(cfg: WindowedConfig, kind: str, q: jax.Array,
                       k: jax.Array, norms, positions: jax.Array) -> tuple:
    """The plain form of a layer's q and k: [B, T, H·d] each -> ([B, T, H +
    ``n_kv_heads``, d], H): q's heads and then k's, each head normed
    (``norms``: the layer's ``q_norm`` and ``k_norm``, or None without
    ``qk_norm``) and turned by the rope of the layer's kind, each a float32
    pass. The two go through the rope as one array: a program holds this
    form beside the kernels until it is lowered
    (``lax.platform_dependent``), and every ``jax.numpy`` op traced is
    set-up."""
    (b, t, _), d = q.shape, cfg.head_dim
    scope = "full" if kind == "full" else "swa"
    q = count_lowering(q, "qk_layout_plain_lowerings")
    q, k = q.reshape(b, t, -1, d), k.reshape(b, t, -1, d)
    if norms is not None:
        with jax.named_scope(f"{scope}.qknorm"):
            q = rms_norm(q, norms[0], cfg.norm_eps)
            k = rms_norm(k, norms[1], cfg.norm_eps)
    nh, x = q.shape[2], jnp.concatenate([q, k], axis=2)
    with jax.named_scope(f"{scope}.rope"):
        if kind == "full":
            return yarn_rope(cfg, x, positions), nh
        return rope(x, positions, cfg.window_rope_theta), nh


@functools.partial(jax.jit, static_argnums=(0, 1))
def _plain_head_major(cfg, kind, q, k, norms, positions):
    """Under ``jax.jit``: Laguna's layer 0 and its full layers are one
    kind at one shape, and the second trace finds the first's."""
    x, nh = _normed_and_turned(cfg, kind, q, k, norms, positions)
    with jax.named_scope("attn.layout"):
        x = x.transpose(0, 2, 1, 3)
    return x[:, :nh], x[:, nh:]


def _kernel_operands(cfg, kind, norms, positions):
    """(cos table, sin table, the norms' weights or None, lanes turned)."""
    full = kind == "full"
    rot, inv_freq, scale = _rotary(cfg, full)
    weights = None
    if norms is not None:
        with jax.named_scope("full.qknorm" if full else "swa.qknorm"):
            weights = jnp.stack(norms).astype(jnp.float32)
    return (*qk_layout.rotation_tables(positions, inv_freq, cfg.head_dim,
                                       scale), weights, rot)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def qk_head_major(cfg: WindowedConfig, kind: str, q: jax.Array, k: jax.Array,
                  norms, positions: jax.Array) -> tuple:
    """q: [B, T, H·d], k: [B, T, ``n_kv_heads``·d] as the projections leave
    them (operands ``qk_layout.supported``) -> (q [B, H, T, d], k [B,
    ``n_kv_heads``, T, d]) as the attention kernels read them, normed and
    turned. Chosen by the platform being lowered for, in each pass on its
    own (as ``ops/causal_conv.py`` chooses): ``ops/qk_layout.py``'s kernels
    for TPU, one pass over q and k; elsewhere the plain form and its
    transposes. No transform differentiates through the choice: the
    backward pass is the backward kernel, or the plain form's own VJP.
    ``qk_layout_kernel_lowerings`` / ``qk_layout_plain_lowerings`` count
    which one each lowered program holds."""
    def kernel(q, k, norms, positions):
        cos, sin, weights, rot = _kernel_operands(cfg, kind, norms, positions)
        return qk_layout.forward(
            count_lowering(q, "qk_layout_kernel_lowerings"), k, cos, sin,
            weights, rot // 2, cfg.norm_eps)

    return lax.platform_dependent(
        q, k, norms, positions, tpu=kernel,
        default=functools.partial(_plain_head_major, cfg, kind))


def _qk_head_major_fwd(cfg, kind, q, k, norms, positions):
    out = qk_head_major(cfg, kind, q, k, norms, positions)
    out = tuple(checkpoint_name(x, name) for x, name in zip(out, QK_NAMES))
    return out, (q, k, norms, positions)


def _qk_head_major_bwd(cfg, kind, residuals, cotangents):
    def kernel(dq, dk, q, k, norms, positions):
        cos, sin, weights, rot = _kernel_operands(cfg, kind, norms, positions)
        dq, dk, dw = qk_layout.backward(dq, dk, q, k, cos, sin, weights,
                                        rot // 2, cfg.norm_eps)
        return dq, dk, None if norms is None else tuple(
            dw[i].astype(w.dtype) for i, w in enumerate(norms))

    def plain(dq, dk, q, k, norms, positions):
        return jax.vjp(lambda q, k, norms: _plain_head_major(
            cfg, kind, q, k, norms, positions), q, k, norms)[1]((dq, dk))

    return (*lax.platform_dependent(*cotangents, *residuals, tpu=kernel,
                                    default=plain), None)


qk_head_major.defvjp(_qk_head_major_fwd, _qk_head_major_bwd)


def attention_head_major(q: jax.Array, k: jax.Array, v: jax.Array, window):
    """``llama.attention`` (causal, with its counters) for q: [B, Hq, T, D]
    and k: [B, Hkv, T, D] head-major already and bf16 operands the fused
    kernels take: they read q and k as they are; the dense form takes them
    token-major again."""
    def dense(q, k, v):
        with jax.named_scope("attn.dense"):
            return dense_attention(
                count_lowering(q.transpose(0, 2, 1, 3),
                               "attn_dense_lowerings"),
                k.transpose(0, 2, 1, 3), v, window=window)

    def kernel(q, k, v):
        return flash_attention_head_major(
            count_lowering(q, "attn_kernel_lowerings"), k, v, window=window)

    return lax.platform_dependent(q, k, v, tpu=kernel, default=dense)


def attend(cfg: WindowedConfig, kind: str, q: jax.Array, k: jax.Array,
           v: jax.Array, norms, positions: jax.Array) -> jax.Array:
    """Attention of a ``"full"`` or ``"window"`` layer on q: [B, T, H·d] and
    k: [B, T, ``n_kv_heads``·d] as the projections leave them and v: [B, T,
    ``n_kv_heads``, d] -> [B, T, H·d]: each head of q and k normed and
    turned by the rope of the layer's kind, then ``llama.attention``'s
    contract. Operands that both the q/k layout kernels and the attention
    kernels take go head-major through ``qk_head_major`` to
    ``attention_head_major``; everything else runs the plain form and
    ``llama.attention`` from token-major operands."""
    full = kind == "full"
    scope = "full" if full else "swa"
    (b, t, _), d = q.shape, cfg.head_dim
    window = None if full else cfg.window
    if (v.dtype == jnp.bfloat16
            and qk_layout.supported(q.shape, k.shape, q.dtype, d,
                                    _rotary(cfg, full)[0])
            and flash_supported((b, t, q.shape[2] // d, d), v.shape, q.dtype,
                                window=window)):
        with jax.named_scope(f"{scope}.rope"):
            q, k = qk_head_major(cfg, kind, q, k, norms, positions)
        with jax.named_scope(f"{scope}.attn"):
            return attention_head_major(q, k, v, window)
    x, nh = _normed_and_turned(cfg, kind, q, k, norms, positions)
    with jax.named_scope(f"{scope}.attn"):
        return attention(x[:, :, :nh], x[:, :, nh:], v, window=window)


def attention_block(cfg: WindowedConfig, kind: str, x: jax.Array, lp: Params,
                    positions: jax.Array) -> jax.Array:
    """The attention block of a ``"full"`` or ``"window"`` layer with its
    residual. x: [B, T, H]."""
    b, t, _ = x.shape
    full = kind == "full"
    scope = "full" if full else "swa"
    nh = cfg.full_heads if full else cfg.window_heads
    nkv, d = cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope(f"{scope}.qkv"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k = y @ lp["wq"], y @ lp["wk"]
        v = (y @ lp["wv"]).reshape(b, t, nkv, d)
        if cfg.attn_gate:
            gate = jnp.dot(y, lp["wg"], preferred_element_type=jnp.float32)
    o = attend(cfg, kind, q, k, v,
               (lp["q_norm"], lp["k_norm"]) if cfg.qk_norm else None,
               positions)
    with jax.named_scope(f"{scope}.out"):
        if cfg.attn_gate:
            o = (o.reshape(b, t, nh, d).astype(jnp.float32)
                 * jax.nn.sigmoid(gate)[..., None]).astype(x.dtype)
        return x + o.reshape(b, t, nh * d) @ lp["wo"]


def moe_mlp(cfg: WindowedConfig, y: jax.Array, lp: Params):
    """The expert layer's MLP on normed tokens y: [N, H] -> ([N, H], stats):
    what the held experts give for the assignments routed to them, plus the
    shared expert where the model has one."""
    with jax.named_scope("moe.router"):
        if cfg.router == "softmax":
            selected, weights = hybrid.route(cfg, y, lp["router"])
        elif cfg.router == "sigmoid":   # DeepSeek-V3's, no selection bias
            selected, weights = deepseek.route(cfg, y, lp["router"], 0.0)
        else:
            raise ValueError(f"router {cfg.router!r}: sigmoid or softmax")
    shared = None
    if cfg.shared_intermediate:
        shared = lambda y: swiglu(  # noqa: E731
            y, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return expert_mlp(
        y, selected, weights, lp["w_gate"], lp["w_up"], lp["w_down"],
        n_held=cfg.n_held, expert_offset=cfg.expert_offset, shared=shared)


def _cast(lp: Params, dtype) -> Params:
    with jax.named_scope("weights.cast"):
        return {k: v if k in _FLOAT32_LEAVES else v.astype(dtype)
                for k, v in lp.items()}


def hidden_states(params: Params, tokens: jax.Array, cfg: WindowedConfig):
    """tokens: [B, T] -> (final-normed states [B, T, H], the expert layers'
    stats, a row a sparse layer in the layers' order). Master weights stay
    float32; a layer's compute-dtype copy is made inside its scan step."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        b, t, h = x.shape
        positions = jnp.broadcast_to(jnp.arange(t), tokens.shape)
    recomputed = functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES))

    @recomputed
    def first(x, lp):
        lp = _cast(lp, cfg.dtype)
        x = attention_block(cfg, "full", x, lp, positions)
        with jax.named_scope("dense.mlp"):
            y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            return x + swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"])

    def sparse(kind):
        @recomputed
        def run(x, lp):
            lp = _cast(lp, cfg.dtype)
            x = attention_block(cfg, kind, x, lp, positions)
            with jax.named_scope("windowed.glue"):
                y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps).reshape(b * t, h)
            out, stats = moe_mlp(cfg, y, lp)
            with jax.named_scope("windowed.glue"):
                return x + out.reshape(b, t, h), stats
        return run

    window, full = sparse("window"), sparse("full")

    def period(x, lps):
        x, window_stats = lax.scan(window, x, lps["window"])
        x, full_stats = full(x, lps["full"])
        return x, {"window": window_stats, "full": full_stats}

    if "first" in params:
        x = first(x, params["first"])
    x, stats = lax.scan(period, x, {"window": params["window"],
                                    "full": params["full"]})
    if "tail" in params:
        x, tail_stats = lax.scan(window, x, params["tail"])
    with jax.named_scope("windowed.glue"):
        # [periods, windows a period, ...] and [periods, ...] -> [layers, ...]
        stats = jax.tree_util.tree_map(
            lambda win, full: jnp.concatenate(
                [win, full[:, None]], axis=1).reshape(-1, *full.shape[1:]),
            stats["window"], stats["full"])
        if "tail" in params:
            stats = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b]), stats, tail_stats)
        x = rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    return x, stats


def forward(params: Params, tokens: jax.Array, cfg: WindowedConfig):
    """tokens: [B, T] int32 -> logits [B, T, vocab] float32, whole (tests
    and small batches; the loss does not call this)."""
    x, _ = hidden_states(params, tokens, cfg)
    return jnp.dot(x, params["lm_head"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


def loss_fn(params: Params, tokens: jax.Array, cfg: WindowedConfig):
    """Next-token cross-entropy (the last position predicts nothing), and
    the forward pass's stats; the head a chunk of positions at a time."""
    x, stats = hidden_states(params, tokens, cfg)
    with jax.named_scope("loss.chunk"):
        head = params["lm_head"].astype(cfg.dtype)
        return chunked_next_token_loss((x,), head, tokens,
                                       lambda nlls: nlls[0]), stats


def make_train_step(cfg: WindowedConfig, optimizer):
    """Returns jittable (params, opt_state, tokens) -> (params, opt_state,
    loss, stats)."""

    def step(params, opt_state, tokens):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, cfg)
        params, opt_state = apply_updates(optimizer, grads, opt_state, params)
        return params, opt_state, loss, stats

    return step
