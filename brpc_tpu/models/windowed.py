"""Window-and-full-attention sparse-expert transformer (``model_type:
laguna``, poolside/Laguna-XS.2, 33B-A3B): three sliding-window attention
layers of 64 query heads to one full-attention layer of 48, all over 8 KV
heads of 128, each kind with a rope of its own, a per-head sigmoid gate on
every head's output, and after the one leading dense layer 256
sigmoid-routed experts (top-8) beside one shared expert. TPU-first
functional JAX with the entry points of the other models — a frozen config
with a ``tiny()`` preset, ``init_params``, ``hidden_states``, ``forward``,
``loss_fn`` and ``make_train_step``.

The layers, as published (``config.json``). Every layer is ``x = x +
attn(norm(x)); x = x + mlp(norm(x))`` with RMSNorm (``llama.rms_norm``).
Layer ``i`` is full attention where ``i % period == 0``, else sliding-window,
so the stack is full, window, window, window, full, ...; layer 0's MLP is a
dense SwiGLU of ``intermediate``, every other layer's the experts.

- **Attention** (H = ``full_heads`` or ``window_heads`` query heads, d =
  ``head_dim``). ``q = y·W_q`` [T, H, d], ``k = y·W_k``, ``v = y·W_v`` [T,
  ``n_kv_heads``, d], ``g = y·W_g`` [T, H]. Rope, halves rotated
  (``llama.rope``'s convention). Window layers: plain rope of
  ``window_rope_theta`` on the whole head. Full layers: yarn on the leading
  ``full_rotary_factor`` of the head (``yarn_inv_freq``: interpolated and
  extrapolated frequencies blended by a linear ramp between the dimensions
  that turn ``beta_fast`` and ``beta_slow`` times over the original context,
  cos and sin times ``attention_factor``), the rest unrotated. Scores
  ``q·k / sqrt(d)`` masked to ``0 <= i - j`` (full) or ``0 <= i - j <
  window`` (window), float32 softmax, through ``llama.attention``: on a TPU
  the fused kernels, for a window the band kernels that skip the tiles
  outside it (``ops/flash_attention.py``), the dense masked form elsewhere.
  ``o_h <- sigmoid(g_h) · o_h`` per head; ``·W_o``. No biases, no q/k norm.
- **Experts.** ``s = sigmoid(y·W_r)`` in float32 over ALL ``n_experts``; the
  ``experts_per_token`` largest; weights ``s_sel / sum s_sel ·
  routed_scaling`` (``deepseek.route``, with no selection bias); experts
  SwiGLU of ``moe_intermediate``; plus one unweighted shared SwiGLU of
  ``shared_intermediate``. What follows the routing is
  ``models/experts.py``, and so is the contract of a chip's share: told
  ``n_held`` and ``expert_offset`` it routes over all, normalises over all
  the selected wherever they live, computes what its own give, drops
  nothing.

What the published config leaves to convention (the benchmark's
configuration file lists each under ``assumed``): SwiGLU with silu; the gate
per head, read from the layer's normed input; the router as above; no q/k
norm; the rotated lanes as two halves; no auxiliary balance loss.

How it runs. Layer 0 is a tree of its own (``first``). The layers after it
are stacked by kind in periods of ``period - 1`` window layers and the full
layer that follows them — ``window`` [periods, period - 1, ...], ``full``
[periods, ...] — and run under ONE ``lax.scan`` over periods whose body scans
the period's window layers and then runs its full layer; window layers left
over at the end (the published 40 layers end in three) are ``tail``, scanned
after. One compiled body of each kind whatever the depth. Each layer is
recomputed in the backward pass (``jax.checkpoint``) from its input and what
``SAVED_NAMES`` names: the attention kernel's output and log-sum-exp, and the
experts' integer routing layout. bf16 compute; float32 master weights,
router, softmax and loss (``models/chunked_loss.py``).

``make_train_step``'s step also returns ``stats``, a row an expert layer in
the layers' order, of what ``experts.expert_mlp`` counts. Named scopes:
``swa.qkv``, ``swa.rope``, ``swa.attn`` (around the kernels' own
``attn.*``), ``swa.out`` and ``full.*`` likewise, ``dense.mlp``,
``moe.router``, ``moe.sort``, ``moe.experts``, ``moe.combine``,
``moe.shared``; ``windowed.glue`` around the norms, residuals and reshapes
between them; ``embed``, ``weights.cast``, ``loss.chunk`` and ``opt.update``
as in every model.
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

from brpc_tpu.models import deepseek
from brpc_tpu.models.chunked_loss import chunked_next_token_loss
from brpc_tpu.models.experts import expert_mlp, swiglu
from brpc_tpu.models.llama import _dense_init, attention, rms_norm, rope
from brpc_tpu.models.train_step import apply_updates
from brpc_tpu.ops import grouped_matmul as gm
from brpc_tpu.ops.flash_attention import RESIDUAL_NAMES

Params = Dict[str, Any]

_FLOAT32_LEAVES = ("router",)       # never cast to the compute dtype

# What a layer keeps across its recomputation beside its input.
SAVED_NAMES = (*RESIDUAL_NAMES, gm.LAYOUT_NAME)


@dataclasses.dataclass(frozen=True)
class WindowedConfig:
    """The defaults are Laguna-XS.2 as published, every expert held."""
    vocab_size: int = 100352
    hidden: int = 2048
    n_layers: int = 40
    period: int = 4                   # a full layer, then period - 1 window
    full_heads: int = 48
    window_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512
    window_rope_theta: float = 10000.0
    full_rope_theta: float = 500000.0         # the full layers' yarn ...
    full_rotary_factor: float = 0.5
    yarn_factor: float = 64.0
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672   # 0.1 ln 64 + 1
    intermediate: int = 8192          # layer 0's dense SwiGLU
    n_experts: int = 256              # the router's width, always whole
    experts_per_token: int = 8
    moe_intermediate: int = 512       # one expert's SwiGLU
    shared_intermediate: int = 512    # the shared expert's
    routed_scaling: float = 2.5
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

    @property
    def layer_kinds(self) -> tuple:
        """``"full"`` or ``"window"`` for every layer, by the published
        pattern."""
        return tuple("full" if i % self.period == 0 else "window"
                     for i in range(self.n_layers))

    @property
    def stacks(self) -> tuple:
        """(whole periods after layer 0, window layers left over)."""
        return divmod(self.n_layers - 1, self.period)


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
    ``attention_factor``; the other lanes pass."""
    rot = int(cfg.head_dim * cfg.full_rotary_factor)
    angles = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    cos = (jnp.cos(angles) * cfg.yarn_attention_factor)[:, :, None, :]
    sin = (jnp.sin(angles) * cfg.yarn_attention_factor)[:, :, None, :]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x1 * sin + x2 * cos).astype(x.dtype), x[..., rot:]], axis=-1)


def init_params(key: jax.Array, cfg: WindowedConfig) -> Params:
    """Matrices normal(0, fan_in^-1/2), norms 1."""
    h, pd, d = cfg.hidden, cfg.param_dtype, cfg.head_dim
    periods, n_tail = cfg.stacks
    kv_out = cfg.n_kv_heads * d
    k_emb, k_first, k_win, k_full, k_tail, k_out = jax.random.split(key, 6)

    def stack(key, lead, heads, mlp):
        ks = iter(jax.random.split(key, 16))
        layers = {name: _dense_init(next(ks), lead + shape, pd, fan_in)
                  for name, shape, fan_in in (
                      ("wq", (h, heads * d), h), ("wk", (h, kv_out), h),
                      ("wv", (h, kv_out), h), ("wg", (h, heads), h),
                      ("wo", (heads * d, h), heads * d), *mlp)}
        layers["attn_norm"] = jnp.ones(lead + (h,), pd)
        layers["mlp_norm"] = jnp.ones(lead + (h,), pd)
        return layers

    f, fs = cfg.moe_intermediate, cfg.shared_intermediate
    dense = (("w_gate", (h, cfg.intermediate), h),
             ("w_up", (h, cfg.intermediate), h),
             ("w_down", (cfg.intermediate, h), cfg.intermediate))
    sparse = (("router", (h, cfg.n_experts), h),
              ("w_gate", (cfg.n_held, h, f), h),
              ("w_up", (cfg.n_held, h, f), h),
              ("w_down", (cfg.n_held, f, h), f),
              ("shared_gate", (h, fs), h), ("shared_up", (h, fs), h),
              ("shared_down", (fs, h), fs))
    params = {
        "embed": _dense_init(k_emb, (cfg.vocab_size, h), pd, 1.0),
        "first": stack(k_first, (), cfg.full_heads, dense),
        "window": stack(k_win, (periods, cfg.period - 1), cfg.window_heads,
                        sparse),
        "full": stack(k_full, (periods,), cfg.full_heads, sparse),
        "final_norm": jnp.ones((h,), pd),
        "lm_head": _dense_init(k_out, (h, cfg.vocab_size), pd, h),
    }
    if n_tail:
        params["tail"] = stack(k_tail, (n_tail,), cfg.window_heads, sparse)
    return params


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
        q = (y @ lp["wq"]).reshape(b, t, nh, d)
        k = (y @ lp["wk"]).reshape(b, t, nkv, d)
        v = (y @ lp["wv"]).reshape(b, t, nkv, d)
        gate = jnp.dot(y, lp["wg"], preferred_element_type=jnp.float32)
    with jax.named_scope(f"{scope}.rope"):
        if full:
            q, k = yarn_rope(cfg, q, positions), yarn_rope(cfg, k, positions)
        else:
            q = rope(q, positions, cfg.window_rope_theta)
            k = rope(k, positions, cfg.window_rope_theta)
    with jax.named_scope(f"{scope}.attn"):
        o = attention(q, k, v, window=None if full else cfg.window)
    with jax.named_scope(f"{scope}.out"):
        o = (o.reshape(b, t, nh, d).astype(jnp.float32)
             * jax.nn.sigmoid(gate)[..., None]).astype(x.dtype)
        return x + o.reshape(b, t, nh * d) @ lp["wo"]


def moe_mlp(cfg: WindowedConfig, y: jax.Array, lp: Params):
    """The expert layer's MLP on normed tokens y: [N, H] -> ([N, H], stats):
    what the held experts give for the assignments routed to them, plus the
    shared expert."""
    with jax.named_scope("moe.router"):
        # DeepSeek-V3's router without its selection bias
        selected, weights = deepseek.route(cfg, y, lp["router"], 0.0)
    return expert_mlp(
        y, selected, weights, lp["w_gate"], lp["w_up"], lp["w_down"],
        n_held=cfg.n_held, expert_offset=cfg.expert_offset,
        shared=lambda y: swiglu(y, lp["shared_gate"], lp["shared_up"],
                                lp["shared_down"]))


def _cast(lp: Params, dtype) -> Params:
    with jax.named_scope("weights.cast"):
        return {k: v if k in _FLOAT32_LEAVES else v.astype(dtype)
                for k, v in lp.items()}


def hidden_states(params: Params, tokens: jax.Array, cfg: WindowedConfig):
    """tokens: [B, T] -> (final-normed states [B, T, H], the expert layers'
    stats, a row a layer from layer 1 on). Master weights stay float32; a
    layer's compute-dtype copy is made inside its scan step."""
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

    x = first(x, params["first"])
    x, stats = lax.scan(period, x, {"window": params["window"],
                                    "full": params["full"]})
    if "tail" in params:
        x, tail_stats = lax.scan(window, x, params["tail"])
    with jax.named_scope("windowed.glue"):
        # [periods, period - 1, ...] and [periods, ...] -> [layers, ...]
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
