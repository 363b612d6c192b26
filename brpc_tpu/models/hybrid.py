"""Hybrid linear-attention / full-attention sparse-expert transformer
(``model_type: qwen3_next``, Qwen/Qwen3-Next-80B-A3B-Instruct): three Gated
DeltaNet layers to one gated full-attention layer, every layer followed by
512 softmax-routed experts (top-10) beside one gated shared expert. TPU-first
functional JAX with the entry points of the other models — a frozen config
with a ``tiny()`` preset, ``init_params``, ``hidden_states``, ``forward``,
``loss_fn`` and ``make_train_step``.

The layers, as published (``config.json``; HF ``modeling_qwen3_next.py``).
Every layer is ``x = x + mixer(norm(x)); x = x + moe(norm(x))``; layer ``i``
is full attention where ``(i + 1) % full_attention_interval == 0``, else
linear, so one period is linear, linear, linear, full. ``norm`` is RMSNorm in
float32 with weight ``(1 + w)``, ``w`` initialised 0 — also the final norm
and the per-head q/k norms.

- **Gated DeltaNet layer** (``linear_key_heads`` key heads, twice as many
  value heads, all of 128). ``[q, k, v, z] = y·W_qkvz``, ``[b, a] = y·W_ba``;
  ``[q, k, v] <- silu(conv(concat(q, k, v)))``, a causal depthwise
  convolution of ``conv_kernel`` taps over the channels, no bias
  (``ops/causal_conv.py``: one Pallas kernel a pass on a TPU);
  ``beta = sigmoid(b)``; ``g = -exp(A_log) · softplus(a + dt_bias)`` in
  float32, one ``A_log`` and ``dt_bias`` a value head; q and k L2-normalised
  per head, each key head serving ``value/key`` value heads, q scaled by
  dk^-1/2. Per value head, from ``S = 0``: ``S <- exp(g_t) S; delta =
  beta_t (v_t - S^T k_t); S <- S + k_t delta^T; o_t = S^T q_t``
  (``ops/gated_delta.py``: chunks of 64 in the WY form, three Pallas
  kernels on a TPU). Output ``RMSNorm_128(o) · w · silu(z)`` per head
  (``w`` initialised 1), then ``·W_out``.
- **Gated full attention** (16 query heads over 2 KV heads of 256).
  ``y·W_q`` gives each head its query (first half) and its gate (second);
  q and k RMS-normed per head; rope, halves rotated, on the first
  ``partial_rotary_factor`` of each head; causal softmax attention scaled by
  head_dim^-1/2 through ``llama.attention`` (the fused kernels on a TPU);
  ``o · sigmoid(gate)``; ``·W_o``. No biases.
- **Expert layer** (every layer). ``p = softmax(y·W_g)`` in float32 over ALL
  ``n_experts``; the ``experts_per_token`` largest; weights ``p_sel /
  sum p_sel``; experts SwiGLU of ``moe_intermediate``; plus ``sigmoid(y·w_s) ·
  SwiGLU(y)``, the shared expert. ``route`` is the softmax router of every
  model that has one (``models/windowed.py`` calls it for Mellum2, as it
  calls ``deepseek.route`` for Laguna-XS.2); what follows the routing is
  ``models/experts.py``, shared with ``models/deepseek.py``, and so is the
  contract of a chip's share: told ``n_held`` and ``expert_offset`` it routes
  over all, normalises over all the selected wherever they live, computes
  what its own give, drops nothing.

Departures from the published model, none changing a width: the released
code lays ``W_qkvz``'s columns out grouped by key head, here they lie q | k |
v | z (a column permutation of random weights; loading published weights
would need it), and likewise ``W_ba`` is b | a and ``W_q`` q | gate inside
each head as published; no multi-token-prediction head; no auxiliary balance
loss; ``intermediate_size`` belongs to dense layers, of which there are none
(``mlp_only_layers`` empty, ``decoder_sparse_step`` 1).

How it runs. Per-layer tensors are stacked by kind: the linear layers'
[periods, interval - 1, ...], the full layers' [periods, ...]. The stack is
ONE ``lax.scan`` over periods whose body scans the period's linear layers and
then runs its full layer, so one compiled body of each kind whatever the
depth. Each layer is recomputed in the backward pass (``jax.checkpoint``)
from its input and what ``SAVED_NAMES`` names: the attention kernel's output
and log-sum-exp, the experts' integer routing layout and the rule's T
(``gated_delta.INVERSE_NAME``: every chunk's (I + A)^-1, 33.5 MB a linear
layer at 8,192 tokens, as much as the layer's saved input; it depends on no
state, so ``gdn_chunk_prep`` runs once a layer and step and the recomputation
and the backward kernel read what it made). The rule's output and chunk-entry
states carry names too (``gated_delta.RESIDUAL_NAMES``) but are NOT saved:
the forward kernel runs twice a layer, since keeping them (201 MB a layer)
left the compiled step 0.07 GiB of the chip (PERF.md section 4). bf16
compute; float32 master weights, norms, router, decays, softmax and loss
(``models/chunked_loss.py``).

The short convolution and its silu are ``causal_conv.conv_silu`` on the whole
``qkvz``: on a TPU, at bf16 and whole tiles, the kernels ``conv_silu_fwd`` and
``conv_silu_bwd`` reach q | k | v — the taps' width — by block index, so no
slice is copied and nothing is padded. A grid step holds a block of positions
by 512 channels in VMEM and widens it to float32 there a run of rows at a
time; the K - 1 rows of history (in the backward pass also the K - 1 rows
ahead) are one 16-row bf16 tile from a second block spec on the same array,
zeros at the sequence's ends. bf16 in, float32 taps, products, sums, silu and
silu', bf16 out; ``dtaps`` a float32 sum that stays in VMEM across the
positions. The result is not saved: the forward kernel runs again in a
layer's recomputation (y is 134 MB a layer), the backward kernel recomputes
the pre-activation from x. ``conv_lowerings`` counts the programs lowered
with the kernels; their names keep clear of ``gdn_``, the prefix by which the
benchmark takes an op for one of the rule's kernels. Elsewhere the plain
form (``causal_conv.causal_conv`` and ``jax.nn.silu``) runs.

``make_train_step``'s step also returns ``stats``, a row a layer in the
layers' order, of what ``experts.expert_mlp`` counts (``routed``,
``dropped``, ``group_max``, ``group_mean``, ``rows_in_use``, ``selected``).
Named scopes: ``gdn.in_proj``, ``gdn.conv``, ``gdn.rule``, ``gdn.out``,
``gattn.qkv``, ``gattn.out``, ``moe.router``, ``moe.sort``, ``moe.experts``,
``moe.combine``, ``moe.shared``; ``hybrid.glue`` around the norms, residuals
and reshapes between them, ``embed``, ``weights.cast``, ``loss.chunk`` and
``opt.update`` as in every model.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from brpc_tpu.models.chunked_loss import chunked_next_token_loss
from brpc_tpu.models.experts import chosen, expert_mlp, swiglu
from brpc_tpu.models.llama import _dense_init, attention, rope
from brpc_tpu.models.train_step import apply_updates
from brpc_tpu.ops import gated_delta
from brpc_tpu.ops import grouped_matmul as gm
from brpc_tpu.ops.causal_conv import conv_silu
from brpc_tpu.ops.flash_attention import RESIDUAL_NAMES

Params = Dict[str, Any]

# never cast to the compute dtype: norms' weights (1 + w loses w in bf16),
# the router, the decay's two vectors, and the convolution's taps, whose
# gradient is a sum over every position
_FLOAT32_LEAVES = ("router", "mixer_norm", "mlp_norm", "q_norm", "k_norm",
                   "out_norm", "a_log", "dt_bias", "conv")

# What a layer keeps across its recomputation beside its input.
SAVED_NAMES = (*RESIDUAL_NAMES, gm.LAYOUT_NAME, gated_delta.INVERSE_NAME)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """The defaults are Qwen3-Next-80B-A3B-Instruct as published, every
    expert held."""
    vocab_size: int = 151936
    hidden: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    n_experts: int = 512              # the router's width, always whole
    experts_per_token: int = 10
    moe_intermediate: int = 512       # one expert's SwiGLU
    shared_intermediate: int = 512    # the shared expert's
    norm_eps: float = 1e-6
    n_held: int = 512                 # experts this chip holds ...
    expert_offset: int = 0            # ... from this one on
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def tiny(vocab_size: int = 256, n_held: int = 2,
             expert_offset: int = 0) -> "HybridConfig":
        """A toy config for tests / dry runs: one period (3 linear + 1 full
        layer), 8 experts of which ``n_held`` live here, top-2."""
        return HybridConfig(
            vocab_size=vocab_size, hidden=64, n_layers=4, n_heads=4,
            n_kv_heads=2, head_dim=32, linear_key_heads=2,
            linear_value_heads=4, linear_key_dim=16, linear_value_dim=16,
            n_experts=8, experts_per_token=2, moe_intermediate=32,
            shared_intermediate=32, n_held=n_held,
            expert_offset=expert_offset)

    @property
    def layer_kinds(self) -> tuple:
        """``"linear"`` or ``"full"`` for every layer, by the published
        rule."""
        return tuple(
            "full" if (i + 1) % self.full_attention_interval == 0
            else "linear" for i in range(self.n_layers))


def init_params(key: jax.Array, cfg: HybridConfig) -> Params:
    """Matrices normal(0, fan_in^-1/2), zero-centred norms 0, the gated
    norm's weight 1, ``a_log`` = log U(0, 16), ``dt_bias`` 1, the
    convolution's taps normal(0, kernel^-1/2)."""
    h, pd, f = cfg.hidden, cfg.param_dtype, cfg.moe_intermediate
    periods, rest = divmod(cfg.n_layers, cfg.full_attention_interval)
    if rest:
        raise ValueError(f"{cfg.n_layers} layers are no whole periods of "
                         f"{cfg.full_attention_interval}")
    key_dim = cfg.linear_key_heads * cfg.linear_key_dim
    value_dim = cfg.linear_value_heads * cfg.linear_value_dim
    q_out = cfg.n_heads * cfg.head_dim
    kv_out = cfg.n_kv_heads * cfg.head_dim
    k_emb, k_lin, k_full, k_out = jax.random.split(key, 4)

    def stack(key, lead, mixer):
        ks = iter(jax.random.split(key, 16))

        def mat(shape, fan_in):
            return _dense_init(next(ks), lead + shape, pd, fan_in)

        layers = {name: mat(shape, fan_in) for name, shape, fan_in in (
            *mixer,
            ("router", (h, cfg.n_experts), h),
            ("w_gate", (cfg.n_held, h, f), h),
            ("w_up", (cfg.n_held, h, f), h),
            ("w_down", (cfg.n_held, f, h), f),
            ("shared_gate", (h, cfg.shared_intermediate), h),
            ("shared_up", (h, cfg.shared_intermediate), h),
            ("shared_down", (cfg.shared_intermediate, h),
             cfg.shared_intermediate),
            ("shared_w", (h,), h))}
        layers["mixer_norm"] = jnp.zeros(lead + (h,), pd)
        layers["mlp_norm"] = jnp.zeros(lead + (h,), pd)
        return layers, next(ks)

    lead = (periods, cfg.full_attention_interval - 1)
    linear, k_a = stack(k_lin, lead, (
        ("w_qkvz", (h, 2 * key_dim + 2 * value_dim), h),
        ("w_ba", (h, 2 * cfg.linear_value_heads), h),
        ("conv", (cfg.conv_kernel, 2 * key_dim + value_dim),
         cfg.conv_kernel),
        ("w_out", (value_dim, h), value_dim)))
    linear["a_log"] = jnp.log(jax.random.uniform(
        k_a, lead + (cfg.linear_value_heads,), jnp.float32, 1e-3, 16.0)
    ).astype(pd)
    linear["dt_bias"] = jnp.ones(lead + (cfg.linear_value_heads,), pd)
    linear["out_norm"] = jnp.ones(lead + (cfg.linear_value_dim,), pd)
    full, _ = stack(k_full, (periods,), (
        ("wq", (h, 2 * q_out), h), ("wk", (h, kv_out), h),
        ("wv", (h, kv_out), h), ("wo", (q_out, h), q_out)))
    full["q_norm"] = jnp.zeros((periods, cfg.head_dim), pd)
    full["k_norm"] = jnp.zeros((periods, cfg.head_dim), pd)
    return {
        "embed": _dense_init(k_emb, (cfg.vocab_size, h), pd, 1.0),
        "linear": linear,
        "full": full,
        "final_norm": jnp.zeros((h,), pd),
        "lm_head": _dense_init(k_out, (h, cfg.vocab_size), pd, h),
    }


def norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in float32 with the zero-centred weight (1 + w)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def gated_delta_net(cfg: HybridConfig, x: jax.Array, lp: Params) -> jax.Array:
    """The linear-attention block with its residual. x: [B, T, H]."""
    b, t, _ = x.shape
    hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    key_dim, value_dim = hk * dk, hv * dv
    with jax.named_scope("hybrid.glue"):
        y = norm(x, lp["mixer_norm"], cfg.norm_eps)
    with jax.named_scope("gdn.in_proj"):
        qkvz = y @ lp["w_qkvz"]
        ba = jnp.dot(y, lp["w_ba"], preferred_element_type=jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., hv:] + lp["dt_bias"].astype(jnp.float32))
    with jax.named_scope("gdn.conv"):
        # q | k | v are the taps' width of qkvz: the kernels reach them by
        # block index, nothing is sliced
        qkv = conv_silu(qkvz, lp["conv"])
    with jax.named_scope("gdn.rule"):
        def unit(a):                       # L2 norm per head, in float32
            a32 = a.astype(jnp.float32)
            return a32 * lax.rsqrt(
                jnp.sum(a32 * a32, axis=-1, keepdims=True) + 1e-6)

        q = (unit(qkv[..., :key_dim].reshape(b, t, hk, dk))
             * dk ** -0.5).astype(x.dtype)
        k = unit(qkv[..., key_dim:2 * key_dim].reshape(b, t, hk, dk)
                 ).astype(x.dtype)
        v = qkv[..., 2 * key_dim:].reshape(b, t, hv, dv)
        o = gated_delta.gated_delta_rule(q, k, v, g, beta)
    with jax.named_scope("gdn.out"):
        z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, hv, dv)
        o32 = o.astype(jnp.float32)
        var = jnp.mean(o32 * o32, axis=-1, keepdims=True)
        o = (o32 * lax.rsqrt(var + cfg.norm_eps)
             * lp["out_norm"].astype(jnp.float32)
             * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
        return x + o.reshape(b, t, value_dim) @ lp["w_out"]


def gated_attention(cfg: HybridConfig, x: jax.Array, lp: Params,
                    positions: jax.Array) -> jax.Array:
    """The full-attention block with its residual. x: [B, T, H]."""
    b, t, _ = x.shape
    nh, nkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rot = int(d * cfg.partial_rotary_factor)
    with jax.named_scope("hybrid.glue"):
        y = norm(x, lp["mixer_norm"], cfg.norm_eps)
    with jax.named_scope("gattn.qkv"):
        q_gate = (y @ lp["wq"]).reshape(b, t, nh, 2 * d)
        q = norm(q_gate[..., :d], lp["q_norm"], cfg.norm_eps)
        gate = q_gate[..., d:].reshape(b, t, nh * d)
        k = norm((y @ lp["wk"]).reshape(b, t, nkv, d), lp["k_norm"],
                 cfg.norm_eps)
        v = (y @ lp["wv"]).reshape(b, t, nkv, d)

        def partial_rope(a):
            return jnp.concatenate(
                [rope(a[..., :rot], positions, cfg.rope_theta),
                 a[..., rot:]], axis=-1)

        q, k = partial_rope(q), partial_rope(k)
    o = attention(q, k, v)
    with jax.named_scope("gattn.out"):
        o = (o.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
        return x + o @ lp["wo"]


def route(cfg: HybridConfig, y: jax.Array, router: jax.Array):
    """y: [N, H] -> (selected experts [N, k] int32, their weights [N, k]
    float32): softmax over all the experts, the k largest, renormalised
    (``norm_topk_prob: true``). ``cfg``: any config with
    ``experts_per_token`` (this model's, ``WindowedConfig``)."""
    p = jax.nn.softmax(jnp.dot(y.astype(jnp.float32), router,
                               precision=lax.Precision.HIGHEST), axis=-1)
    _, selected = lax.top_k(p, cfg.experts_per_token)
    selected = checkpoint_name(selected.astype(jnp.int32), gm.LAYOUT_NAME)
    # from the saved selection, not from top_k's values: a layer's
    # recomputation then runs the softmax and no top_k (as deepseek.route)
    w = chosen(p, selected)
    return selected, w / jnp.sum(w, axis=1, keepdims=True)


def moe_mlp(cfg: HybridConfig, y: jax.Array, lp: Params):
    """The expert layer's MLP on normed tokens y: [N, H] -> ([N, H], stats):
    what the held experts give for the assignments routed to them, plus the
    shared expert behind its gate."""
    with jax.named_scope("moe.router"):
        selected, weights = route(cfg, y, lp["router"])

    def shared(y):
        gate = jax.nn.sigmoid(jnp.dot(y, lp["shared_w"],
                                      preferred_element_type=jnp.float32))
        out = swiglu(y, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
        return (gate[:, None] * out.astype(jnp.float32)).astype(y.dtype)

    return expert_mlp(
        y, selected, weights, lp["w_gate"], lp["w_up"], lp["w_down"],
        n_held=cfg.n_held, expert_offset=cfg.expert_offset, shared=shared)


def _cast(lp: Params, dtype) -> Params:
    with jax.named_scope("weights.cast"):
        return {k: v if k in _FLOAT32_LEAVES else v.astype(dtype)
                for k, v in lp.items()}


def hidden_states(params: Params, tokens: jax.Array, cfg: HybridConfig):
    """tokens: [B, T] -> (final-normed states [B, T, H], the expert layers'
    stats, a row a layer). Master weights stay float32; a layer's compute-dtype
    copy is made inside its scan step."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        b, t, h = x.shape
        positions = jnp.broadcast_to(jnp.arange(t), tokens.shape)

    def layer(mixer):
        @functools.partial(
            jax.checkpoint,
            policy=jax.checkpoint_policies.save_only_these_names(
                *SAVED_NAMES))
        def run(x, lp):
            lp = _cast(lp, cfg.dtype)
            x = mixer(x, lp)
            with jax.named_scope("hybrid.glue"):
                y = norm(x, lp["mlp_norm"], cfg.norm_eps).reshape(b * t, h)
            out, stats = moe_mlp(cfg, y, lp)
            with jax.named_scope("hybrid.glue"):
                return x + out.reshape(b, t, h), stats
        return run

    linear = layer(lambda x, lp: gated_delta_net(cfg, x, lp))
    full = layer(lambda x, lp: gated_attention(cfg, x, lp, positions))

    def period(x, lps):
        x, linear_stats = lax.scan(linear, x, lps["linear"])
        x, full_stats = full(x, lps["full"])
        return x, {"linear": linear_stats, "full": full_stats}

    x, stats = lax.scan(period, x, {"linear": params["linear"],
                                    "full": params["full"]})
    with jax.named_scope("hybrid.glue"):
        # [periods, interval - 1, ...] and [periods, ...] -> [layers, ...]
        stats = jax.tree_util.tree_map(
            lambda lin, full: jnp.concatenate(
                [lin, full[:, None]], axis=1).reshape(-1, *full.shape[1:]),
            stats["linear"], stats["full"])
        return norm(x, params["final_norm"], cfg.norm_eps), stats


def forward(params: Params, tokens: jax.Array, cfg: HybridConfig):
    """tokens: [B, T] int32 -> logits [B, T, vocab] float32, whole (tests
    and small batches; the loss does not call this)."""
    x, _ = hidden_states(params, tokens, cfg)
    return jnp.dot(x, params["lm_head"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


def loss_fn(params: Params, tokens: jax.Array, cfg: HybridConfig):
    """Next-token cross-entropy (the last position predicts nothing), and
    the forward pass's stats; the head a chunk of positions at a time."""
    x, stats = hidden_states(params, tokens, cfg)
    with jax.named_scope("loss.chunk"):
        head = params["lm_head"].astype(cfg.dtype)
        return chunked_next_token_loss((x,), head, tokens,
                                       lambda nlls: nlls[0]), stats


def make_train_step(cfg: HybridConfig, optimizer):
    """Returns jittable (params, opt_state, tokens) -> (params, opt_state,
    loss, stats)."""

    def step(params, opt_state, tokens):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, cfg)
        params, opt_state = apply_updates(optimizer, grads, opt_state, params)
        return params, opt_state, loss, stats

    return step
