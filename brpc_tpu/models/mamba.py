"""Hybrid Mamba-2 / attention dense transformer (``model_type:
granitemoehybrid``, ibm-granite/granite-4.0-h-micro): nine Mamba-2
state-space layers to one attention layer without positions, a dense SwiGLU
MLP after every mixer, and ONE matrix for the embedding and the output head.
TPU-first functional JAX with the entry points of the other models — a
frozen config with a ``tiny()`` preset, ``init_params``, ``hidden_states``,
``forward``, ``loss_fn`` and ``make_train_step``.

The layers, as published (``config.json``; HF ``modeling_granitemoehybrid``).
``h_0 = embedding_multiplier · E[token]``; every layer is ``h = h +
residual_multiplier · mixer(norm(h))`` then ``h = h + residual_multiplier ·
MLP(norm(h))``, ``norm`` RMSNorm in float32 with weight ``w`` (initialised
1); the mixer is what ``layer_types`` says at that depth; the logits are
``norm(h) E^T / logits_scaling``.

- **Mamba-2 mixer** (``ssm_heads`` heads of ``ssm_head_dim``, one group: a
  state of ``ssm_state`` shared by all heads' B and C). ``[xBC | z] =
  y·W_in`` and ``dt = y·W_dt``; ``xBC <- silu(conv(xBC) + b)``, a causal
  depthwise convolution of ``conv_kernel`` taps with a bias
  (``ops/causal_conv.py``: one Pallas kernel a pass on a TPU); ``[x | B |
  C] = xBC``; ``Delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one
  value a head, in float32. Per head, from ``S = 0``: ``S_t = exp(Delta_t A)
  S_{t-1} + Delta_t B_t x_t^T``, ``y_t = S_t^T C_t + D x_t``
  (``ops/ssd.py``: the chunked SSD form, two Pallas kernels on a TPU, two
  heads of 64 side by side in the lanes). Output ``RMSNorm(y · silu(z)) ·
  w``: the gate first, then ONE norm over all ``ssm_heads · ssm_head_dim``
  channels; then ``·W_out``. No biases but the convolution's.
- **Attention layer** (``n_heads`` query heads over ``n_kv_heads``, heads of
  ``hidden / n_heads``). No rope (``position_embedding_type: nope``), no
  biases; causal softmax of ``q·k · attention_multiplier`` through
  ``flash_attention.attention`` (the fused kernels on a TPU, at heads of
  64). The kernels scale by head_dim^-1/2, so q is multiplied by
  ``attention_multiplier · head_dim^1/2`` first (2^-3 as published: exact in
  bf16).
- **MLP** (every layer): SwiGLU of ``intermediate`` (the published
  ``shared_intermediate_size``; ``num_local_experts`` is 0).
- **Head**: tied, the embedding's float32 leaf read by the gather and, as
  its transpose, by the head; its gradient is the sum of both. The final
  states are divided by ``logits_scaling`` (8: exact in bf16) before the
  head.

Departures from the published model, none changing a width: the released
``in_proj`` is one [H, 2 d_in + 2 N + heads] matrix laid z | xBC | dt; here
it is two leaves, ``in_proj`` laid xBC | z (so the convolution's kernels
reach xBC as the first columns by block index) and ``dt_proj``, whose
product is taken in float32 (a column permutation and split of random
weights; loading published weights would need it); the time step is not
clamped (``time_step_limit`` (0, inf), the released default); no auxiliary
loss.

How it runs. The stack is laid out from ``layer_types``: a period is the
span from one attention layer to the next (the published table: five Mamba,
attention, four Mamba, four times), the Mamba layers stacked
[periods, Mamba layers a period, ...] and the attention layers
[periods, ...]. ONE ``lax.scan`` runs over periods; its body scans the
period's Mamba layers and, before the Mamba layer at the attention layer's
place, runs the attention layer (``lax.cond`` on the place: one compiled
body of each kind whatever the depth, and no slice of the stacked weights
is copied). The 40-layer table and the 10-layer cut lay out alike. Each
layer is recomputed in the backward pass (``jax.checkpoint``) from its input
and what ``SAVED_NAMES`` names (the attention kernel's output and
log-sum-exp). bf16 compute; float32 master weights, norms, the step sizes,
decays, the scan's state, softmax and loss (``models/chunked_loss.py``).

Named scopes: ``ssm.in_proj``, ``ssm.conv``, ``ssm.scan`` (the ``ssd_*``
kernels and ``D x``), ``ssm.norm`` (the gate and the gated norm),
``ssm.out``, ``gqa.qkv``, ``gqa.out``, ``mlp``; ``ssm.glue`` around the
norms, residuals and multipliers of every layer; ``embed``,
``weights.cast``, ``loss.chunk`` and ``opt.update`` as in every model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from brpc_tpu.models import train_step
from brpc_tpu.models.chunked_loss import chunked_next_token_loss
from brpc_tpu.models.experts import swiglu
from brpc_tpu.models.layers import dense_init
from brpc_tpu.ops.causal_conv import conv_silu
from brpc_tpu.ops.flash_attention import RESIDUAL_NAMES, attention
from brpc_tpu.ops.ssd import ssd

Params = Dict[str, Any]

GLUE = "ssm.glue"
# never cast to the compute dtype: the norms' weights, the convolution's
# taps and bias (sums over every position in the gradient), the step size's
# bias, the decay and the skip
_FLOAT32_LEAVES = ("mixer_norm", "mlp_norm", "out_norm", "conv", "conv_bias",
                   "a_log", "dt_bias", "d")

# What a layer keeps across its recomputation beside its input.
SAVED_NAMES = RESIDUAL_NAMES

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    """The defaults are granite-4.0-h-micro as published."""
    vocab_size: int = 100352
    hidden: int = 2048
    n_layers: int = 40
    layer_types: tuple = _PERIOD * 4      # read up to n_layers
    n_heads: int = 32
    n_kv_heads: int = 8
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    conv_kernel: int = 4
    intermediate: int = 8192
    norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MambaConfig":
        """A toy config for tests / dry runs: one period of the published
        table (5 Mamba, attention, 4 Mamba), hidden 64, 4 SSM heads of 16
        over a state of 16, 4 query heads over 2 KV heads of 16."""
        return MambaConfig(
            vocab_size=vocab_size, hidden=64, n_layers=10, n_heads=4,
            n_kv_heads=2, ssm_heads=4, ssm_head_dim=16, ssm_state=16,
            intermediate=128)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """xBC's channels: the heads' x, then B and C."""
        return self.d_inner + 2 * self.ssm_state

    @property
    def layout(self) -> tuple:
        """(periods, Mamba layers a period, the attention layer's place
        among them) of the layers held, from ``layer_types``; a table that
        is not whole periods of one attention layer is a ``ValueError``."""
        kinds = tuple(self.layer_types[:self.n_layers])
        if len(kinds) != self.n_layers or set(kinds) - {"mamba", "attention"}:
            raise ValueError(f"layer_types {kinds} do not name {self.n_layers}"
                             f" layers of 'mamba' and 'attention'")
        periods = kinds.count("attention")
        length = self.n_layers // max(periods, 1)
        period = kinds[:length]
        if (periods == 0 or period.count("attention") != 1
                or period * periods != kinds):
            raise ValueError(f"layer_types {kinds} are no whole periods of "
                             f"one attention layer")
        return periods, length - 1, period.index("attention")


def init_params(key: jax.Array, cfg: MambaConfig) -> Params:
    """Matrices normal(0, fan_in^-1/2) (the embedding by the hidden size, as
    the head reads it), the convolution's taps normal(0, kernel^-1/2) and
    bias U(-kernel^-1/2, kernel^-1/2), norms 1; Mamba-2's usual decays and
    steps: ``a_log`` = log U(1, 16), ``dt_bias`` = softplus^-1 of a step
    log-uniform in [1e-3, 0.1], ``d`` 1."""
    h, pd, f = cfg.hidden, cfg.param_dtype, cfg.intermediate
    periods, n_mamba, _ = cfg.layout
    nh, d = cfg.n_heads, cfg.head_dim
    kv = cfg.n_kv_heads * d
    k_emb, k_m, k_a = jax.random.split(key, 3)

    def stack(key, lead, mixer):
        ks = iter(jax.random.split(key, 16))

        def mat(shape, fan_in):
            return dense_init(next(ks), lead + shape, pd, fan_in)

        layers = {name: mat(shape, fan_in) for name, shape, fan_in in (
            *mixer, ("w_gate", (h, f), h), ("w_up", (h, f), h),
            ("w_down", (f, h), f))}
        layers["mixer_norm"] = jnp.ones(lead + (h,), pd)
        layers["mlp_norm"] = jnp.ones(lead + (h,), pd)
        return layers, ks

    lead = (periods, n_mamba)
    mamba, ks = stack(k_m, lead, (
        ("in_proj", (h, cfg.conv_dim + cfg.d_inner), h),
        ("dt_proj", (h, cfg.ssm_heads), h),
        ("conv", (cfg.conv_kernel, cfg.conv_dim), cfg.conv_kernel),
        ("out_proj", (cfg.d_inner, h), cfg.d_inner)))
    bound = cfg.conv_kernel ** -0.5
    mamba["conv_bias"] = jax.random.uniform(
        next(ks), lead + (cfg.conv_dim,), pd, -bound, bound)
    heads = lead + (cfg.ssm_heads,)
    mamba["a_log"] = jnp.log(jax.random.uniform(next(ks), heads, pd, 1.0,
                                                16.0))
    step = jnp.exp(jax.random.uniform(next(ks), heads, pd, jnp.log(1e-3),
                                      jnp.log(0.1)))
    mamba["dt_bias"] = step + jnp.log(-jnp.expm1(-step))   # softplus^-1
    mamba["d"] = jnp.ones(heads, pd)
    mamba["out_norm"] = jnp.ones(lead + (cfg.d_inner,), pd)
    attn, _ = stack(k_a, (periods,), (
        ("wq", (h, nh * d), h), ("wk", (h, kv), h), ("wv", (h, kv), h),
        ("wo", (nh * d, h), nh * d)))
    return {
        "embed": dense_init(k_emb, (cfg.vocab_size, h), pd, h),
        "mamba": mamba,
        "attention": attn,
        "final_norm": jnp.ones((h,), pd),
    }


def norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in float32 with the weight w."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(
        x.dtype)


def _residual(cfg: MambaConfig, x: jax.Array, out: jax.Array) -> jax.Array:
    """x + residual_multiplier · out, in float32, under the glue scope."""
    with jax.named_scope(GLUE):
        return (x.astype(jnp.float32) + cfg.residual_multiplier
                * out.astype(jnp.float32)).astype(x.dtype)


def mamba_mixer(cfg: MambaConfig, x: jax.Array, lp: Params) -> jax.Array:
    """The Mamba-2 block on normed x [B, T, H] -> [B, T, H], before the
    residual."""
    b, t, _ = x.shape
    nh, p = cfg.ssm_heads, cfg.ssm_head_dim
    with jax.named_scope("ssm.in_proj"):
        xbcz = x @ lp["in_proj"]
        dt = jax.nn.softplus(
            jnp.dot(x, lp["dt_proj"], preferred_element_type=jnp.float32)
            + lp["dt_bias"])
        a = dt * -jnp.exp(lp["a_log"])
    with jax.named_scope("ssm.conv"):
        # xBC is the taps' width of xbcz: the kernels reach it by block
        # index, nothing is sliced
        xbc = conv_silu(xbcz, lp["conv"], lp["conv_bias"])
    with jax.named_scope("ssm.scan"):
        y = ssd(xbc, dt, a, head_dim=p).astype(jnp.float32)
        skip = xbc[..., :cfg.d_inner].reshape(b, t, nh, p).astype(
            jnp.float32) * lp["d"][:, None]
        y = y + skip.reshape(b, t, cfg.d_inner)
    with jax.named_scope("ssm.norm"):
        gated = y * jax.nn.silu(xbcz[..., cfg.conv_dim:].astype(jnp.float32))
        var = jnp.mean(gated * gated, axis=-1, keepdims=True)
        gated = (gated * lax.rsqrt(var + cfg.norm_eps) * lp["out_norm"]
                 ).astype(x.dtype)
    with jax.named_scope("ssm.out"):
        return gated @ lp["out_proj"]


def attention_mixer(cfg: MambaConfig, x: jax.Array, lp: Params) -> jax.Array:
    """Causal attention without positions on normed x [B, T, H] ->
    [B, T, H], before the residual."""
    b, t, _ = x.shape
    nh, nkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("gqa.qkv"):
        scale = jnp.asarray(cfg.attention_multiplier * d ** 0.5, x.dtype)
        q = (x @ lp["wq"]).reshape(b, t, nh, d) * scale
        k = (x @ lp["wk"]).reshape(b, t, nkv, d)
        v = (x @ lp["wv"]).reshape(b, t, nkv, d)
    o = attention(q, k, v)
    with jax.named_scope("gqa.out"):
        return o @ lp["wo"]


def layer(cfg: MambaConfig, mixer):
    """The step of a layer, (x [B, T, H], lp) -> x: the weights cast
    (``_FLOAT32_LEAVES`` left float32), the mixer and the MLP, each on its
    normed input and added back times the residual multiplier."""
    def run(x, lp):
        lp = train_step.cast(lp, cfg.dtype, _FLOAT32_LEAVES)
        with jax.named_scope(GLUE):
            y = norm(x, lp["mixer_norm"], cfg.norm_eps)
        x = _residual(cfg, x, mixer(cfg, y, lp))
        with jax.named_scope(GLUE):
            y = norm(x, lp["mlp_norm"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            out = swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"])
        return _residual(cfg, x, out)
    return run


def hidden_states(params: Params, tokens: jax.Array, cfg: MambaConfig):
    """tokens: [B, T] -> (final-normed states [B, T, H], no stats). Master
    weights stay float32; a layer's compute-dtype copy is made inside its
    scan step."""
    with jax.named_scope("embed"):
        x = (params["embed"][tokens] * cfg.embedding_multiplier).astype(
            cfg.dtype)
    _, n_mamba, place = cfg.layout
    mamba, attn = layer(cfg, mamba_mixer), layer(cfg, attention_mixer)

    @train_step.recomputed(SAVED_NAMES)
    def step(x, lp, i, attn_lp):
        """The Mamba layer i of a period, after the attention layer where
        that is its place. One recomputed unit: its residuals are its
        inputs (the attention layer's weights the same at every i, so the
        scan keeps one copy) and the names saved."""
        x = lax.cond(i == place, lambda x: attn(x, attn_lp), lambda x: x, x)
        return mamba(x, lp)

    def period(x, lps):
        mamba_lps, attn_lp = lps
        x, _ = lax.scan(lambda x, args: (step(x, *args, attn_lp), None), x,
                        (mamba_lps, jnp.arange(n_mamba)))
        if place == n_mamba:            # the attention layer ends the period
            x = train_step.recomputed(SAVED_NAMES)(attn)(x, attn_lp)
        return x, None

    x, _ = lax.scan(period, x, (params["mamba"], params["attention"]))
    with jax.named_scope(GLUE):
        return norm(x, params["final_norm"], cfg.norm_eps), {}


def _head(params: Params, hidden, cfg: MambaConfig):
    """(states divided by ``logits_scaling``, the tied head [H, vocab]),
    both in the compute dtype."""
    x, _ = hidden
    with jax.named_scope(GLUE):
        x = x * jnp.asarray(1.0 / cfg.logits_scaling, x.dtype)
    with jax.named_scope("loss.chunk"):
        return x, params["embed"].T.astype(cfg.dtype)


def forward(params: Params, tokens: jax.Array, cfg: MambaConfig):
    """tokens: [B, T] int32 -> logits [B, T, vocab] float32, whole (tests
    and small batches; the loss does not call this)."""
    x, head = _head(params, hidden_states(params, tokens, cfg), cfg)
    return jnp.dot(x, head, preferred_element_type=jnp.float32)


def loss_fn(params: Params, tokens: jax.Array, cfg: MambaConfig):
    """Next-token cross-entropy (the last position predicts nothing), the
    head a chunk of positions at a time (``models/chunked_loss.py``), and
    no stats."""
    hidden = hidden_states(params, tokens, cfg)
    x, head = _head(params, hidden, cfg)
    with jax.named_scope("loss.chunk"):
        return chunked_next_token_loss((x,), head, tokens,
                                       lambda nlls: nlls[0]), hidden[1]


def make_train_step(cfg: MambaConfig, optimizer):
    """Returns jittable (params, opt_state, tokens) -> (params, opt_state,
    loss, stats)."""
    return train_step.make_train_step(loss_fn, cfg, optimizer)
