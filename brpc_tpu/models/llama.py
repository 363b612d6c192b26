"""Llama-family transformer, TPU-first functional JAX.

This is the flagship model for the parameter-server workloads (the reference's
north-star config: Llama-3-8B embedding-shard serving + gradient allreduce,
BASELINE.json).  Design choices are TPU-idiomatic rather than a torch port:

- params are a plain pytree; per-layer weights are *stacked* on a leading
  ``n_layers`` axis and the decoder runs under ``lax.scan`` — one compiled
  layer body regardless of depth (fast XLA compiles, MXU-friendly).
- compute dtype is bfloat16 by default, accumulation in float32 where it
  matters (RMSNorm reductions, attention softmax, final logits).
- sharding is declared, not hand-scheduled: ``param_specs`` / ``batch_specs``
  give PartitionSpecs over a mesh with axes ``('dp', 'tp')`` (+ optional
  ``'sp'`` sequence axis used by ring attention); XLA inserts the ICI
  collectives.
- GQA attention with RoPE; SwiGLU MLP; RMSNorm; untied LM head.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from brpc_tpu.models.train_step import apply_updates
from brpc_tpu.ops.flash_attention import (flash_attention,
                                          supported as flash_supported)
from brpc_tpu.ops.lowered import count_lowering

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    intermediate: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # compute dtype (MXU-friendly)
    param_dtype: Any = jnp.float32   # master weights / optimizer state

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        """A toy config for tests / dry runs (shapes stay MXU-tileable)."""
        return LlamaConfig(
            vocab_size=vocab_size,
            hidden=128,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            head_dim=32,
            intermediate=256,
        )

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        """The defaults are Llama-3-8B as published (meta-llama/Meta-Llama-3-8B
        config.json: vocab 128,256, hidden 4,096, 32 layers, 32 heads over 8
        KV heads of 128, intermediate 14,336, rope theta 500,000)."""
        return LlamaConfig()


def _dense_init(key, shape, dtype, fan_in):
    scale = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Initialise a parameter pytree. Per-layer tensors are stacked on axis 0."""
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    h, L = cfg.hidden, cfg.n_layers
    q_out = cfg.n_heads * cfg.head_dim
    kv_out = cfg.n_kv_heads * cfg.head_dim
    ks = jax.random.split(k_layers, 7)
    pd = cfg.param_dtype

    def stacked(key, shape, fan_in):
        return _dense_init(key, (L,) + shape, pd, fan_in)

    layers = {
        "wq": stacked(ks[0], (h, q_out), h),
        "wk": stacked(ks[1], (h, kv_out), h),
        "wv": stacked(ks[2], (h, kv_out), h),
        "wo": stacked(ks[3], (q_out, h), q_out),
        "w_gate": stacked(ks[4], (h, cfg.intermediate), h),
        "w_up": stacked(ks[5], (h, cfg.intermediate), h),
        "w_down": stacked(ks[6], (cfg.intermediate, h), cfg.intermediate),
        "attn_norm": jnp.ones((L, h), pd),
        "mlp_norm": jnp.ones((L, h), pd),
    }
    return {
        "embed": _dense_init(k_emb, (cfg.vocab_size, h), pd, 1.0),
        "layers": layers,
        "final_norm": jnp.ones((h,), pd),
        "lm_head": _dense_init(k_out, (h, cfg.vocab_size), pd, h),
    }


def param_specs(cfg: LlamaConfig) -> Params:
    """PartitionSpecs for each param over mesh axes ('dp','tp').

    Megatron-style tensor parallelism: attention/MLP first matmuls are
    column-sharded, second matmuls row-sharded, embeddings vocab-sharded.
    XLA inserts the psum on the row-sharded outputs.
    """
    layers = {
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "w_gate": P(None, None, "tp"),
        "w_up": P(None, None, "tp"),
        "w_down": P(None, "tp", None),
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
    }
    return {
        "embed": P("tp", None),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }


def batch_specs() -> P:
    """Token batches are sharded over data-parallel axis."""
    return P("dp", None)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings. x: [B, T, H, D], positions: [B, T]."""
    d_half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,Dh]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def dense_attention(q, k, v, *, causal: bool = True, window=None):
    """Grouped-query attention with the scores materialised. q: [B,T,Hq,D],
    k: [B,T,Hkv,D], v: [B,T,Hkv,Dv] (Dv = D but for latent attention).
    ``window``: query i sees key j iff 0 <= i - j < window."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    q = q.reshape(b, t, hkv, group, d)
    scores = jnp.einsum("bthgd,bshd->bhgts", q, k).astype(jnp.float32)
    scores = scores * (d ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((t, t), bool), -window)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v)
    return out.reshape(b, t, hq * v.shape[-1])


def attention(q, k, v, *, causal: bool = True, window=None):
    """Grouped-query attention, by the implementation the operands and the
    platform being lowered for allow: the fused blockwise kernel
    (``ops.flash_attention``, no [T,T] array in HBM in either pass) for
    causal bf16 attention at shapes it supports when lowered for TPU, the
    dense form for everything else. One traced function serves every
    platform. ``attn_kernel_lowerings`` / ``attn_dense_lowerings`` count
    which one each lowered program holds. ``window``: sliding-window
    attention, the same mask in both forms (the band kernels)."""

    def dense(q, k, v):
        with jax.named_scope("attn.dense"):
            return dense_attention(
                count_lowering(q, "attn_dense_lowerings"), k, v,
                causal=causal, window=window)

    def kernel(q, k, v):
        return flash_attention(
            count_lowering(q, "attn_kernel_lowerings"), k, v, causal=causal,
            window=window)

    if not (causal and q.dtype == k.dtype == v.dtype == jnp.bfloat16
            and flash_supported(q.shape, k.shape, q.dtype, v.shape,
                                window)):
        return dense(q, k, v)
    return lax.platform_dependent(q, k, v, tpu=kernel, default=dense)


def _layer(cfg: LlamaConfig, x: jax.Array, lp: Params, positions: jax.Array,
           attn_fn=None) -> jax.Array:
    b, t, h = x.shape
    # attention block
    with jax.named_scope("llama.qkv"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (y @ lp["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = (y @ lp["wk"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        v = (y @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    attend = attn_fn if attn_fn is not None else attention
    o = attend(q, k, v)         # under its own scopes (attn.*), not these
    with jax.named_scope("llama.attn_out"):
        x = x + o @ lp["wo"]
    # mlp block
    with jax.named_scope("llama.mlp"):
        y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        return x + (jax.nn.silu(y @ lp["w_gate"])
                    * (y @ lp["w_up"])) @ lp["w_down"]


def forward(params: Params, tokens: jax.Array, cfg: LlamaConfig,
            attn_fn=None) -> jax.Array:
    """tokens: [B, T] int32 -> logits [B, T, vocab] float32.

    Master weights stay in cfg.param_dtype (fp32); compute runs in cfg.dtype
    (bf16) — the cast happens per-layer inside the scan so only one layer's
    bf16 copy is live at a time.
    """
    # Gather rows first, THEN cast: avoids materializing a full bf16 copy of
    # the [vocab, hidden] table (≈1GB at 128k vocab) just to read B*T rows.
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)

    def body(x, lp):
        with jax.named_scope("weights.cast"):
            lp = jax.tree_util.tree_map(lambda a: a.astype(cfg.dtype), lp)
        return _layer(cfg, x, lp, positions, attn_fn), None

    x, _ = lax.scan(body, x, params["layers"])
    with jax.named_scope("llama.head_loss"):
        x = rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
        return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


def loss_fn(params: Params, tokens: jax.Array, cfg: LlamaConfig,
            attn_fn=None) -> jax.Array:
    """Next-token cross-entropy (last position predicts nothing)."""
    logits = forward(params, tokens, cfg, attn_fn)
    with jax.named_scope("llama.head_loss"):
        logits = logits[:, :-1]
        targets = tokens[:, 1:]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(logz - gold)


def make_train_step(cfg: LlamaConfig, optimizer, attn_fn=None):
    """Returns jittable (params, opt_state, tokens) -> (params, opt_state, loss).

    Data-parallel gradient reduction is *not* hand-written: with params
    replicated over 'dp' and batch sharded over 'dp', jit inserts the
    allreduce (the ParallelChannel-fan-out analog, SURVEY.md §2.7).
    """

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg,
                                                  attn_fn)
        params, opt_state = apply_updates(optimizer, grads, opt_state, params)
        return params, opt_state, loss

    return step
