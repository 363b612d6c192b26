"""DeepSeek-V3-shaped transformer (``model_type: deepseek_v3``): multi-head
latent attention and sigmoid-routed sparse experts with shared experts,
TPU-first functional JAX, with the entry points of ``models/llama.py`` — a
frozen config with a ``tiny()`` preset, ``init_params``, ``forward``,
``loss_fn`` and ``make_train_step``.

The stack has two kinds of layer: ``n_dense_layers`` leading layers with a
dense SwiGLU MLP, then expert layers. Each kind is stacked on a leading axis
and runs under its own ``lax.scan``; bf16 compute, float32 master weights.

Layers, as published (kakaocorp/kanana-2-30b-a3b-instruct-2601 and
deepseek-ai/DeepSeek-V3 ``config.json``; HF ``modeling_deepseek_v3.py``):

- **MLA**, without a query low-rank (``q_lora_rank`` null): ``q = y·Wq``
  split per head into ``qk_nope_dim`` | ``qk_rope_dim``; ``y·Wkv_a`` split
  into a latent of ``kv_lora_rank``, RMS-normed, and one rope key shared by
  every head; the latent times ``Wkv_b`` gives each head's ``k_nope`` | ``v``;
  rope on the rope dims, pairs interleaved ((2i, 2i+1) are a pair); scores
  scaled by (nope + rope)^-1/2, causal, float32 softmax. Training-time MLA
  is plain multi-head attention with q/k of 192 and v of 128, and goes
  through ``flash_attention.attention``: the fused kernels on a TPU, the
  dense form elsewhere.
- **Experts**: ``s = sigmoid(y·Wg)`` in float32 over ALL ``n_routed_experts``;
  the ``experts_per_token`` largest of ``s + b`` are selected (``b``:
  ``router_bias``, DeepSeek's ``e_score_correction_bias``); their weights
  are ``s`` (without ``b``), divided by their sum, times ``routed_scaling``;
  plus the shared experts, one unweighted SwiGLU of ``n_shared_experts`` ×
  the expert width.
- **A chip's share** (expert parallelism): the layer is told which experts
  it holds — ``n_held`` of them from ``expert_offset`` — routes over all,
  and computes what its own give; what the absent ones would add is left
  out, and the weights' sum still runs over all the selected, wherever they
  live. That partial result goes on to the next layer. No token is dropped
  whatever the imbalance: the routed experts are a grouped product over the
  rows present (``ops/grouped_matmul.py``), not slots of a fixed capacity.
  Nothing stands in for the absent chips or their exchange.

Departures from the published model, all of them training choices its
config does not fix: ``n_group`` = ``topk_group`` = 1 only (the group limit
is then the identity, as in kanana-2); ``router_bias`` is a buffer — it gets
no gradient and ``make_train_step`` holds it fixed (DeepSeek-V3 nudges it
by expert load between steps); no auxiliary balance loss; no rope scaling
and no ``mscale``; no multi-token prediction.

Memory, the normal path of this model: each layer is recomputed in the
backward pass (``jax.checkpoint`` around the scans' bodies) from its input
and the few values its policy saves by name (``SAVED_NAMES``). One is the
attention kernel's output with its log-sum-exp
(``flash_attention.RESIDUAL_NAMES``; 68 MB a layer at 8,192 tokens of 32
heads, for a forward kernel that is the dearest thing in the layer to run
again): the backward kernels get q, k, v from the recomputed projections and
those two from the forward scan's stack. The other is an expert layer's
integer routing — the experts selected and the ``group_layout`` made from
them (``grouped_matmul.LAYOUT_NAME``, 1.5 MB a layer) — so that the
recomputation runs the router's matmul and sigmoid, whose gradient needs
them, and neither ``top_k`` nor the sort. On a v5e at the kanana cell's size
the first takes 35 ms off a 430 ms step and the second 4.6 ms more (PERF.md
section 6, PR 29). Where attention is the dense form (a CPU, float32, narrow
heads) no activation has a name. The loss takes the output head in chunks of
the sequence, each recomputed, so that no [T, vocab] float32 logits exist
(``models/chunked_loss.py``, shared with ``models/looped.py``).

``make_train_step``'s step also returns ``stats``: per expert layer the
assignments routed to held experts, the largest and the mean expert's rows,
the assignments dropped (0 by construction, counted all the same), the rows
of the bound in use (whole tiles: what the row movement around the products
works over) and the experts each token selected.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from brpc_tpu.models import train_step
from brpc_tpu.models.experts import (expert_mlp, sigmoid_route,
                                     swiglu as _swiglu)
from brpc_tpu.models.layers import dense_init, rms_norm
from brpc_tpu.ops import grouped_matmul as gm
from brpc_tpu.ops.flash_attention import RESIDUAL_NAMES, attention

Params = Dict[str, Any]

_FLOAT32_LEAVES = ("router", "router_bias")     # never cast to the compute dtype

# What a layer keeps across its recomputation beside its input, by the names
# the values are given where they are made: the attention kernel's output
# and log-sum-exp, and an expert layer's routing layout.
SAVED_NAMES = (*RESIDUAL_NAMES, gm.LAYOUT_NAME)


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    """The defaults are kanana-2-30b-a3b-instruct-2601 as published, every
    expert held."""
    vocab_size: int = 128256
    hidden: int = 2048
    n_layers: int = 48
    n_dense_layers: int = 1
    n_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    kv_lora_rank: int = 512
    intermediate: int = 6144          # the dense layers' SwiGLU
    moe_intermediate: int = 768       # one expert's SwiGLU
    n_routed_experts: int = 128       # the router's width, always whole
    n_shared_experts: int = 2
    experts_per_token: int = 6
    routed_scaling: float = 2.448
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    n_held: int = 128                 # experts this chip holds ...
    expert_offset: int = 0            # ... from this one on
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def tiny(vocab_size: int = 256, n_held: int = 2,
             expert_offset: int = 0) -> "DeepseekConfig":
        """A toy config for tests / dry runs: 1 dense + 2 expert layers, 8
        experts of which ``n_held`` live here, top-2."""
        return DeepseekConfig(
            vocab_size=vocab_size, hidden=64, n_layers=3, n_dense_layers=1,
            n_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
            kv_lora_rank=32, intermediate=128, moe_intermediate=32,
            n_routed_experts=8, n_shared_experts=2, experts_per_token=2,
            n_held=n_held, expert_offset=expert_offset)


def init_params(key: jax.Array, cfg: DeepseekConfig) -> Params:
    """Per-layer tensors are stacked on axis 0, dense and expert layers
    apart. Matrices normal(0, fan_in^-1/2), norms 1, ``router_bias``
    normal(0, 0.01) so that it does something."""
    h, pd = cfg.hidden, cfg.param_dtype
    nh, e, f = cfg.n_heads, cfg.n_routed_experts, cfg.moe_intermediate
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    shared = cfg.n_shared_experts * f
    k_emb, k_dense, k_moe, k_out = jax.random.split(key, 4)

    def stack(key, n, extra):
        ks = iter(jax.random.split(key, 5 + len(extra)))

        def mat(shape, fan_in):
            return dense_init(next(ks), (n,) + shape, pd, fan_in)

        layers = {
            "wq": mat((h, nh * qk), h),
            "wkv_a": mat((h, cfg.kv_lora_rank + cfg.qk_rope_dim), h),
            "wkv_b": mat((cfg.kv_lora_rank,
                          nh * (cfg.qk_nope_dim + cfg.v_dim)),
                         cfg.kv_lora_rank),
            "wo": mat((nh * cfg.v_dim, h), nh * cfg.v_dim),
            "kv_norm": jnp.ones((n, cfg.kv_lora_rank), pd),
            "attn_norm": jnp.ones((n, h), pd),
            "mlp_norm": jnp.ones((n, h), pd),
        }
        for name, shape, fan_in in extra:
            layers[name] = mat(shape, fan_in)
        return layers

    n_moe = cfg.n_layers - cfg.n_dense_layers
    moe = stack(k_moe, n_moe, [
        ("router", (h, e), h),
        ("shared_gate", (h, shared), h), ("shared_up", (h, shared), h),
        ("shared_down", (shared, h), shared),
        ("w_gate", (cfg.n_held, h, f), h), ("w_up", (cfg.n_held, h, f), h),
        ("w_down", (cfg.n_held, f, h), f)])
    moe["router_bias"] = 0.01 * jax.random.normal(
        jax.random.fold_in(k_moe, 1), (n_moe, e), jnp.float32)
    return {
        "embed": dense_init(k_emb, (cfg.vocab_size, h), pd, 1.0),
        "dense": stack(k_dense, cfg.n_dense_layers, [
            ("w_gate", (h, cfg.intermediate), h),
            ("w_up", (h, cfg.intermediate), h),
            ("w_down", (cfg.intermediate, h), cfg.intermediate)]),
        "moe": moe,
        "final_norm": jnp.ones((h,), pd),
        "lm_head": dense_init(k_out, (h, cfg.vocab_size), pd, h),
    }


def rope_interleaved(x: jax.Array, positions: jax.Array,
                     theta: float) -> jax.Array:
    """Rotary embeddings with (2i, 2i+1) as pair i (``rope_interleave``).
    x: [B, T, H, D], positions: [B, T]."""
    d_half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d_half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla(cfg: DeepseekConfig, x: jax.Array, lp: Params,
        positions: jax.Array) -> jax.Array:
    """The attention block with its residual. x: [B, T, H]."""
    b, t, _ = x.shape
    nh, nope, rank = cfg.n_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    with jax.named_scope("mla.norm"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("mla.q_proj"):
        q = (y @ lp["wq"]).reshape(b, t, nh, nope + cfg.qk_rope_dim)
    with jax.named_scope("mla.kv_down"):
        ckv = y @ lp["wkv_a"]
        latent = rms_norm(ckv[..., :rank], lp["kv_norm"], cfg.norm_eps)
        k_rope = ckv[..., None, rank:]                 # one head for all
    with jax.named_scope("mla.kv_up"):
        kv = (latent @ lp["wkv_b"]).reshape(b, t, nh, nope + cfg.v_dim)
    with jax.named_scope("mla.rope"):
        q_rope = rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
        k_rope = rope_interleaved(k_rope, positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, q_rope.shape)],
            axis=-1)
        v = kv[..., nope:]
    o = attention(q, k, v)
    with jax.named_scope("mla.out_proj"):
        return x + o @ lp["wo"]


def moe_mlp(cfg: DeepseekConfig, y: jax.Array, lp: Params):
    """The expert layer's MLP on normed tokens y: [N, H] -> ([N, H], stats):
    what the held experts give for the assignments routed to them, plus the
    shared experts (``models/experts.py``: the router and everything after
    the routing)."""
    with jax.named_scope("moe.router"):
        selected, weights = sigmoid_route(cfg, y, lp["router"],
                                          lp["router_bias"])
    return expert_mlp(
        y, selected, weights, lp["w_gate"], lp["w_up"], lp["w_down"],
        n_held=cfg.n_held, expert_offset=cfg.expert_offset,
        shared=lambda y: _swiglu(y, lp["shared_gate"], lp["shared_up"],
                                 lp["shared_down"]))


def hidden_states(params: Params, tokens: jax.Array, cfg: DeepseekConfig):
    """tokens: [B, T] -> (final-normed states [B, T, H], per-expert-layer
    stats). Master weights stay float32; each layer's compute-dtype copy is
    made inside its scan step, and each step is recomputed in the backward
    pass from its input and what ``SAVED_NAMES`` names."""
    x, positions = train_step.embed(params, tokens, cfg.dtype)
    recomputed = train_step.recomputed(SAVED_NAMES)

    @recomputed
    def dense_layer(x, lp):
        lp = train_step.cast(lp, cfg.dtype, _FLOAT32_LEAVES)
        x = mla(cfg, x, lp, positions)
        with jax.named_scope("dsv3.dense_mlp"):
            y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            return x + _swiglu(y, lp["w_gate"], lp["w_up"],
                               lp["w_down"]), None

    moe_layer = recomputed(train_step.expert_layer(
        cfg, _FLOAT32_LEAVES, lambda x, lp: mla(cfg, x, lp, positions),
        rms_norm, "dsv3.glue", moe_mlp))
    x, _ = lax.scan(dense_layer, x, params["dense"])
    x, stats = lax.scan(moe_layer, x, params["moe"])
    with jax.named_scope("dsv3.glue"):
        x = rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    return x, stats


def forward(params: Params, tokens: jax.Array, cfg: DeepseekConfig):
    """tokens: [B, T] int32 -> logits [B, T, vocab] float32, whole (tests
    and small batches; the loss does not call this)."""
    return train_step.whole_logits(hidden_states(params, tokens, cfg),
                                   params, cfg)


def loss_fn(params: Params, tokens: jax.Array, cfg: DeepseekConfig):
    """Next-token cross-entropy (the last position predicts nothing), and
    the forward pass's stats (``train_step.next_token_loss``)."""
    return train_step.next_token_loss(hidden_states(params, tokens, cfg),
                                      params, tokens, cfg)


def make_train_step(cfg: DeepseekConfig, optimizer):
    """Returns jittable (params, opt_state, tokens) -> (params, opt_state,
    loss, stats). ``router_bias`` is held fixed: its gradient is nought, and
    the optimizer's decay of it is taken out again."""
    return train_step.make_train_step(loss_fn, cfg, optimizer,
                                      frozen=(("moe", "router_bias"),))
