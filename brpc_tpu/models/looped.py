"""Looped language model (``model_type: ouro``, ByteDance Ouro 1.4B / 2.6B
"LoopLM"): ONE stack of decoder layers run ``total_ut_steps`` times on the
same weights, with an exit gate after every pass and a loss over all of
them. TPU-first functional JAX with the entry points of ``models/llama.py``
and ``models/deepseek.py``: a frozen config with a ``tiny()`` preset,
``init_params``, ``hidden_states``, ``loss_fn`` and ``make_train_step``.

The equations (R passes, L layers; RMS(.; g) is RMSNorm with gain g)::

    x0 = E[tokens]
    for r = 1..R:                                  # the same weights every pass
        h = x(r-1)
        for l = 1..L:
            h = h + RMS(Attn_l(RMS(h; g1_l)); g2_l)    # sandwich: a norm before and after
            h = h + RMS(SwiGLU_l(RMS(h; g3_l)); g4_l)
        x(r) = RMS(h; g_final)         # closes every pass; feeds head, gate AND pass r+1
        logits(r) = x(r) W_head        lambda(r) = sigmoid(x(r) w_gate + b_gate)
    exit distribution of a position: p_r = lambda(r) prod_{j<r} (1 - lambda(j)),
        the last pass taking what is left, p_R = prod_{j<R} (1 - lambda(j))
    loss of a position = sum_r p_r CE(logits(r), next token) - beta H(p)

Attention is plain multi-head (16 heads over 16 KV heads of 128), rope on the
whole head (halves rotated), positions 0..T-1 in every pass, no biases. The
four gains of a layer are the released code's ``input_layernorm`` (g1),
``input_layernorm_2`` (g2), ``post_attention_layernorm`` (g3) and
``post_attention_layernorm_2`` (g4); the gate is its ``early_exit_gate``, a
Linear(hidden, 1). The loss is the paper's first-stage objective, the
expected loss under the exit distribution less ``exit_beta`` times its
entropy; the later stage that fits the gate alone and the serving-time
``early_exit_threshold`` are not here.

How it runs. Per-layer weights are stacked on a leading axis and scanned, and
the pass loop is a second ``lax.scan`` around that scan which closes over the
stack: one compiled layer body whatever L and R are, and the backward pass
adds the R passes' gradients into one float32 stack of the weights' size.
bf16 compute; float32 master weights, norms' reductions, softmax, gate,
logits and loss. Each of the R x L layer applications is recomputed in the
backward pass (``jax.checkpoint`` around the layer) from its input and the
attention kernel's output and log-sum-exp, kept by name
(``flash_attention.RESIDUAL_NAMES``) as ``models/deepseek.py`` keeps them. The
head runs R times a step, a chunk of positions at a time with all R passes of
the chunk together (``models/chunked_loss.py``), since the exit distribution
couples a position's passes.

``make_train_step``'s step also returns ``stats``: ``pass_loss`` [R] (mean
cross-entropy of each pass's logits), ``exit_mass`` [R] (mean p_r) and
``exit_entropy`` (mean H(p), nats; ln R at uniform), each over the positions
that predict a token. Named scopes: ``loop.layer.attn``, ``loop.layer.mlp``,
``loop.pass_norm``, ``loop.exit_gate``, ``loop.head``, ``loop.exit_loss``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from brpc_tpu.models.chunked_loss import chunked_next_token_loss
from brpc_tpu.models.llama import _dense_init, attention, rms_norm, rope
from brpc_tpu.models.train_step import apply_updates
from brpc_tpu.ops.flash_attention import RESIDUAL_NAMES

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    """The defaults are Ouro-2.6B as published (ByteDance/Ouro-2.6B
    ``config.json``); ``exit_beta`` is a training choice it does not give."""
    vocab_size: int = 49152
    hidden: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    intermediate: int = 5632
    total_ut_steps: int = 4
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    exit_beta: float = 0.1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LoopedConfig":
        """A toy config for tests / dry runs: 2 layers run 3 times."""
        return LoopedConfig(vocab_size=vocab_size, hidden=64, n_layers=2,
                            n_heads=4, n_kv_heads=4, head_dim=16,
                            intermediate=128, total_ut_steps=3)


def init_params(key: jax.Array, cfg: LoopedConfig) -> Params:
    """Per-layer tensors are stacked on axis 0. Matrices and the gate's
    weight normal(0, fan_in^-1/2), gains 1, the gate's bias 0."""
    h, n, pd = cfg.hidden, cfg.n_layers, cfg.param_dtype
    q_out, kv_out = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    ks = iter(jax.random.split(key, 10))

    def mat(shape, fan_in):
        return _dense_init(next(ks), shape, pd, fan_in)

    gain = lambda: jnp.ones((n, h), pd)  # noqa: E731
    return {
        "embed": mat((cfg.vocab_size, h), 1.0),
        "layers": {
            "wq": mat((n, h, q_out), h), "wk": mat((n, h, kv_out), h),
            "wv": mat((n, h, kv_out), h), "wo": mat((n, q_out, h), q_out),
            "w_gate": mat((n, h, cfg.intermediate), h),
            "w_up": mat((n, h, cfg.intermediate), h),
            "w_down": mat((n, cfg.intermediate, h), cfg.intermediate),
            "attn_norm": gain(), "attn_out_norm": gain(),
            "mlp_norm": gain(), "mlp_out_norm": gain(),
        },
        "final_norm": jnp.ones((h,), pd),
        "lm_head": mat((h, cfg.vocab_size), h),
        "exit_gate": {"w": mat((h,), h), "b": jnp.zeros((), pd)},
    }


def _layer(cfg: LoopedConfig, x: jax.Array, lp: Params,
           positions: jax.Array) -> jax.Array:
    """One application of one layer, sandwich-normed. x: [B, T, H]."""
    b, t, _ = x.shape
    eps = cfg.norm_eps
    with jax.named_scope("loop.layer.attn"):
        y = rms_norm(x, lp["attn_norm"], eps)
        q = (y @ lp["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = (y @ lp["wk"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        v = (y @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        o = attention(rope(q, positions, cfg.rope_theta),
                      rope(k, positions, cfg.rope_theta), v) @ lp["wo"]
        x = x + rms_norm(o, lp["attn_out_norm"], eps)
    with jax.named_scope("loop.layer.mlp"):
        y = rms_norm(x, lp["mlp_norm"], eps)
        o = (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) @ lp["w_down"]
        return x + rms_norm(o, lp["mlp_out_norm"], eps)


def hidden_states(params: Params, tokens: jax.Array,
                  cfg: LoopedConfig) -> jax.Array:
    """tokens: [B, T] -> the final-normed states of every pass [R, B, T, H].
    Master weights stay float32; a layer's compute-dtype copy is made inside
    its scan step, so the gradient of the shared stack gathers in float32."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)
    with jax.named_scope("weights.cast"):
        final_norm = params["final_norm"].astype(cfg.dtype)

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES))
    def layer(x, lp):
        with jax.named_scope("weights.cast"):
            lp = jax.tree_util.tree_map(lambda w: w.astype(cfg.dtype), lp)
        return _layer(cfg, x, lp, positions), None

    def one_pass(x, _):
        x, _ = lax.scan(layer, x, params["layers"])
        with jax.named_scope("loop.pass_norm"):
            x = rms_norm(x, final_norm, cfg.norm_eps)
        return x, x

    _, states = lax.scan(one_pass, x, None, length=cfg.total_ut_steps)
    return states


def exit_log_probs(gate_logits: jax.Array) -> jax.Array:
    """The gate's logits of a position's R passes [..., R] -> ln p [..., R]:
    p_r = lambda_r prod_{j<r}(1 - lambda_j), the last pass taking the rest
    whatever its own gate says."""
    stay = jax.nn.log_sigmoid(-gate_logits)              # ln(1 - lambda)
    before = jnp.cumsum(stay, axis=-1) - stay            # ln prod_{j<r}
    leave = jax.nn.log_sigmoid(gate_logits).at[..., -1].set(0.0)
    return before + leave


def loss_of_states(params: Params, states: jax.Array, tokens: jax.Array,
                   cfg: LoopedConfig):
    """The passes' final states [R, B, T, H] -> (the expected next-token
    cross-entropy under the exit distribution less ``exit_beta`` times its
    entropy, stats)."""
    with jax.named_scope("loop.exit_gate"):
        gate = params["exit_gate"]
        gate_logits = jnp.einsum(
            "rbth,h->btr", states.astype(jnp.float32), gate["w"],
            precision=lax.Precision.HIGHEST) + gate["b"]

    def position_loss(nlls, gate_c):
        with jax.named_scope("loop.exit_loss"):
            nll = jnp.stack(nlls, axis=-1)                   # [chunk, R]
            log_p = exit_log_probs(gate_c)
            p = jnp.exp(log_p)
            entropy = -jnp.sum(p * log_p, axis=-1)
            return {"loss": jnp.sum(p * nll, axis=-1)
                    - cfg.exit_beta * entropy,
                    "pass_loss": nll, "exit_mass": p,
                    "exit_entropy": entropy}

    with jax.named_scope("loop.head"):
        out = chunked_next_token_loss(
            tuple(states[r] for r in range(states.shape[0])),
            params["lm_head"].astype(cfg.dtype), tokens, position_loss,
            extras=(gate_logits,))
    return out.pop("loss"), out


def loss_fn(params: Params, tokens: jax.Array, cfg: LoopedConfig):
    """(loss, stats) of the whole forward pass: ``loss_of_states`` of
    ``hidden_states``."""
    return loss_of_states(params, hidden_states(params, tokens, cfg), tokens,
                          cfg)


def make_train_step(cfg: LoopedConfig, optimizer):
    """Returns jittable (params, opt_state, tokens) -> (params, opt_state,
    loss, stats)."""

    def step(params, opt_state, tokens):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, cfg)
        params, opt_state = apply_updates(optimizer, grads, opt_state, params)
        return params, opt_state, loss, stats

    return step
