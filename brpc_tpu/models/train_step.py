"""What the train models share around their own layers: the embedding, the
per-layer cast to the compute dtype, the recomputation policy, the body of a
layer whose MLP is experts, the scan over periods of layers, the head and
next-token loss over a model's ``hidden_states``, and the step itself —
value and gradient, then one optimizer update (``apply_updates``).

A model file keeps the entry points by name (``hidden_states``, ``forward``,
``loss_fn``, ``make_train_step``) and hands its own functions to these; what
it hands in is looked up when its ``hidden_states`` runs, at trace time."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from brpc_tpu.models.chunked_loss import chunked_next_token_loss


def embed(params, tokens, dtype):
    """tokens: [B, T] -> (their rows of ``params["embed"]`` in ``dtype``
    [B, T, H], the positions [B, T]), under ``embed``. The rows are gathered
    first and cast after: no compute-dtype copy of the whole [vocab, H]
    table is made to read B*T rows of it."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(dtype)
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)
    return x, positions


def cast(lp, dtype, kept=()):
    """A layer's weights in the compute dtype, made inside its scan step so
    that one layer's copy is live at a time, the leaves named in ``kept``
    left as they are (float32), under ``weights.cast``."""
    with jax.named_scope("weights.cast"):
        return {k: v if k in kept else v.astype(dtype) for k, v in lp.items()}


def recomputed(saved: tuple):
    """``jax.checkpoint`` for a layer's step: run again in the backward pass
    from its input and the values whose ``checkpoint_name`` is in
    ``saved``."""
    return functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(*saved))


def expert_layer(cfg, kept, mixer, norm, glue: str, experts):
    """The step of a layer whose MLP is experts, (x [B, T, H], lp) -> (x,
    stats): the weights ``cast`` (``kept`` left float32), ``x = mixer(x,
    lp)``, then ``x + experts(cfg, norm(x, lp["mlp_norm"], eps), lp)`` on the
    flattened tokens, the norm, the flattening and the residual under the
    scope ``glue``."""
    def run(x, lp):
        lp = cast(lp, cfg.dtype, kept)
        x = mixer(x, lp)
        b, t, h = x.shape
        with jax.named_scope(glue):
            y = norm(x, lp["mlp_norm"], cfg.norm_eps).reshape(b * t, h)
        out, stats = experts(cfg, y, lp)
        with jax.named_scope(glue):
            return x + out.reshape(b, t, h), stats
    return run


def scan_periods(x, inner, outer, inner_stack, outer_stack):
    """ONE ``lax.scan`` over periods of layers whose body scans a period's
    inner layers (``inner`` over ``inner_stack`` [periods, inner layers a
    period, ...]) and then runs its outer layer (``outer``, ``outer_stack``
    [periods, ...]) -> (x, (the inner layers' stats, the outer's))."""
    def period(x, lps):
        outer_lp, inner_lps = lps
        x, inner_stats = lax.scan(inner, x, inner_lps)
        x, outer_stats = outer(x, outer_lp)
        return x, (outer_stats, inner_stats)

    x, (outer_stats, inner_stats) = lax.scan(period, x,
                                             (outer_stack, inner_stack))
    return x, (inner_stats, outer_stats)


def in_layer_order(inner_stats, outer_stats):
    """``scan_periods``' stats, [periods, inner layers a period, ...] and
    [periods, ...] -> [layers, ...], each period's outer layer last."""
    return jax.tree_util.tree_map(
        lambda inner, outer: jnp.concatenate(
            [inner, outer[:, None]], axis=1).reshape(-1, *outer.shape[1:]),
        inner_stats, outer_stats)


def whole_logits(hidden, params, cfg):
    """A model's ``hidden_states`` (final states [B, T, H], stats) -> logits
    [B, T, vocab] float32, whole."""
    x, _ = hidden
    return jnp.dot(x, params["lm_head"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


def next_token_loss(hidden, params, tokens, cfg):
    """A model's ``hidden_states`` -> (next-token cross-entropy, the last
    position predicting nothing, and its stats), the head a chunk of
    positions at a time, each chunk's float32 logits recomputed in the
    backward pass (``models/chunked_loss.py``)."""
    x, stats = hidden
    with jax.named_scope("loss.chunk"):
        head = params["lm_head"].astype(cfg.dtype)
        return chunked_next_token_loss((x,), head, tokens,
                                       lambda nlls: nlls[0]), stats


def make_train_step(loss_fn, cfg, optimizer, frozen=()):
    """Returns jittable (params, opt_state, tokens) -> (params, opt_state,
    loss, stats), ``loss_fn(params, tokens, cfg)`` giving (loss, stats).
    ``frozen``: as ``apply_updates`` takes it."""

    def step(params, opt_state, tokens):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, cfg)
        params, opt_state = apply_updates(optimizer, grads, opt_state, params,
                                          frozen=frozen)
        return params, opt_state, loss, stats

    return step


def apply_updates(optimizer, grads, opt_state, params, frozen=()):
    """One optimizer step on float32 master weights: ``(params,
    opt_state)`` after it. ``frozen`` names leaves by their path of keys
    (``("moe", "router_bias")``) whose update is nought whatever the
    optimizer makes of them (its weight decay moves a leaf with no
    gradient). All of it sits under the scope ``opt.update``."""
    with jax.named_scope("opt.update"):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        for path in frozen:
            updates = _zeroed(updates, path)
        # params/updates are fp32 master copies; no precision-losing casts.
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
    return params, opt_state


def _zeroed(tree, path):
    key, *rest = path
    return {**tree, key: _zeroed(tree[key], rest) if rest
            else jnp.zeros_like(tree[key])}
