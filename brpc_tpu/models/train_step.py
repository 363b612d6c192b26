"""What every model's ``make_train_step`` does once it has its gradients."""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
