"""Mixture-of-Experts layer with expert parallelism over an 'ep' mesh axis.

Expert parallelism is the PartitionChannel shape at the model tier (SURVEY
§2.7: shard-addressed calls — tokens are "requests" routed to expert
"partitions"). TPU-first design:

- experts live sharded over 'ep' (each device owns E/ep experts);
- routing is dense top-1 gating with a fixed capacity per expert —
  compiler-friendly (static shapes, no data-dependent gather/scatter), the
  standard Switch-Transformer recipe;
- dispatch/combine are einsums against a one-hot dispatch mask, so the
  cross-device movement compiles to ICI all-to-alls inside jit when the
  token batch is dp-sharded and experts are ep-sharded.

This is the top-1 toy layer that the multi-chip dry run
(``__graft_entry__.dryrun_multichip``) uses to exercise the 'ep' axis; no
model calls it. The expert layer real models use is ``deepseek.moe_mlp``
(models/deepseek.py): top-k of sigmoid scores over all the experts, no
capacity and no dropped token, a grouped product over the experts a chip
holds (ops/grouped_matmul.py), shared experts.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    hidden: int = 128
    intermediate: int = 256
    n_experts: int = 4
    capacity_factor: float = 2.0
    dtype: Any = jnp.bfloat16


def init_moe_params(key: jax.Array, cfg: MoeConfig):
    kg, k1, k2 = jax.random.split(key, 3)
    h, f, e = cfg.hidden, cfg.intermediate, cfg.n_experts
    scale_in = h ** -0.5
    scale_out = f ** -0.5
    return {
        "gate": jax.random.normal(kg, (h, e), jnp.float32) * scale_in,
        "w_in": jax.random.normal(k1, (e, h, f), jnp.float32) * scale_in,
        "w_out": jax.random.normal(k2, (e, f, h), jnp.float32) * scale_out,
    }


def moe_param_specs():
    """Experts shard over 'ep' (leading dim); gate replicated."""
    return {
        "gate": P(None, None),
        "w_in": P("ep", None, None),
        "w_out": P("ep", None, None),
    }


def moe_layer(params, x: jax.Array, cfg: MoeConfig):
    """x: [B, T, H] -> ([B, T, H], aux_loss).

    Top-1 routing with capacity C = capacity_factor * T*B / E; overflow
    tokens pass through the residual unchanged (standard Switch behavior).
    aux_loss is the load-balancing term (mean_prob · mean_assignment · E).
    """
    b, t, h = x.shape
    e = cfg.n_experts
    n = b * t
    cap = max(1, int(cfg.capacity_factor * n / e))
    xf = x.reshape(n, h)

    logits = (xf.astype(jnp.float32) @ params["gate"])          # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                          # [N]
    gate = jnp.take_along_axis(probs, expert[:, None], 1)[:, 0]  # [N]

    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.int32)          # [N, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot                    # 1-based
    pos_in_expert = jnp.sum(pos, axis=-1) - 1                    # [N]
    keep = pos_in_expert < cap

    # dispatch tensor [N, E, C]: one-hot of (expert, slot) for kept tokens
    slot_oh = jax.nn.one_hot(jnp.where(keep, pos_in_expert, cap), cap + 1,
                             dtype=xf.dtype)[:, :cap]            # [N, C]
    dispatch = onehot.astype(xf.dtype)[:, :, None] * slot_oh[:, None, :]

    # route tokens to expert buffers: [E, C, H] — with x dp-sharded and
    # experts ep-sharded this einsum lowers to the all-to-all
    buffers = jnp.einsum("nec,nh->ech", dispatch, xf)
    y = jnp.einsum("ech,ehf->ecf", buffers.astype(cfg.dtype),
                   params["w_in"].astype(cfg.dtype))
    y = jax.nn.gelu(y)
    y = jnp.einsum("ecf,efh->ech", y, params["w_out"].astype(cfg.dtype))
    # combine back, weighted by the gate
    out = jnp.einsum("nec,ech->nh", dispatch, y.astype(jnp.float32))
    out = out * gate[:, None]

    # load-balancing auxiliary (Switch eq. 4)
    density = jnp.mean(onehot.astype(jnp.float32), axis=0)       # [E]
    density_proxy = jnp.mean(probs, axis=0)                      # [E]
    aux = jnp.sum(density * density_proxy) * e

    return out.reshape(b, t, h).astype(x.dtype), aux
