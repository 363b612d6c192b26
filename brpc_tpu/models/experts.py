"""A sparse expert layer: its two routers, one chip's share of the routed
experts and what every chip computes alike, for every model that has such a
layer (``models/deepseek.py``, kanana-2: sigmoid scores, a selection bias, a
scaling factor, unweighted shared experts; ``models/hybrid.py``,
Qwen3-Next: softmax scores, one shared expert behind a sigmoid gate;
``models/windowed.py``: Laguna-XS.2, sigmoid scores with a scaling beside one
unweighted shared expert, and Mellum2, softmax scores and no shared expert at
all). How the experts are chosen and weighted is the model's, by one of the
two routers: ``sigmoid_route`` (sigmoid scores; ``moe_routed_scaling_factor``,
a selection bias) and ``softmax_route`` (softmax scores renormalised over
the chosen: ``norm_topk_prob: true``). What follows the routing is the
same for all of them (``expert_mlp``), and ``shared`` says what is added
beside it: a function of the tokens where the published config has a shared
expert (``shared_expert_intermediate_size``, ``n_shared_experts``), None
where it has none. Each model keeps its own ``moe_mlp``, which routes and
says what is shared.

The layer is told which experts it holds — ``n_held`` of them from
``expert_offset`` — and computes what its own give for the assignments
routed to them: the assignments are laid out by expert
(``grouped_matmul.group_layout``), the tokens' rows fetched (``dispatch``),
the three grouped products of a SwiGLU run over the rows present, and each
token's rows summed by weight (``combine``). What the absent experts would
add is left out, no token is dropped whatever the imbalance, and nothing
stands in for the absent chips or their exchange.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from brpc_tpu.ops import grouped_matmul as gm


def swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def chosen(scores: jax.Array, selected: jax.Array) -> jax.Array:
    """scores [N, E] over all the router's experts, ``selected`` [N, k] ->
    [N, k], each token's scores of the experts it chose: ``take_along_axis``
    to the bit, read a slot at a time by a compare against the experts' numbers
    and a sum with one term that is not zero. Its transpose is a dense
    ``where`` into [N, E]. (XLA:TPU gathers, and scatter-adds, a scalar at a
    time: 8 ns each, PERF.md section 7 row 7.)"""
    expert = jnp.arange(scores.shape[1], dtype=selected.dtype)
    return jnp.stack(
        [jnp.sum(jnp.where(selected[:, j, None] == expert, scores, 0), axis=1)
         for j in range(selected.shape[1])], axis=1)


def sigmoid_route(cfg, y: jax.Array, router: jax.Array, bias):
    """y: [N, H] -> (selected experts [N, k] int32, their weights [N, k]
    float32): ``s = sigmoid(y·router)`` over all the experts, the k
    (``cfg.experts_per_token``) largest of ``s + bias``, weights ``s`` (not
    ``bias``) renormalised over the chosen, times ``cfg.routed_scaling``.
    The selection carries ``gm.LAYOUT_NAME``: a layer's recomputation that
    saves it runs the sigmoid and no ``top_k``."""
    s = jax.nn.sigmoid(jnp.dot(y.astype(jnp.float32), router,
                               precision=lax.Precision.HIGHEST))
    _, selected = lax.top_k(s + bias, cfg.experts_per_token)
    selected = checkpoint_name(selected.astype(jnp.int32), gm.LAYOUT_NAME)
    w = chosen(s, selected)
    w = w / jnp.sum(w, axis=1, keepdims=True) * cfg.routed_scaling
    return selected, w


def softmax_route(cfg, y: jax.Array, router: jax.Array):
    """y: [N, H] -> (selected experts [N, k] int32, their weights [N, k]
    float32): softmax over all the experts, the k largest, renormalised
    (``norm_topk_prob: true``)."""
    p = jax.nn.softmax(jnp.dot(y.astype(jnp.float32), router,
                               precision=lax.Precision.HIGHEST), axis=-1)
    _, selected = lax.top_k(p, cfg.experts_per_token)
    selected = checkpoint_name(selected.astype(jnp.int32), gm.LAYOUT_NAME)
    # from the saved selection, not from top_k's values: a layer's
    # recomputation then runs the softmax and no top_k
    w = chosen(p, selected)
    return selected, w / jnp.sum(w, axis=1, keepdims=True)


def expert_mlp(y, selected, weights, w_gate, w_up, w_down, *, n_held: int,
               expert_offset: int, shared):
    """y: normed tokens [N, H]; ``selected`` [N, k] int32, the experts each
    token chose among ALL the router's; ``weights`` [N, k] float32, theirs;
    the held experts' stacks [n_held, H, F], [n_held, H, F], [n_held, F, H];
    ``shared``: y -> [N, H], what is added whatever the routing, or None
    where the model has no shared expert (nothing is added and no
    ``moe.shared`` scope opens). Returns
    ([N, H], stats): the assignments routed to held experts, those dropped
    (0 by construction, counted all the same), the fullest and the mean held
    expert's rows, the rows of the bound in use (whole tiles: what the row
    movement around the products works over) and ``selected``."""
    n, k = selected.shape
    with jax.named_scope("moe.sort"):
        local = selected - expert_offset
        group_of = jnp.where((local >= 0) & (local < n_held), local,
                             n_held).reshape(n * k)
        tile = gm.choose_tile(n * k, n_held)
        lay = gm.group_layout(group_of, n_held, tile)
    with jax.named_scope("moe.experts"):
        to_gate, to_up = gm.dispatch(y, lay, copies=2)
        product = lambda a, w: gm.grouped_matmul(  # noqa: E731
            a, w, lay.tile_group, lay.n_tiles)
        hidden = jax.nn.silu(product(to_gate, w_gate)) * product(to_up, w_up)
        rows = product(hidden, w_down)
    with jax.named_scope("moe.combine"):
        routed = gm.combine(rows, weights, lay)
    if shared is not None:
        with jax.named_scope("moe.shared"):
            alike = shared(y)
    with jax.named_scope("moe.sort"):               # the layout's counts
        n_routed = jnp.sum(lay.held.astype(jnp.int32))
        stats = {
            "routed": n_routed,
            "dropped": n_routed - jnp.sum(lay.row_valid.astype(jnp.int32)),
            "group_max": jnp.max(lay.group_sizes),
            "group_mean": jnp.mean(lay.group_sizes.astype(jnp.float32)),
            "rows_in_use": lay.n_tiles[0] * tile,
            "selected": selected,
        }
    if shared is None:
        return routed, stats
    with jax.named_scope("moe.shared"):
        return routed + alike, stats
