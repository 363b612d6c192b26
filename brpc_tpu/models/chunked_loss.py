"""Next-token loss with the output head taken a chunk of positions at a time.

No [B*T, vocab] float32 logits exist in either pass: a ``lax.scan`` runs over
chunks of the flattened positions, each chunk's logits are made, reduced to a
negative log-likelihood per position and dropped, and the chunk is recomputed
in the backward pass (``jax.checkpoint``). The head may be applied to the
final states of one forward pass (``models/deepseek.py``) or of several
(``models/looped.py``: one a pass of its loop, on one head), and what a
position's loss is made of — its one likelihood, or all the passes' weighted
by an exit distribution — is the caller's ``position_loss``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def chunk_size(n: int, passes: int = 1) -> int:
    """Positions whose logits exist at once: the largest divisor of n up to
    1,024 over the passes that share a chunk (at one pass 66 MB of float32
    at a vocabulary slice of 16,032; at four passes of 256 positions 201 MB
    at a vocabulary of 49,152)."""
    return next(c for c in range(min(n, 1024 // passes), 0, -1) if n % c == 0)


def chunked_next_token_loss(states, head, tokens, position_loss, extras=()):
    """The mean over the positions that predict a token (the last of a
    sequence predicts nothing) of what ``position_loss`` gives.

    ``states``: a tuple of final states [B, T, H], one a pass; ``head``
    [H, vocab] in their dtype; ``tokens`` [B, T]; ``extras``: arrays
    [B, T, ...] handed on by chunk. ``position_loss(nlls, *extras_c)`` gets
    a tuple of float32 negative log-likelihoods [chunk], one a pass, and the
    extras' rows of the chunk, and returns an array, or a tree of arrays,
    with the positions leading; each leaf comes back as its mean."""
    b, t, h = states[0].shape
    targets = jnp.roll(tokens, -1, axis=1).reshape(b * t)
    counts = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t)).reshape(b * t)
    chunk = chunk_size(b * t, len(states))

    def counted_sum(total, value, counts_c):
        where = counts_c.reshape((-1,) + (1,) * (value.ndim - 1)) \
            if value.ndim > 1 else counts_c
        return total + jnp.sum(jnp.where(where, value, 0.0), axis=0)

    @jax.checkpoint
    def piece(total, args):
        xs_c, target_c, counts_c, extras_c = args
        nlls = []
        for x_c in xs_c:
            with jax.named_scope("loss.logits"):
                logits = jnp.dot(x_c, head,
                                 preferred_element_type=jnp.float32)
            with jax.named_scope("loss.nll"):
                gold = jnp.take_along_axis(logits, target_c[:, None],
                                           axis=1)[:, 0]
                nlls.append(jax.nn.logsumexp(logits, axis=-1) - gold)
        values = position_loss(tuple(nlls), *extras_c)
        return jax.tree_util.tree_map(
            lambda a, v: counted_sum(a, v, counts_c), total, values), None

    by_chunk = lambda a: a.reshape(-1, chunk, *a.shape[2:])  # noqa: E731
    extras = tuple(by_chunk(e) for e in extras)
    nll = jax.ShapeDtypeStruct((chunk,), jnp.float32)
    shapes = jax.eval_shape(
        position_loss, (nll,) * len(states),
        *(jax.ShapeDtypeStruct(e.shape[1:], e.dtype) for e in extras))
    zero = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape[1:], jnp.float32), shapes)
    total, _ = lax.scan(piece, zero, (
        tuple(x.reshape(-1, chunk, h) for x in states),
        targets.reshape(-1, chunk), counts.reshape(-1, chunk), extras))
    return jax.tree_util.tree_map(lambda s: s / (b * (t - 1)), total)
