"""Ring attention — sequence/context parallelism over the ICI ring.

The reference has no sequence parallelism (SURVEY.md §5.8); its scaffolding
for it is the combo-channel fan-out + the streaming pipe.  The TPU-native
realization: shard the sequence over a mesh axis ('sp'), keep Q resident,
and rotate K/V blocks around the ring with ``lax.ppermute`` while
accumulating attention with an online (flash-style) softmax — compute on
block i overlaps the transfer of block i+1, so the ring latency hides
behind the MXU work (jax-ml.github.io/scaling-book recipe; RingAttention,
Liu et al. 2023).

Causal masking across ring steps uses global block positions: ring step s
on device d holds KV block (d - s) mod n; a Q block attends iff
kv_block <= q_block, with the diagonal block applying the triangular mask.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _block_attend(q, k, v, mask):
    """One block pair: returns (unnormalized out, row max, row sumexp).

    q: [B,Tq,Hkv,G,D]  k/v: [B,Tk,Hkv,D]  mask: [Tq,Tk] additive (0/-inf).
    """
    d = q.shape[-1]
    scores = jnp.einsum("bthgd,bshd->bhgts", q, k).astype(jnp.float32)
    scores = scores * (d ** -0.5) + mask[None, None, None]
    # The row max is only the softmax's shift: the result does not depend on
    # it, so its total derivative is exactly zero and it is held constant
    # (as flash attention's backward does). Left in, the max's VJP into the
    # bf16 scores made every dq/dk NaN on a v5e — finite on the CPU — in the
    # sp=2 ring and in the model's backward even at sp=1 (PR 21 chip runs).
    m = lax.stop_gradient(jnp.max(scores, axis=-1))     # [B,H,G,Tq]
    # guard fully-masked rows (exp(-inf - -inf))
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])             # [B,H,G,Tq,Ts]
    l = jnp.sum(p, axis=-1)                             # [B,H,G,Tq]
    o = jnp.einsum("bhgts,bshd->bthgd", p.astype(v.dtype), v)
    return o.astype(jnp.float32), m_safe, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Online-softmax merge of two partial attention states."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    # o: [B,T,H,G,D]; m/l: [B,H,G,T] -> broadcast to o layout
    def scale(o, a):
        return o * jnp.transpose(a, (0, 3, 1, 2))[..., None]
    return scale(o1, a1) + scale(o2, a2), m, l1 * a1 + l2 * a2


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    head_axis: str | None = None,
) -> jax.Array:
    """Sequence-sharded GQA attention.

    q: [B, T, Hq, D], k/v: [B, T, Hkv, D] — T is the GLOBAL sequence,
    sharded over ``axis`` (dim 1). ``head_axis`` optionally keeps the head
    dim sharded (tensor parallelism composes: sp rotates KV while tp splits
    heads). Returns [B, T, Hq*D] with the same sharding as q.
    """
    n = mesh.shape[axis]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(None, axis, head_axis),
            P(None, axis, head_axis),
            P(None, axis, head_axis),
        ),
        out_specs=P(None, axis, head_axis),
        check_vma=False,
    )
    def _ring(q_blk, k_blk, v_blk):
        b, t, hq_l, d = q_blk.shape
        hkv_l = k_blk.shape[2]
        group = hq_l // hkv_l
        my = lax.axis_index(axis)
        qg = q_blk.reshape(b, t, hkv_l, group, d)

        neg = jnp.float32(-1e30)
        tri = jnp.where(
            jnp.tril(jnp.ones((t, t), bool)), 0.0, neg
        ).astype(jnp.float32)
        zeros = jnp.zeros((t, t), jnp.float32)
        full_neg = jnp.full((t, t), neg, jnp.float32)

        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, s):
            o, m, l, kc, vc = carry
            kv_idx = (my - s) % n
            if causal:
                mask = jnp.where(
                    kv_idx == my, tri,
                    jnp.where(kv_idx < my, zeros, full_neg),
                )
            else:
                mask = zeros
            o2, m2, l2 = _block_attend(qg, kc, vc, mask)
            o, m, l = _merge(o, m, l, o2, m2, l2)
            # rotate KV to the next device; the compiler overlaps this
            # ppermute with the next iteration's compute
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return (o, m, l, kc, vc), None

        o0 = jnp.zeros((b, t, hkv_l, group, d), jnp.float32)
        m0 = jnp.full((b, hkv_l, group, t), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv_l, group, t), jnp.float32)
        (o, m, l, _, _), _ = lax.scan(
            step, (o0, m0, l0, k_blk, v_blk), jnp.arange(n)
        )
        denom = jnp.transpose(l, (0, 3, 1, 2))[..., None]
        out = o / jnp.maximum(denom, 1e-20)
        return out.reshape(b, t, hq_l * d).astype(q_blk.dtype)

    return _ring(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """DeepSpeed-Ulysses-style sequence parallelism: all_to_all swaps the
    sharded dim from sequence to heads, runs FULL-sequence attention on a
    head subset per device, and swaps back.  Complements ring attention:
    better when heads >> devices and the sequence fits per-device HBM.
    """
    n = mesh.shape[axis]
    hq, hkv = q.shape[2], k.shape[2]
    if hkv % n != 0:
        raise ValueError(f"kv heads {hkv} not divisible by axis size {n}")
    group = hq // hkv

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )
    def _ulysses(q_blk, k_blk, v_blk):
        # [B, T/n, H, D] -> all_to_all -> [B, T, H/n, D]
        def seq2head(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        def head2seq(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        qh, kh, vh = seq2head(q_blk), seq2head(k_blk), seq2head(v_blk)
        b, t, hq_l, d = qh.shape
        hkv_l = kh.shape[2]
        qg = qh.reshape(b, t, hkv_l, hq_l // hkv_l, d)
        mask = (
            jnp.where(jnp.tril(jnp.ones((t, t), bool)), 0.0, -1e30)
            if causal else jnp.zeros((t, t))
        ).astype(jnp.float32)
        o, m, l = _block_attend(qg, kh, vh, mask)
        denom = jnp.transpose(l, (0, 3, 1, 2))[..., None]
        out = (o / jnp.maximum(denom, 1e-20)).astype(q_blk.dtype)
        out = out.reshape(b, t, hq_l, d)
        return head2seq(out).reshape(b, q_blk.shape[1], hq * d)

    return _ulysses(q, k, v)
