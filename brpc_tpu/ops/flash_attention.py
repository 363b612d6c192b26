"""Flash attention — pallas TPU kernel for the model hot path.

The attention score matrix never touches HBM: each grid program owns one
[BLOCK_Q, D] query tile in VMEM and streams K/V tiles through the MXU with
the online-softmax recurrence (running max / sum / accumulator). Causal
programs stop at the diagonal tile, so the wasted-FLOPs triangle is skipped
at tile granularity (guide: /opt/skills/guides/pallas_guide.md).

GQA layout matches brpc_tpu.models.llama: q [B, T, Hq, D], k/v
[B, T, Hkv, D]; the kv head for q head h is h // (Hq // Hkv). Inside, the
kernel works head-major ([B, H, T, D]): Mosaic wants the last two block
dimensions to be (a multiple of 8, a multiple of 128) or the whole array
dimension, and a one-head block of the [B, T, H, D] layout has 1 against H
there. The wrapper pays two transposes for it.

``flash_attention(..., interpret=True)`` runs the same kernel through the
pallas interpreter (CPU tests); on TPU leave it False.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
            seq_len: int, causal: bool, scale: float):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # [BQ, D]
    bq, d = q.shape

    row = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    n_kv_total = seq_len // block_k
    if causal:
        # tiles fully above the diagonal contribute nothing
        last_row = qi * block_q + block_q - 1
        n_kv = jnp.minimum((last_row // block_k) + 1, n_kv_total)
    else:
        n_kv = n_kv_total

    def body(kj, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [BQ, BK]
        if causal:
            col = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col <= row, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-20)
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q: [B,T,Hq,D], k/v: [B,T,Hkv,D] -> [B,T,Hq*D] (llama.attention
    contract)."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(f"seq {t} must divide blocks {block_q}/{block_k}")
    scale = d ** -0.5

    grid = (b, hq, t // block_q)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))  # [B, H, T, D]
    out = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          seq_len=t, causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, t, d),
                         lambda bi, h, qi: (bi, h // group, 0, 0)),
            pl.BlockSpec((1, 1, t, d),
                         lambda bi, h, qi: (bi, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, h, qi: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, t, d), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out.transpose(0, 2, 1, 3).reshape(b, t, hq * d)
