"""Operations and bytes the hybrid Mamba-2 / attention train step NEEDS
(granite-4.0-h-micro, one pipeline stage's share), from shapes alone:
never recomputed work. The state-space scan's work is counted in its
chunked form at the published chunk (``mamba_chunk_size``, 256), forward and
backward, whatever chunk the kernels use, so a roofline reads the same work
whatever implements it. ``m`` is the model's sizes as the configuration file
gives them.
"""

from __future__ import annotations

BF16, F32 = 2, 4
PUBLISHED_CHUNK = 256


def layer_counts(m: dict) -> tuple:
    """(Mamba layers, attention layers) of the depth held."""
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    return kinds.count("mamba"), kinds.count("attention")


def _ssm(m: dict) -> tuple:
    nh, p, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    return nh, p, n, nh * p, nh * p + 2 * m["mamba_n_groups"] * n


def ssd_scan(m: dict, batch: int, seq: int) -> dict:
    """The scan of ONE Mamba layer, forward and backward (3 x forward). A
    token forward, in chunks of Q = 256: G = C B^T, 2 Q N, once for every
    head (one group); a head's masked product with its x, 2 Q P (the whole
    [Q, Q] block, as the chunked form multiplies it), the read of the
    entering state, C S, 2 N P, and the write of the chunk's state,
    B^T (w x), 2 N P. Bytes: x, y, B, C (bf16) and Delta, a (float32) read
    or written once, and as much again for their gradients and dy."""
    nh, p, n, d_in, _ = _ssm(m)
    q, tokens = PUBLISHED_CHUNK, batch * seq
    fwd = tokens * (nh * (2 * q * p + 4 * n * p) + 2 * q * n)
    once = tokens * (BF16 * (2 * d_in + 2 * n) + F32 * 2 * nh)
    return {"flops": 3 * fwd, "bytes": 2 * once}


def gqa_attention(m: dict, batch: int, seq: int) -> dict:
    """Causal grouped-query attention of ONE attention layer, forward and
    backward, heads of hidden / heads: QK^T and PV forward, dV, dP, dQ and
    dK backward (6 products over the t(t+1)/2 pairs at or below the
    diagonal of every query head); q, k, v, o and dO read once, o, dQ, dK,
    dV written once."""
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // nh
    pairs = batch * nh * seq * (seq + 1) // 2
    return {"flops": 6 * 2 * pairs * d,
            "bytes": BF16 * batch * seq * d * (4 * nh + 4 * nkv)}


def parameters(m: dict) -> int:
    """Numbers in the weights held: the Mamba and attention layers with
    their MLPs and norms, the tied embedding once, the final norm."""
    h, f = m["hidden_size"], m["shared_intermediate_size"]
    nh, _, _, d_in, conv = _ssm(m)
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    d = h // heads
    mlp = 3 * h * f + 2 * h
    mamba = (h * (conv + d_in + nh) + d_in * h + m["mamba_d_conv"] * conv
             + conv + 3 * nh + d_in + mlp)
    attention = h * (heads + 2 * kv) * d + heads * d * h + mlp
    n_mamba, n_attention = layer_counts(m)
    return (n_mamba * mamba + n_attention * attention
            + m["vocab_size"] * h + h)


def mamba_train_step(m: dict, batch: int, seq: int) -> dict:
    """Model FLOPs of one forward + backward pass (3 x forward; recomputed
    work does not count): every matmul at 2*m*n*k, the convolution's
    multiply-adds, the scan as ``ssd_scan`` counts it, causal attention's
    pairs, the tied head."""
    h, v, f = m["hidden_size"], m["vocab_size"], m["shared_intermediate_size"]
    nh, _, _, d_in, conv = _ssm(m)
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    d = h // heads
    t = batch * seq
    mlp = 2 * t * 3 * h * f
    mamba = (2 * t * (h * (conv + d_in + nh) + d_in * h)
             + 2 * t * m["mamba_d_conv"] * conv
             + ssd_scan(m, batch, seq)["flops"] // 3 + mlp)
    attention = (2 * t * (h * (heads + 2 * kv) * d + heads * d * h)
                 + gqa_attention(m, batch, seq)["flops"] // 3 + mlp)
    n_mamba, n_attention = layer_counts(m)
    fwd = n_mamba * mamba + n_attention * attention + 2 * t * h * v
    return {"flops": 3 * fwd, "tokens": t}
