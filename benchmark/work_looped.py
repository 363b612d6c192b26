"""Operations and bytes the looped language model's train step NEEDS
(Ouro-2.6B: one stack of layers run ``total_ut_steps`` times, a head after
every pass), from shapes alone: every layer application and every head
counted once forward and twice backward, never the recomputation. ``m`` is
the model's sizes as the configuration file gives them.
"""

from __future__ import annotations

BF16 = 2


def mha_attention(m: dict, batch: int, seq: int) -> dict:
    """Causal attention of ONE layer application, forward and backward, q,
    k and v of one width: QK^T and PV forward, dV, dP, dQ and dK backward (6
    products over the t(t+1)/2 pairs at or below the diagonal; a fused
    kernel's recomputed scores do not count); q, k, v, o and dO read once,
    o, dQ, dK, dV written once."""
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    pairs = batch * nh * seq * (seq + 1) // 2
    return {"flops": 6 * 2 * pairs * d,
            "bytes": BF16 * batch * seq * d * (4 * nh + 4 * nkv)}


def layer_applications(m: dict) -> int:
    return m["total_ut_steps"] * m["num_hidden_layers"]


def looped_train_step(m: dict, batch: int, seq: int) -> dict:
    """Model FLOPs of one forward + backward pass (3 x forward): the
    layers' seven matrices ``total_ut_steps`` times, the head as often, and
    causal attention in every application."""
    h, inter, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    hkv = m["num_key_value_heads"] * m["head_dim"]
    t = batch * seq
    layer = 2 * t * (h * hq + 2 * h * hkv + hq * h + 3 * h * inter)
    attn = mha_attention(m, batch, seq)["flops"] // 3
    fwd = (layer_applications(m) * (layer + attn)
           + m["total_ut_steps"] * 2 * t * h * v)
    return {"flops": 3 * fwd, "tokens": t}
