"""Operations and bytes the hybrid linear-attention train step NEEDS
(Qwen3-Next-80B-A3B, one chip's share), from shapes and from the count of
assignments really routed to the experts held: never a padded bound, never
recomputed work, never what a chunked form adds to the recurrence. ``m`` is
the model's sizes as the configuration file gives them (``num_experts``:
the experts held; ``router_experts``: the router's width).
"""

from __future__ import annotations

BF16, F32 = 2, 4


def gated_delta_rule(m: dict, batch: int, seq: int) -> dict:
    """The recurrence of ONE linear layer, forward and backward, whatever
    implements it. A token and value head forward: the decayed state read
    against k (S^T k), the write k delta^T and the read against q (S^T q),
    three products of 2 * dk * dv; backward twice that (9 in all). A
    chunked form's local products (k k^T, its inverse, q k^T) are in the
    time, not in the work. Bytes: q, k of the key heads, v, o of the value
    heads and g, beta read or written once, and as much again for their
    gradients and dO."""
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    tokens = batch * seq
    once = tokens * (BF16 * (2 * hk * dk + 2 * hv * dv) + F32 * 2 * hv)
    return {"flops": 9 * 2 * tokens * hv * dk * dv, "bytes": 2 * once}


def gqa_attention(m: dict, batch: int, seq: int) -> dict:
    """Causal grouped-query attention of ONE full layer, forward and
    backward, q, k and v of ``head_dim``: QK^T and PV forward, dV, dP, dQ
    and dK backward (6 products over the t(t+1)/2 pairs at or below the
    diagonal of every query head; a fused kernel's recomputed scores do not
    count); q, k, v, o and dO read once, o, dQ, dK, dV written once."""
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    pairs = batch * nh * seq * (seq + 1) // 2
    return {"flops": 6 * 2 * pairs * d,
            "bytes": BF16 * batch * seq * d * (4 * nh + 4 * nkv)}


def layer_counts(m: dict) -> tuple:
    """(linear layers, full layers) of the depth held."""
    full = m["num_hidden_layers"] // m["full_attention_interval"]
    return m["num_hidden_layers"] - full, full


def hybrid_train_step(m: dict, batch: int, seq: int, routed_rows) -> dict:
    """Model FLOPs of one forward + backward pass (3 x forward; recomputed
    work does not count). ``routed_rows``: per layer, the assignments
    routed to held experts in this step."""
    h, v = m["hidden_size"], m["vocab_size"]
    t = batch * seq
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    n_linear, n_full = layer_counts(m)
    linear = (2 * t * (h * (2 * key_dim + 2 * value_dim) + h * 2 * hv
                       + value_dim * h)
              + 2 * t * m["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)
              + gated_delta_rule(m, batch, seq)["flops"] // 3)
    full = (2 * t * (h * 2 * nh * d + 2 * h * nkv * d + nh * d * h)
            + gqa_attention(m, batch, seq)["flops"] // 3)
    f = m["moe_intermediate_size"]
    moe = 2 * t * (h * m["router_experts"]
                   + 3 * h * m["shared_expert_intermediate_size"] + h)
    routed = sum(2 * 3 * int(r) * h * f for r in routed_rows)
    fwd = (n_linear * linear + n_full * full + m["num_hidden_layers"] * moe
           + routed + 2 * t * h * v)
    return {"flops": 3 * fwd, "tokens": t}
