"""Operations and bytes the DeepSeek-V3-shaped train step NEEDS
(kanana-2-30b-a3b, one chip's share), from shapes and from the count of
assignments really routed to the experts held: never the padded bound of
the grouped product, never recomputed work. ``m`` is the model's sizes as
the configuration file gives them (``n_routed_experts``: the experts held).
"""

from __future__ import annotations

BF16 = 2


def mla_attention(m: dict, batch: int, seq: int) -> dict:
    """Causal attention of one layer, forward and backward, with q/k of
    nope + rope and v of its own width: QK^T and PV forward, dV, dP, dQ and
    dK backward (6 products over the t(t+1)/2 pairs at or below the
    diagonal; a fused kernel's recomputed scores do not count); q, k, v, o
    and dO read once, o, dQ, dK, dV written once."""
    nh = m["num_attention_heads"]
    d_qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    d_v = m["v_head_dim"]
    pairs = batch * nh * seq * (seq + 1) // 2
    rows = batch * nh * seq
    return {"flops": 3 * 2 * pairs * (d_qk + d_v),
            "bytes": BF16 * rows * (4 * d_qk + 4 * d_v)}


def routed_experts(m: dict, rows: int) -> dict:
    """The grouped products of one expert layer over ``rows`` assignments
    present: gate, up and down forward, and for each its two backward
    products (9 products of 2 * rows * hidden * width); each product reads
    its rows and the held experts' matrices and writes its result once."""
    h, f, held = (m["hidden_size"], m["moe_intermediate_size"],
                  m["n_routed_experts"])
    return {"flops": 9 * 2 * rows * h * f,
            "bytes": 9 * BF16 * (rows * (h + f) + held * h * f)}


def dsv3_train_step(m: dict, batch: int, seq: int, routed_rows) -> dict:
    """Model FLOPs of one forward + backward pass (3 x forward; recomputed
    work does not count). ``routed_rows``: per expert layer, the
    assignments routed to held experts in this step."""
    h, v = m["hidden_size"], m["vocab_size"]
    nh = m["num_attention_heads"]
    d_qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    t = batch * seq
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    mla = 2 * t * (h * nh * d_qk
                   + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                   + m["kv_lora_rank"] * nh * (m["qk_nope_head_dim"]
                                              + m["v_head_dim"])
                   + nh * m["v_head_dim"] * h)
    attn = mla_attention(m, batch, seq)["flops"] // 3
    dense_mlp = 2 * t * 3 * h * m["intermediate_size"]
    f = m["moe_intermediate_size"]
    moe_mlp = 2 * t * (h * m["router_experts"]
                       + 3 * h * m["n_shared_experts"] * f)
    routed = sum(2 * 3 * int(r) * h * f for r in routed_rows)
    fwd = (m["num_hidden_layers"] * (mla + attn) + n_dense * dense_mlp
           + n_moe * moe_mlp + routed + 2 * t * h * v)
    return {"flops": 3 * fwd, "tokens": t}
