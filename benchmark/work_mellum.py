"""Operations and bytes the Mellum2 train step NEEDS (one chip's share: no
gate, no shared expert, no dense layer, a softmax router of 64 outputs),
from shapes and from the count of assignments really routed to the experts
held: never a tile pair the band only touches, never a padded bound, never
recomputed work. The same work whatever implements it. ``m`` is the model's
sizes as the configuration file gives them (``num_experts``: the experts
held; ``router_experts``: the router's width; the per-layer lists read up
to ``num_hidden_layers``). What ``work_swa`` and ``work_dsv3`` count alike
is their function.
"""

from __future__ import annotations

import work_dsv3
import work_swa


def band_attention(m: dict, batch: int, seq: int) -> dict:
    """Sliding-window attention of ONE window layer, forward and backward,
    over exactly the pairs the window shows (7,864,832 a head at 8,192 /
    1,024): ``work_swa``'s count at this model's one head count."""
    return work_swa._attention(
        m, batch, seq, m["num_attention_heads"],
        work_swa.visible_pairs(seq, m["sliding_window"]))


def gqa_attention(m: dict, batch: int, seq: int) -> dict:
    """Causal grouped-query attention of ONE full layer, forward and
    backward, over the seq (seq + 1) / 2 pairs at or below the diagonal."""
    return work_swa._attention(m, batch, seq, m["num_attention_heads"],
                               seq * (seq + 1) // 2)


def routed_experts(m: dict, rows: int) -> dict:
    """The grouped products of one expert layer over ``rows`` assignments
    present (``work_dsv3``'s count: 9 products of 2 * rows * hidden *
    width, rows and the held experts' matrices moved once a product), the
    experts held under this configuration's key."""
    return work_dsv3.routed_experts(
        dict(m, n_routed_experts=m["num_experts"]), rows)


def layer_counts(m: dict) -> tuple:
    """(window layers, full layers) of the depth held."""
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    return kinds.count("sliding_attention"), kinds.count("full_attention")


def mellum_train_step(m: dict, batch: int, seq: int, routed_rows) -> dict:
    """Model FLOPs of one forward + backward pass (3 x forward; recomputed
    work does not count). ``routed_rows``: per expert layer, the
    assignments routed to held experts in this step."""
    h, v, d = m["hidden_size"], m["vocab_size"], m["head_dim"]
    nh, nkv, t = (m["num_attention_heads"], m["num_key_value_heads"],
                  batch * seq)
    n_window, n_full = layer_counts(m)
    proj = 2 * t * (2 * h * nh * d + 2 * h * nkv * d)       # q, o; k, v
    scores = (n_window * band_attention(m, batch, seq)["flops"]
              + n_full * gqa_attention(m, batch, seq)["flops"]) // 3
    router = 2 * t * h * m["router_experts"]
    routed = sum(2 * 3 * int(r) * h * m["moe_intermediate_size"]
                 for r in routed_rows)
    fwd = ((n_window + n_full) * (proj + router) + scores + routed
           + 2 * t * h * v)
    return {"flops": 3 * fwd, "tokens": t}
