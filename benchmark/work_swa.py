"""Operations and bytes the window-and-full-attention train step NEEDS
(Laguna-XS.2, one chip's share), from shapes and from the count of
assignments really routed to the experts held: never a tile pair the band
only touches, never a padded bound, never recomputed work. ``m`` is the
model's sizes as the configuration file gives them (``num_experts``: the
experts held; ``router_experts``: the router's width; the per-layer lists
read up to ``num_hidden_layers``).
"""

from __future__ import annotations

BF16 = 2


def visible_pairs(seq: int, window: int) -> int:
    """(query, key) pairs with 0 <= i - j < window in a sequence of ``seq``:
    the first ``window`` queries see 1, 2, ... keys, every later one
    ``window``. 4,063,488 at 8,192 / 512; ``seq (seq + 1) / 2`` from a window
    of ``seq`` on."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _heads(m: dict, kind: str) -> int:
    return next(h for t, h in zip(m["layer_types"],
                                  m["num_attention_heads_per_layer"])
                if t == kind)


def _attention(m: dict, batch: int, seq: int, heads: int, pairs: int) -> dict:
    """QK^T and PV forward, dV, dP, dQ and dK backward (6 products of 2 *
    head_dim a visible pair and query head; a fused kernel's recomputed
    scores do not count); q, k, v, o and dO read once, o, dQ, dK, dV written
    once."""
    d, nkv = m["head_dim"], m["num_key_value_heads"]
    return {"flops": 6 * 2 * batch * heads * pairs * d,
            "bytes": BF16 * batch * seq * d * (4 * heads + 4 * nkv)}


def band_attention(m: dict, batch: int, seq: int) -> dict:
    """Sliding-window attention of ONE window layer, forward and backward,
    over exactly the pairs the window shows."""
    return _attention(m, batch, seq, _heads(m, "sliding_attention"),
                      visible_pairs(seq, m["sliding_window"]))


def gqa_attention(m: dict, batch: int, seq: int) -> dict:
    """Causal grouped-query attention of ONE full layer, forward and
    backward, over the seq (seq + 1) / 2 pairs at or below the diagonal."""
    return _attention(m, batch, seq, _heads(m, "full_attention"),
                      seq * (seq + 1) // 2)


def layer_counts(m: dict) -> tuple:
    """(window layers, full layers) of the depth held."""
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    return kinds.count("sliding_attention"), kinds.count("full_attention")


def windowed_train_step(m: dict, batch: int, seq: int, routed_rows) -> dict:
    """Model FLOPs of one forward + backward pass (3 x forward; recomputed
    work does not count). ``routed_rows``: per expert layer, the
    assignments routed to held experts in this step."""
    h, v, d = m["hidden_size"], m["vocab_size"], m["head_dim"]
    nkv, t = m["num_key_value_heads"], batch * seq
    n_window, n_full = layer_counts(m)
    n_dense = m["mlp_layer_types"][:m["num_hidden_layers"]].count("dense")

    def mixer(heads, attention):       # q, k, v, the gate, o; the scores
        return (2 * t * (h * heads * d + 2 * h * nkv * d + h * heads
                         + heads * d * h) + attention["flops"] // 3)

    window = mixer(_heads(m, "sliding_attention"),
                   band_attention(m, batch, seq))
    full = mixer(_heads(m, "full_attention"), gqa_attention(m, batch, seq))
    dense = 2 * t * 3 * h * m["intermediate_size"]
    f = m["moe_intermediate_size"]
    moe = 2 * t * (h * m["router_experts"]
                   + 3 * h * m["shared_expert_intermediate_size"])
    routed = sum(2 * 3 * int(r) * h * f for r in routed_rows)
    fwd = (n_window * window + n_full * full + n_dense * dense
           + (m["num_hidden_layers"] - n_dense) * moe + routed
           + 2 * t * h * v)
    return {"flops": 3 * fwd, "tokens": t}
