"""Operations and bytes a call NEEDS, from shapes alone.

A roofline share divides the least time the chip could take for this work
by the time a kernel took, so it reads the same work whatever implements
it: the scatter's count is the rows it must read and write and the
gradients it must read, not the whole-table copy today's program makes.
"""

from __future__ import annotations

F32 = 4


def gather_rows(k: int, dim: int) -> dict:
    """Read k ids and k rows, write k rows."""
    return {"flops": 0, "bytes": k * 4 + 2 * k * dim * F32}


def scatter_sub(k: int, dim: int) -> dict:
    """Read k ids, k gradient rows and k table rows; write k table rows;
    one multiply (by lr) and one subtract per element."""
    return {"flops": 2 * k * dim, "bytes": k * 4 + 3 * k * dim * F32}


def ps_train_step(k: int, dim: int) -> dict:
    """One trainer step on the device: a gather and a scatter of k rows."""
    g, s = gather_rows(k, dim), scatter_sub(k, dim)
    return {"flops": g["flops"] + s["flops"], "bytes": g["bytes"] + s["bytes"]}


def llama_train_step(m: dict, batch: int, seq: int) -> dict:
    """Model FLOPs of one forward + backward pass (3 x forward; recomputed
    work does not count): every matmul at 2*m*n*k, causal attention at half
    of the full score and value products. ``m`` is the model's sizes as the
    configuration file gives them."""
    h, inter, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    hkv = m["num_key_value_heads"] * m["head_dim"]
    t = batch * seq
    per_layer = 2 * t * (h * hq + 2 * h * hkv + hq * h + 3 * h * inter)
    attn = 2 * 2 * batch * seq * seq * hq // 2      # QK^T and PV, causal
    fwd = m["num_hidden_layers"] * (per_layer + attn) + 2 * t * h * v
    return {"flops": 3 * fwd, "tokens": t}


def all_reduce(bytes_per_chip: int, n: int) -> dict:
    """Ring all-reduce: each chip sends and receives 2(n-1)/n of its
    shard's bytes."""
    return {"bus_bytes": 2 * (n - 1) * bytes_per_chip // n}


def roofline_seconds(work: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the peak rate and bytes over the peak bandwidth."""
    return max(work.get("flops", 0) / peak["bf16_flops_per_s"],
               work.get("bytes", 0) / peak["hbm_bytes_per_s"])
