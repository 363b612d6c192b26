"""Readers of the hybrid Mamba-2 train step's kernels in the profiler's trace
(reduced by ``trace_reduce``): rooflines whose work comes from
``work_mamba``, which ``readers/train.py`` and ``readers/hybrid.py`` cannot
give (they count other layers under other keys). Returns None where there is
nothing to read: no trace, a program without such ops, or a driver that left
no sizes."""

from __future__ import annotations

import work
import work_mamba
from readers import train


def kernel_roofline(run, prefix: str, work_fn: str, layers: str):
    """The least time the chip could take for the work that the traced
    steps NEEDED of one kind of kernel, over the device time of the ops
    named ``prefix*``. ``work_fn``: ``ssd_scan`` (a Mamba layer's scan, in
    the chunked form at the published chunk) or ``gqa_attention`` (an
    attention layer's causal pairs); ``layers``: the kind of layer that
    runs it, ``mamba`` or ``attention``, of which the depth held has so
    many."""
    seconds = train._op_seconds(run, prefix)
    c = run["outcome"].counters
    if seconds is None or not c.get("calls_in_trace") or "sizes" not in c:
        return None
    m = c["sizes"]
    count = dict(zip(("mamba", "attention"),
                     work_mamba.layer_counts(m)))[layers]
    w = getattr(work_mamba, work_fn)(m, c["batch"], c["sequence"])
    least = work.roofline_seconds(w, run["peak"]) * c["calls_in_trace"]
    return 100.0 * least * count / seconds
