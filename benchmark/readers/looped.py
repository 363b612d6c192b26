"""Readers of the looped language model's train step in the profiler's trace
(reduced by ``trace_reduce``). Each returns None where there is nothing to
read: no trace, a program without such ops, or a driver that left no sizes.
"""

from __future__ import annotations

import work
import work_looped
from readers import train


def attn_roofline(run, prefix: str):
    """The least time the chip could take for the causal multi-head work of
    the traced steps' layer applications (``work_looped.mha_attention``), over
    the device time of the ops named ``prefix*``."""
    seconds = train._op_seconds(run, prefix)
    c = run["outcome"].counters
    if seconds is None or not c.get("calls_in_trace") or "sizes" not in c:
        return None
    m = c["sizes"]
    w = work_looped.mha_attention(m, c["batch"], c["sequence"])
    least = (work.roofline_seconds(w, run["peak"]) * c["calls_in_trace"]
             * work_looped.layer_applications(m))
    return 100.0 * least / seconds
