"""Readers of the Mellum2 train step's kernels in the profiler's trace
(reduced by ``trace_reduce``): rooflines whose work comes from
``work_mellum``, which ``readers/windowed.py`` and ``readers/train.py``
cannot give (they count heads a layer kind and an expert count under other
keys). Returns None where there is nothing to read: no trace, a program
without such ops, or a driver that left no sizes."""

from __future__ import annotations

import work
import work_mellum
from readers import train


def kernel_roofline(run, prefix: str, work_fn: str, layers: str = None):
    """The least time the chip could take for the work that the traced
    steps NEEDED of one kind of kernel, over the device time of the ops
    named ``prefix*``. ``work_fn``: ``band_attention`` (a window layer's
    visible pairs; the tile pairs the band only touches are in the time, not
    in the work) or ``gqa_attention`` (a full layer's causal pairs), of
    which the depth held has so many ``layers`` (``window`` or ``full``); or
    ``routed_experts`` (per expert layer and step, from the assignments the
    step's stats counted)."""
    seconds = train._op_seconds(run, prefix)
    c = run["outcome"].counters
    if seconds is None or not c.get("calls_in_trace") or "sizes" not in c:
        return None
    m, n = c["sizes"], c["calls_in_trace"]
    if work_fn == "routed_experts":
        least = sum(work.roofline_seconds(
            work_mellum.routed_experts(m, rows), run["peak"])
            for step in c["routed_rows"][:n] for rows in step)
    else:
        count = dict(zip(("window", "full"),
                         work_mellum.layer_counts(m)))[layers]
        w = getattr(work_mellum, work_fn)(m, c["batch"], c["sequence"])
        least = work.roofline_seconds(w, run["peak"]) * n * count
    return 100.0 * least / seconds
