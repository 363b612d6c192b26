"""Readers of the window-and-full-attention train step's kernels in the
profiler's trace (reduced by ``trace_reduce``): a roofline whose work comes
from ``work_swa``. Returns None where there is nothing to read: no trace, a
program without such ops, or a driver that left no sizes."""

from __future__ import annotations

import work
import work_swa
from readers import train


def kernel_roofline(run, prefix: str, work_fn: str, layers: str):
    """The least time the chip could take for the work that the traced
    steps NEEDED of one kind of attention, over the device time of the ops
    named ``prefix*``. ``work_fn``: ``band_attention`` (a window layer's
    visible pairs; the tile pairs the band only touches are in the time, not
    in the work) or ``gqa_attention`` (a full layer's causal pairs);
    ``layers``: the kind of layer that runs it, ``window`` or ``full``, of
    which the depth held has so many."""
    seconds = train._op_seconds(run, prefix)
    c = run["outcome"].counters
    if seconds is None or not c.get("calls_in_trace") or "sizes" not in c:
        return None
    m = c["sizes"]
    count = dict(zip(("window", "full"), work_swa.layer_counts(m)))[layers]
    w = getattr(work_swa, work_fn)(m, c["batch"], c["sequence"])
    least = work.roofline_seconds(w, run["peak"]) * c["calls_in_trace"]
    return 100.0 * least * count / seconds
