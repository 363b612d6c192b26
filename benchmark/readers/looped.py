"""Readers of the looped language model's train step in the profiler's trace
(reduced by ``trace_reduce``). Each returns None where there is nothing to
read: no trace, a program without such ops, or a driver that left no sizes.
"""

from __future__ import annotations

import re

import work
import work_looped
from readers import train

_SHAPE = re.compile(r"\[([\d,]*)\]$")


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


def vocab_ops_share_of_step(run, program: str):
    """Device seconds of the ops whose result has the vocabulary's
    dimension, over the device seconds of the step program's executions:
    the heads' products and the loss over the passes (and, a few
    milliseconds of it, the optimizer's passes over embedding and head)."""
    t = run["trace"]
    c = run["outcome"].counters
    if not t or "sizes" not in c:
        return None
    vocab = str(c["sizes"]["vocab_size"])

    def has_vocab(key):
        shape = _SHAPE.search(key)
        return shape is not None and vocab in shape.group(1).split(",")

    seconds = sum(v for k, v in t["op_seconds"].items()
                  if k.startswith(program + ":") and has_vocab(k))
    whole = t["module_seconds"].get(program)
    return 100.0 * seconds / whole if seconds and whole else None
