"""Readers of the profiler's trace (reduced by ``trace_reduce``)."""

from __future__ import annotations

import harness
import work


def idle_share(run):
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None


def program_roofline(run, program: str, work_fn: str):
    """The least time the chip could take for the work that the program's
    launches in the traced window NEEDED (rows really asked for, not the
    padded bucket, not the table copy), over the device time of all the
    program's operations."""
    t = run["trace"]
    c = run["outcome"].counters
    if not t or not c.get("calls_in_trace"):
        return None
    seconds = sum(v for k, v in t["op_seconds"].items()
                  if k.startswith(program + ":"))
    if seconds <= 0:
        return None
    w = getattr(work, work_fn)(c["ids_per_call"], c["dim"])
    least = work.roofline_seconds(w, run["peak"]) * c["calls_in_trace"]
    return 100.0 * least / seconds


def step_mfu(run):
    """Model FLOPs of the steps in the traced window, over the window, over
    the chip's bf16 peak."""
    t = run["trace"]
    c = run["outcome"].counters
    if not t or not c.get("calls_in_trace") or "step_flops" not in c:
        return None
    return (100.0 * c["step_flops"] * c["calls_in_trace"] / t["window_s"]
            / run["peak"]["bf16_flops_per_s"])


def module_gap_ms_quantile(run, q: float):
    t = run["trace"]
    if not t or not t["module_gaps_s"]:
        return None
    return harness.percentile([g * 1e3 for g in t["module_gaps_s"]], q)
