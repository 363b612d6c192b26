"""Readers of the train step's named kernels and named scopes in the
profiler's trace (reduced by ``trace_reduce``) and of the ``stats`` the step
returns. Each returns None where there is nothing to read: no trace, or a
program without such ops."""

from __future__ import annotations

import statistics

import work
import work_dsv3


def _op_seconds(run, prefix: str):
    """Device seconds of the ops whose name starts with ``prefix`` (keys are
    ``<program>:<op name>_<shape>``)."""
    t = run["trace"]
    if not t:
        return None
    seconds = sum(v for k, v in t["op_seconds"].items()
                  if k.split(":", 1)[-1].startswith(prefix))
    return seconds or None


def kernel_roofline(run, prefix: str, work_fn: str):
    """The least time the chip could take for the work that the traced
    steps NEEDED of these kernels, over the device time of the ops named
    ``prefix*``. ``work_fn``: ``mla_attention`` (per layer, from shapes) or
    ``routed_experts`` (per expert layer and step, from the assignments the
    step's stats counted)."""
    seconds = _op_seconds(run, prefix)
    c = run["outcome"].counters
    if seconds is None or not c.get("calls_in_trace") or "sizes" not in c:
        return None
    m, n = c["sizes"], c["calls_in_trace"]
    if work_fn == "mla_attention":
        w = work_dsv3.mla_attention(m, c["batch"], c["sequence"])
        least = (work.roofline_seconds(w, run["peak"]) * n
                 * m["num_hidden_layers"])
    else:
        least = sum(work.roofline_seconds(
            work_dsv3.routed_experts(m, rows), run["peak"])
            for step in c["routed_rows"][:n] for rows in step)
    return 100.0 * least / seconds


def op_share_of_step(run, prefix: str, program: str):
    """Device seconds of the ops named ``prefix*`` over the device seconds
    of the step program's executions."""
    seconds = _op_seconds(run, prefix)
    if seconds is None:
        return None
    whole = run["trace"]["module_seconds"].get(program)
    return 100.0 * seconds / whole if whole else None


def scope_share_of_step(run, scopes: list, program: str, phases=None):
    """SELF device seconds of the step's ops written under a scope of the
    program with a component that starts with one of ``scopes`` (``-``: under
    none; ``trace_reduce.scope_of``), in one of ``phases`` if given, over
    the device seconds of the step program's executions. None without a
    trace, without the driver's scope table, or where no such op ran."""
    t = run["trace"]
    table = (t or {}).get("scope_seconds", {}).get(program)
    if not table:
        return None
    seconds = sum(
        s for scope, by_phase in table.items()
        if any(part.startswith(p) for part in scope.split("/")
               for p in scopes)
        for phase, s in by_phase.items() if not phases or phase in phases)
    whole = t["module_seconds"].get(program)
    return 100.0 * seconds / whole if seconds and whole else None


def stats_median(run, key: str):
    """The median over the window's steps of a number the driver derived
    from the step's stats."""
    values = run["outcome"].counters.get("series", {}).get(key)
    return float(statistics.median(values)) if values else None
