"""Readers of the benchmark's own spans and of the load workers' logs.

A reader takes the run (``ctx``, ``outcome``, ``trace``, ``peak``) and the
``args`` of its metric's file, and returns the value, or None where it
finds nothing to read."""

from __future__ import annotations

import harness


def series_quantile(run, series: str, q: float):
    values = run["outcome"].counters["series"].get(series)
    if values is None or not len(values):
        return None
    return harness.percentile(values, q)


def series_mean(run, series: str):
    values = run["outcome"].counters["series"].get(series)
    if values is None or not len(values):
        return None
    return float(sum(values) / len(values))


def sequence_ms_quantile(run, end: str, through: list, q: float):
    """Per handler thread, the span ``end`` together with the unbroken run
    of ``through`` spans before it is one request's time in the layer:
    from the first of them to the end of ``end``."""
    by_thread = {}
    for name, t0, t1, _, thread in run["ctx"].spans.rows:
        by_thread.setdefault(thread, []).append((t0, t1, name))
    out = []
    for rows in by_thread.values():
        rows.sort()
        for i, (t0, t1, name) in enumerate(rows):
            if name != end:
                continue
            first = t0
            j = i - 1
            while j >= 0 and rows[j][2] in through:
                first = rows[j][0]
                j -= 1
            out.append((t1 - first) * 1e3)
    return harness.percentile(out, q) if out else None


def span_gbps(run, name: str):
    rows = run["ctx"].spans.named(name)
    seconds = sum(r[2] - r[1] for r in rows)
    nbytes = sum(r[3] for r in rows)
    return nbytes / seconds / 1e9 if seconds > 0 and nbytes else None


def needed_bytes_share_of_hbm(run, work_fn: str):
    """The whole step's share of the chip's HBM bandwidth: the bytes the
    algorithm needs for the calls completed in the traced window, over the
    window, over the peak (these cells do no matmul: bytes are what binds)."""
    import work

    c = run["outcome"].counters
    trace = run["trace"]
    if not trace or not c.get("calls_in_trace"):
        return None
    w = getattr(work, work_fn)(c["ids_per_call"], c["dim"])
    return (100.0 * w["bytes"] * c["calls_in_trace"] / trace["window_s"]
            / run["peak"]["hbm_bytes_per_s"])
