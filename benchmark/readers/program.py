"""Readers of the program's own span trees (``brpc_tpu.obs.rpcz``), in the
process that owns the chip: the shard servers live in ``run.py``'s
process, so their request trees are here; the load workers' client spans
are in the workers and are not read.

Read are the traced requests that began inside the last profiler session
the store saw, or every traced request where it saw none
(``--cpu-dry-run``). A request is a server-side handler root
(``side="server"``: ``ps.handler``) with what hangs below it, and beside
it the phases the native core stamped (``rpc.recv``, ``rpc.queue``,
``rpc.copy_in``, ``rpc.gil_wait``, ``rpc.copy_out``, ``rpc.send``), which
share its trace id and its parent. Where the store holds no such
request every function returns None.

Generic functions only; a metric's file names one and its arguments.
``methods`` keeps the requests whose root is one of those RPC methods
(none given: all)."""

from __future__ import annotations

import sys

import harness

sys.path.insert(0, harness.ROOT)
from brpc_tpu.obs import rpcz  # noqa: E402


def _index(run):
    """(roots, {root id: [its spans, itself left out]}) of this run."""
    if "_program_spans" in run:
        return run["_program_spans"]
    spans = rpcz.default_ring().session_spans()
    by_id = {s.span_id: s for s in spans if s.span_id}
    roots = [s for s in spans if s.side == "server" and s.trace_id]
    by_request = {(r.trace_id, r.parent_id): r for r in roots}

    def root_of(s):
        cur = s
        while cur is not None and cur.side == "span":
            up = by_id.get(cur.parent_id)
            if up is None or up.side in ("client", "user"):
                # a phase: beside the handler root, under the client's call
                return by_request.get((cur.trace_id, cur.parent_id))
            cur = up
        return cur if cur is not None and cur.side == "server" else None

    below = {id(r): [] for r in roots}
    for s in spans:
        if s.side != "span" or not s.trace_id:
            continue
        r = root_of(s)
        if r is not None:
            below[id(r)].append(s)
    run["_program_spans"] = (roots, below)
    return run["_program_spans"]


def _requests(run, methods):
    roots, below = _index(run)
    return [(r, below[id(r)]) for r in roots
            if not methods or r.method in methods]


def _ms(s):
    return (s.end_ns - s.start_ns) * 1e-6


def span_ms_quantile(run, name: str, q: float, methods=None):
    """The q-th percentile of the durations of the spans ``name``."""
    out = [_ms(s) for _, spans in _requests(run, methods) for s in spans
           if s.name == name]
    return harness.percentile(out, q) if out else None


def span_sum_ms_quantile(run, name: str, q: float, methods=None):
    """Per request, the durations of its spans ``name`` added up (a
    request with none counts 0); the q-th percentile over requests."""
    out = [sum(_ms(s) for s in spans if s.name == name)
           for _, spans in _requests(run, methods)]
    return harness.percentile(out, q) if out else None


def covered_ns(lo: int, hi: int, intervals) -> int:
    """How much of [lo, hi] the (start, end) intervals cover together."""
    total, edge = 0, lo
    for s, e in sorted(intervals):
        s, e = max(s, edge), min(e, hi)
        if e > s:
            total += e - s
            edge = e
    return total


def root_self_ms_quantile(run, q: float, methods=None):
    """A handler root's self time: its duration less what its direct
    children cover (choosing-metrics, section 4)."""
    out = []
    for r, spans in _requests(run, methods):
        kids = [(s.start_ns, s.end_ns) for s in spans
                if s.parent_id == r.span_id]
        out.append((r.end_ns - r.start_ns
                    - covered_ns(r.start_ns, r.end_ns, kids)) * 1e-6)
    return harness.percentile(out, q) if out else None


def child_share(run, parent: str, children: list, through=(),
                methods=None):
    """The spans ``children`` as a share (%) of the spans ``parent``, by
    summed duration. A ``parent`` lasts until the last of its own
    ``through`` children ends: work the call started and the native core
    finished after it returned (``dev.stage.h2d``)."""
    top = part = 0.0
    for _, spans in _requests(run, methods):
        ends = {}
        for s in spans:
            if s.name in through:
                ends[s.parent_id] = max(ends.get(s.parent_id, 0), s.end_ns)
        top += sum(max(s.end_ns, ends.get(s.span_id, 0)) - s.start_ns
                   for s in spans if s.name == parent) * 1e-6
        part += sum(_ms(s) for s in spans if s.name in children)
    return 100.0 * part / top if top > 0 else None


def copy_ratio(run, methods=None):
    """Bytes the host copied (the ``copy`` spans' ``nbytes``) over the
    payload bytes of the requests (their roots' request + response)."""
    copied = payload = 0
    for r, spans in _requests(run, methods):
        payload += r.request_bytes + r.response_bytes
        copied += sum(s.nbytes for s in spans if s.copy)
    return copied / payload if payload else None


def span_gbps(run, name: str, methods=None):
    """Bytes over seconds of the spans ``name``."""
    nbytes = seconds = 0.0
    for _, spans in _requests(run, methods):
        for s in spans:
            if s.name == name:
                nbytes += s.nbytes
                seconds += (s.end_ns - s.start_ns) * 1e-9
    return nbytes / seconds / 1e9 if seconds > 0 and nbytes else None
