"""rpcz-style per-call tracing (reference src/brpc/builtin/rpcz_service.cpp,
src/brpc/span.cpp).

Every instrumented call — client-side ``Channel.call``, server-side
handler dispatch, PS lookups, user code under ``span(...)`` — appends one
``Span`` to a bounded store.  ``dump_rpcz`` answers the /rpcz queries:
most-recent-first, filterable by service/method/side/errors.  The store
is lossy: under heavy traffic old spans fall off the back (counted in
``rpcz_spans_dropped``), which is the reference's behaviour (rpcz keeps a
time-bounded window, not a full log).

**One tree per traced request.**  A call-level span (``side`` client /
server / user) is a *root*.  A root is *traced* when the request arrived
with a trace id, when it began under a traced span of the same thread
(``obs.span(...)`` always is one), or when the process-wide budget admits
it: ``TRACED_ROOTS_PER_SECOND`` while a profiler session records (a
traced window sees every request), ``TRACED_ROOTS_PER_SECOND_UNWATCHED``
otherwise (what tracing costs a process nobody is looking at stays
small).  Trace ids go over the wire only with a request somebody asked
to see — made under ``obs.span``, inside a profiler session, or on
behalf of a request that itself arrived with ids — so a server behind
many clients traces what its own budget admits, not the sum of theirs.
While a traced root is its thread's *current* span, :func:`begin` /
:func:`end` record children (``side="span"``, named ``<layer>.<what>``)
at the layer boundaries of the request path; with no traced current span
:func:`begin` is one thread-local read and a branch and records nothing.
Spans that the native core times (socket receive, scheduling, DMA)
arrive by :func:`record` with their own stamps; one whose end comes after
the call that started it has returned (``dev.stage.h2d``: the transfer,
``rpc.send``: the socket write) by :func:`record_late` with the slot of
the core's late-stamp table in which the end will appear.  All times are
``time.monotonic_ns()`` — ``CLOCK_MONOTONIC``, the native core's clock.

On the request path a child costs a tuple, not an object: the root keeps
``(name, start, end, nbytes, copy)`` for each child its thread closed,
and the child ``Span``s — ids, parents — are made from those when the
store is read.  One thread closes them, so their intervals nest, and the
tree is their containment.

Server side, the phases of one request — ``rpc.recv``, ``rpc.queue``,
``rpc.copy_in``, ``rpc.gil_wait``, the handler root, ``rpc.copy_out``,
``rpc.send`` — follow one another, so they are siblings: all carry the
request's ``trace_id`` and, as ``parent_id``, the client's call span
that came over the wire (0 when the client sent none).  Everything the
handler does hangs below the handler root.

**The profiler's clock.**  While a ``jax.profiler`` session is active
(asked once per root that is traced or that the unwatched budget would
turn away, and only when ``jax`` is already imported),
every stack-disciplined span of a traced request is also written into
the profiler's trace as ``brt/<name>``, and the store notes when it saw
the session start and end.  The spans of the last session are set aside
when it ends, so later traffic cannot push them out.
"""

from __future__ import annotations

import contextlib
import dataclasses
import random
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from brpc_tpu.analysis.race import checked_lock
from brpc_tpu.obs.vars import Adder

__all__ = ["Span", "SpanRing", "default_ring", "record_span", "span",
           "dump_rpcz", "set_capacity", "clear", "begin", "end", "record",
           "record_late", "start_root", "finish_root", "current",
           "set_late_stamps", "TRACED_ROOTS_PER_SECOND",
           "TRACED_ROOTS_PER_SECOND_UNWATCHED"]

#: call-level spans kept (a traced one holds its children): more than one
#: traced window of 10 s at the budget, or of the busier candidate mix
#: (360 requests/s, some 23 spans each: 8 MB of tuples), with room to spare
DEFAULT_CAPACITY = 1 << 14

#: the budget of rule (c): roots traced per second by this process's own
#: choice, the native side's rule (FLAGS_rpcz_max_per_second,
#: cpp/rpc/span.h) written once here: the first while a profiler session
#: records (above the busier candidate mix's 360 requests/s — 180 a shard,
#: two shards a process — so a traced window sees every request), the
#: second the rest of the time.  Requests that arrive with a trace id and
#: code under ``obs.span`` are traced besides.
TRACED_ROOTS_PER_SECOND = 512
TRACED_ROOTS_PER_SECOND_UNWATCHED = 16

_NO_ANNOTATIONS: Tuple[str, ...] = ()


@dataclasses.dataclass(slots=True)
class Span:
    service: str
    method: str
    side: str = "client"            # "client" | "server" | "user" | "span"
    peer: str = ""                  # remote address when known
    request_bytes: int = 0
    response_bytes: int = 0
    start_ns: int = 0               # monotonic ns
    end_ns: int = 0
    wall_time: float = 0.0          # epoch seconds at start (display)
    error_code: int = 0
    error_text: str = ""
    annotations: Sequence[str] = _NO_ANNOTATIONS
    trace_id: int = 0               # 0: not traced (a flat call record)
    span_id: int = 0
    parent_id: int = 0
    nbytes: int = 0                 # bytes the span moved or copied
    copy: bool = False              # the span IS a host memcpy of nbytes
    # the span that was current before this one, the open profiler
    # annotation, whether the root saw a profiler session, and whether
    # its ids go over the wire with the calls made under it
    _up: "Optional[Span]" = dataclasses.field(default=None, repr=False,
                                              compare=False)
    _ann: object = dataclasses.field(default=None, repr=False,
                                     compare=False)
    _mirror: bool = dataclasses.field(default=False, repr=False,
                                      compare=False)
    _spread: bool = dataclasses.field(default=False, repr=False,
                                      compare=False)
    _pushed: bool = dataclasses.field(default=False, repr=False,
                                      compare=False)
    # a traced root's children as its thread closed them — (name, start,
    # end, nbytes, copy), or (name, start, late-stamp slot, nbytes) where
    # the end comes later — a server root's native stamps before the
    # handler (first byte, frame whole, service entered, handler called)
    # and after it (respond entered, response in its buffer, bytes that
    # copied, late-stamp slot of "written"), and the child Spans made from
    # all of it once every late stamp was in
    _events: "Optional[list]" = dataclasses.field(default=None, repr=False,
                                                  compare=False)
    _phases: "Optional[tuple]" = dataclasses.field(default=None, repr=False,
                                                   compare=False)
    _sent: "Optional[tuple]" = dataclasses.field(default=None, repr=False,
                                                 compare=False)
    _below: "Optional[list]" = dataclasses.field(default=None, repr=False,
                                                 compare=False)

    @property
    def latency_us(self) -> float:
        return (self.end_ns - self.start_ns) / 1e3

    @property
    def name(self) -> str:
        """``<layer>.<what>``: a child's own name; a server root is its
        service's handler (``Ps.Lookup`` -> ``ps.handler``)."""
        if self.side == "server":
            return f"{self.service.lower()}.handler"
        return f"{self.service}.{self.method}"

    def annotate(self, text: str) -> None:
        self.annotations = [*self.annotations, text]

    def to_dict(self) -> Dict[str, object]:
        return {
            "service": self.service,
            "method": self.method,
            "side": self.side,
            "peer": self.peer,
            "request_bytes": self.request_bytes,
            "response_bytes": self.response_bytes,
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "wall_time": self.wall_time,
            "latency_us": round(self.latency_us, 3),
            "error_code": self.error_code,
            "error_text": self.error_text,
            "annotations": list(self.annotations),
            "name": self.name,
            "trace_id": f"{self.trace_id:016x}" if self.trace_id else "",
            "span_id": f"{self.span_id:016x}" if self.span_id else "",
            "parent_id": f"{self.parent_id:016x}" if self.parent_id else "",
            "nbytes": self.nbytes,
            "copy": self.copy,
        }


class _Budget:
    """At most ``per_second`` admissions in any whole second of ``clock``
    (the native collector's speed limit, var::RateLimiter) — and, where
    ``unwatched`` is given, no more than that many while ``watched()``
    says nobody is looking (asked only once the smaller number is
    used up)."""

    __slots__ = ("per_second", "unwatched", "_watched", "_clock", "_second",
                 "_used")

    def __init__(self, per_second: int, unwatched: Optional[int] = None,
                 watched: Callable[[], bool] = lambda: True,
                 clock: Callable[[], float] = time.monotonic):
        self.per_second = per_second
        self.unwatched = per_second if unwatched is None else unwatched
        self._watched = watched
        self._clock = clock
        self._second = -1
        self._used = 0

    def admit(self) -> bool:
        second = int(self._clock())
        if second != self._second:
            self._second = second
            self._used = 0
        if self._used >= self.per_second or (
                self._used >= self.unwatched and not self._watched()):
            return False
        self._used += 1
        return True


class SpanRing:
    """Bounded span store.  ``append`` takes no lock (a deque append is
    one step under the interpreter lock); what it pushes out is counted."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self._mu = checked_lock("obs.rpcz_ring")   # resize / clear / swap
        self._ring: deque = deque(maxlen=capacity)
        self._session: deque = deque(maxlen=capacity)
        self.dropped = Adder()
        # the last profiler session this store saw, on monotonic_ns (each
        # edge to within one root's interarrival); 0 = none yet
        self.session_start_ns = 0
        self.session_end_ns = 0
        self._in_session = False

    @property
    def capacity(self) -> int:
        return self._ring.maxlen

    def set_capacity(self, capacity: int) -> None:
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        with self._mu:
            self._ring = deque(self._ring, maxlen=capacity)
            self._session = deque(self._session, maxlen=capacity)

    def append(self, s: Span) -> None:
        ring = self._ring
        if len(ring) == ring.maxlen:
            self.dropped.add(1)
        ring.append(s)

    def __len__(self) -> int:
        return len(self._ring)

    def clear(self) -> None:
        with self._mu:
            self._ring.clear()
            self._session.clear()
            self.session_start_ns = self.session_end_ns = 0
            self._in_session = False

    def note_profiler(self, active: bool, now_ns: int) -> None:
        """A root's look at the profiler (a traced one's, or one the
        unwatched budget turned away): the edges of a session are where
        the answer changes.  When one ends, its spans are set
        aside whole (the ring starts again empty)."""
        if active == self._in_session:
            return
        with self._mu:
            if active == self._in_session:
                return
            self._in_session = active
            if active:
                self.session_start_ns = now_ns
                self.session_end_ns = 0
                return
            self.session_end_ns = now_ns
            self._session, self._ring = self._ring, deque(
                maxlen=self._ring.maxlen)

    def snapshot(self) -> List[Span]:
        """Every span held, oldest first: the last profiler session's,
        then the ring's."""
        return _with_children(list(self._session) + list(self._ring))

    def session_spans(self) -> List[Span]:
        """The spans that began inside the last profiler session this
        store saw; every span held where it saw none."""
        spans = list(self._session) + list(self._ring)
        t0, t1 = self.session_start_ns, self.session_end_ns
        if t0:
            spans = [s for s in spans
                     if s.start_ns >= t0 and (not t1 or s.start_ns <= t1)]
        return _with_children(spans)

    def dump(self, limit: int = 50, service: Optional[str] = None,
             method: Optional[str] = None, side: Optional[str] = None,
             errors_only: bool = False) -> List[Dict[str, object]]:
        """Most-recent-first dicts of the call-level spans matching the
        filters (with no filter, a call made under another call is shown
        under it only).  A traced one carries its tree: ``children`` (nested,
        oldest first) and, for a server root whose client is elsewhere,
        ``phases`` (the request's receive / queue / send siblings)."""
        snapshot = _with_children(list(self._ring))

        def wanted(s: Span) -> bool:
            return not (
                s.side == "span"
                or (service is not None and s.service != service)
                or (method is not None and s.method != method)
                or (side is not None and s.side != side)
                or (errors_only and s.error_code == 0))

        unfiltered = service is None and method is None \
            and side is None and not errors_only
        by_id = {s.span_id: s for s in snapshot if s.span_id}
        kids: Dict[int, List[Span]] = {}
        phases: Dict[Tuple[int, int], List[Span]] = {}
        for s in snapshot:
            if not s.trace_id:
                continue
            if s.parent_id in by_id:
                kids.setdefault(s.parent_id, []).append(s)
            elif s.side == "span":
                phases.setdefault((s.trace_id, s.parent_id), []).append(s)

        def tree(s: Span) -> Dict[str, object]:
            d = s.to_dict()
            below = kids.get(s.span_id) if s.span_id else None
            if below:
                d["children"] = [tree(c) for c in sorted(
                    below, key=lambda c: c.start_ns)]
            if s.side == "server" and s.trace_id:
                beside = phases.get((s.trace_id, s.parent_id))
                if beside:
                    d["phases"] = [c.to_dict() for c in sorted(
                        beside, key=lambda c: c.start_ns)]
            return d

        out: List[Dict[str, object]] = []
        for s in reversed(snapshot):
            if not wanted(s):
                continue
            if unfiltered and s.trace_id and s.parent_id in by_id:
                continue        # shown under the span it was made from
            out.append(tree(s))
            if len(out) >= limit:
                break
        return out


_default_ring = SpanRing()
_default_ring.dropped.expose("rpcz_spans_dropped")


class _Current(threading.local):
    """The thread's current traced span.  The class default answers for a
    thread that never set one, so the no-span path is a plain attribute
    read (a missing attribute would cost an exception inside getattr)."""
    cur: "Optional[Span]" = None


_tls = _Current()
_new_id = random.Random().getrandbits
_late_stamp: Callable[[int], int] = lambda slot: 0
_trace_annotation = None        # jax.profiler.TraceAnnotation, once seen


def default_ring() -> SpanRing:
    return _default_ring


def set_capacity(capacity: int) -> None:
    _default_ring.set_capacity(capacity)


def clear() -> None:
    _default_ring.clear()


def record_span(s: Span, ring: Optional[SpanRing] = None) -> None:
    # "ring or _default_ring" would misroute: an EMPTY SpanRing is falsy
    # through __len__.
    (_default_ring if ring is None else ring).append(s)


def dump_rpcz(limit: int = 50, service: Optional[str] = None,
              method: Optional[str] = None, side: Optional[str] = None,
              errors_only: bool = False) -> List[Dict[str, object]]:
    return _default_ring.dump(limit=limit, service=service, method=method,
                              side=side, errors_only=errors_only)


def set_late_stamps(read: Callable[[int], int]) -> None:
    """``read(slot)`` is the native core's late stamp in that slot on
    ``monotonic_ns`` (0: the work has not finished); the binding that
    loads the core registers it."""
    global _late_stamp
    _late_stamp = read


def _profiler_active() -> bool:
    """Whether a profiler session is recording — asked of JAX only where
    the process already imported it."""
    global _trace_annotation
    if _trace_annotation is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
    return _trace_annotation.is_enabled()


def _saw_profiler() -> bool:
    """A root's look at the profiler; the default store notes the edges
    of a session where the answer changes."""
    active = _profiler_active()
    _default_ring.note_profiler(active, time.monotonic_ns())
    return active


_budget = _Budget(TRACED_ROOTS_PER_SECOND, TRACED_ROOTS_PER_SECOND_UNWATCHED,
                  watched=_saw_profiler)


def current() -> Optional[Span]:
    """The thread's current traced span (a root), or None."""
    return _tls.cur


def _push(s: Span, up: Optional[Span]) -> None:
    s._up = up
    s._pushed = True
    s._events = []
    if s._mirror:
        s._ann = _trace_annotation("brt/" + s.name)
        s._ann.__enter__()
    _tls.cur = s


def _pop(s: Span) -> None:
    if s._ann is not None:
        s._ann.__exit__(None, None, None)
        s._ann = None
    _tls.cur = s._up
    s._up = None
    s._pushed = False


def _child(name: str, trace_id: int, parent_id: int, start_ns: int,
           end_ns: int, nbytes: int, copy: bool) -> Span:
    layer, _, what = name.partition(".")
    return Span(layer, what, "span", start_ns=start_ns, end_ns=end_ns,
                trace_id=trace_id, span_id=_new_id(64) or 1,
                parent_id=parent_id, nbytes=nbytes, copy=copy)


def _children(root: Span) -> list:
    """The child Spans of a traced root, made from what its thread
    recorded: a server root's phases beside it, and below it its events,
    each under the innermost event that contains it (a late one: that
    contains its start — the work it times may outlive the call that
    started it).  A late event whose stamp is not in yet is left out, and
    the answer is kept only once nothing is missing."""
    if root._below is not None:
        return root._below
    out = []
    whole = True
    if root._phases is not None:
        first, framed, entered, called = root._phases
        asked = root.request_bytes
        phases = [("rpc.recv", first, framed, asked + 12, False),  # + header
                  ("rpc.queue", framed, entered, 0, False),
                  ("rpc.copy_in", entered, called, asked, True),
                  ("rpc.gil_wait", called, root.start_ns, 0, False)]
        if root._sent is None:
            whole = False           # the handler has not responded yet
        else:
            respond, copied, nbytes, slot = root._sent
            phases.append(("rpc.copy_out", respond, copied, nbytes, True))
            written = _late_stamp(slot)
            if written >= copied:
                phases.append(("rpc.send", copied, written,
                               root.response_bytes + 12, False))
            else:
                whole = False
        for name, t0, t1, nbytes, copy in phases:
            out.append(_child(name, root.trace_id, root.parent_id, t0, t1,
                              nbytes, copy))
    events = root._events or ()
    first_below = len(out)
    open_ = [root]
    for name, t0, t1, nbytes, copy in sorted(
            (e for e in events if len(e) == 5),
            key=lambda e: (e[1], -e[2])):
        while len(open_) > 1 and not (open_[-1].start_ns <= t0
                                      and t1 <= open_[-1].end_ns):
            open_.pop()
        open_.append(_child(name, root.trace_id, open_[-1].span_id, t0, t1,
                            nbytes, copy))
        out.append(open_[-1])
    below = out[first_below:]
    for name, t0, slot, nbytes in (e for e in events if len(e) == 4):
        t1 = _late_stamp(slot)
        if t1 < t0:
            whole = False
            continue
        # (half-open: it starts where the sibling before it ended)
        inside = [c for c in below if c.start_ns <= t0 < c.end_ns]
        up = min(inside, key=lambda c: c.end_ns - c.start_ns,
                 default=root)
        out.append(_child(name, root.trace_id, up.span_id, t0, t1, nbytes,
                          False))
    if whole and root.end_ns:
        root._below = out
    return out


def _with_children(spans: List[Span]) -> List[Span]:
    out = []
    for s in spans:
        out.append(s)
        if s._events or s._phases is not None:
            out.extend(_children(s))
    return out


def begin(name: str, nbytes: int = 0, copy: bool = False) -> Optional[list]:
    """Opens a child of the thread's current span.  None — after one
    thread-local read and a branch — where the thread has no traced
    span; pass the result to :func:`end` either way."""
    cur = _tls.cur
    if cur is None:
        return None
    ann = None
    if cur._mirror:
        ann = _trace_annotation("brt/" + name)
        ann.__enter__()
    return [cur, name, nbytes, copy, ann, time.monotonic_ns()]


def end(child: Optional[list], nbytes: Optional[int] = None) -> None:
    """Closes what :func:`begin` opened (None, or closed already:
    nothing to do)."""
    if child is None or child[0] is None:
        return
    now = time.monotonic_ns()
    root, name, nb, copy, ann, t0 = child
    child[0] = None
    if ann is not None:
        ann.__exit__(None, None, None)
    root._events.append((name, t0, now, nb if nbytes is None else nbytes,
                         copy))


def record(name: str, start_ns: int, end_ns: int, nbytes: int = 0,
           copy: bool = False) -> None:
    """A finished child of the thread's current span with its own stamps
    (what the native core timed inside a call this thread made).  Nothing
    where the thread has no traced span."""
    cur = _tls.cur
    if cur is not None:
        cur._events.append((name, start_ns, end_ns, nbytes, copy))


def record_late(name: str, start_ns: int, slot: int, nbytes: int = 0) -> None:
    """A child of the thread's current span that the native core will
    finish after the call that started it has returned: its end is the
    late stamp that appears in ``slot`` (:func:`set_late_stamps`).  It
    hangs below the span that contains its start and may end after it."""
    cur = _tls.cur
    if cur is not None:
        cur._events.append((name, start_ns, slot, nbytes))


def start_root(service: str, method: str, side: str, *, peer: str = "",
               request_bytes: int = 0, trace_id: int = 0,
               parent_id: int = 0, force: bool = False,
               push: bool = True) -> Span:
    """Opens a call-level span.  It is traced — given ids, and, with
    ``push``, made the thread's current span so that children hang from
    it — when it begins under a traced span, arrived with ``trace_id``,
    is ``force``d (``obs.span``) or the budget admits it; otherwise it
    is the flat record rpcz always kept.  A server root is a request of
    its own: it never joins the span its thread happens to have open."""
    s = Span(service, method, side, peer=peer, request_bytes=request_bytes,
             wall_time=time.time())
    cur = _tls.cur
    if cur is not None and side != "server":
        s.trace_id, s.parent_id, s._mirror, s._spread = \
            cur.trace_id, cur.span_id, cur._mirror, cur._spread
    elif trace_id or force or _budget.admit():
        s._mirror = _saw_profiler()
        s._spread = bool(trace_id) or force or s._mirror
        s.trace_id = trace_id or _new_id(64) or 1
        s.parent_id = parent_id
    if s.trace_id:
        s.span_id = _new_id(64) or 1
        if push:
            _push(s, cur)
    s.start_ns = time.monotonic_ns()
    return s


def finish_root(s: Span, ring: Optional[SpanRing] = None) -> None:
    """Closes and records a span :func:`start_root` opened."""
    if not s.end_ns:
        s.end_ns = time.monotonic_ns()
    if s._pushed:
        _pop(s)
    record_span(s, ring)


@contextlib.contextmanager
def span(service: str, method: str, side: str = "user", peer: str = "",
         request_bytes: int = 0, ring: Optional[SpanRing] = None):
    """Trace a block of user code:

        with obs.span("Trainer", "step") as sp:
            ...
            sp.annotate("compiled")

    The span is always traced: every instrumented call and layer
    boundary inside the block, on this thread, hangs below it.  An
    exception inside the block marks the span failed (code 2001) and
    re-raises; the span is recorded either way.
    """
    s = start_root(service, method, side, peer=peer,
                   request_bytes=request_bytes, force=True)
    try:
        yield s
    except Exception as e:  # noqa: BLE001
        s.error_code = s.error_code or 2001
        s.error_text = s.error_text or str(e)
        raise
    finally:
        finish_root(s, ring)


def format_rpcz(spans: List[Dict[str, object]]) -> str:
    """Text rendering in the /rpcz style: one line per span, a traced
    request's children indented under it."""
    lines: List[str] = []

    def child(d, depth):
        extra = f" {d['nbytes']}B" if d["nbytes"] else ""
        extra += " copy" if d["copy"] else ""
        lines.append(f"{'  ' * depth}{d['name']} "
                     f"lat={d['latency_us']:.1f}us{extra}")
        for c in d.get("children", ()):
            walk(c, depth + 1)

    def walk(d, depth):
        if d["side"] == "span":
            child(d, depth)
            return
        err = (f" error={d['error_code']}({d['error_text']})"
               if d["error_code"] else "")
        trace = f" trace={d['trace_id']}" if d["trace_id"] else ""
        before = [p for p in d.get("phases", ())
                  if p["start_ns"] < d["start_ns"]]
        after = [p for p in d.get("phases", ())
                 if p["start_ns"] >= d["start_ns"]]
        for p in before:
            child(p, depth)
        lines.append(
            f"{'  ' * depth}{d['side']:6s} {d['service']}.{d['method']} "
            f"peer={d['peer'] or '-'} req={d['request_bytes']}B "
            f"rsp={d['response_bytes']}B lat={d['latency_us']:.1f}us"
            f"{err}{trace}")
        for c in d.get("children", ()):
            walk(c, depth + 1)
        for p in after:
            child(p, depth)

    for d in spans:
        walk(d, 0)
    return "\n".join(lines)
