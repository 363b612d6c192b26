"""One span tree per traced request (brpc_tpu.obs.rpcz): the store, the
budget, the tree a Lookup and an ApplyGrad leave across the socket and
down to the fake PJRT plug-in, and the same spans on the profiler's
clock."""

import collections
import glob
import time

import numpy as np
import pytest

from brpc_tpu import obs
from brpc_tpu.obs import rpcz

VOCAB, DIM, K = 256, 8, 64


@pytest.fixture(autouse=True)
def _obs_on_and_clean():
    obs.set_enabled(True)
    rpcz.clear()
    yield
    obs.set_enabled(True)
    rpcz.clear()


# ---------------------------------------------------------------------------
# the store and the budget (pure Python)
# ---------------------------------------------------------------------------

def test_budget_admits_its_constant_per_second_under_a_fake_clock():
    now = [100.0]
    watched = [True]
    budget = rpcz._Budget(rpcz.TRACED_ROOTS_PER_SECOND,
                          rpcz.TRACED_ROOTS_PER_SECOND_UNWATCHED,
                          watched=lambda: watched[0], clock=lambda: now[0])
    # serve_lookup's rate: 180 a shard, both shards in one process
    assert rpcz.TRACED_ROOTS_PER_SECOND >= 360
    assert rpcz.TRACED_ROOTS_PER_SECOND_UNWATCHED * 10 <= 180
    admitted = sum(budget.admit() for _ in range(1000))
    assert admitted == rpcz.TRACED_ROOTS_PER_SECOND
    now[0] = 100.9                                  # the same second
    assert not budget.admit()
    now[0] = 101.0                                  # the next one
    assert sum(budget.admit() for _ in range(1000)) == \
        rpcz.TRACED_ROOTS_PER_SECOND
    # with no profiler session recording, the small constant
    now[0], watched[0] = 102.0, False
    assert sum(budget.admit() for _ in range(1000)) == \
        rpcz.TRACED_ROOTS_PER_SECOND_UNWATCHED
    watched[0] = True                               # one starts mid-second
    assert sum(budget.admit() for _ in range(1000)) == \
        rpcz.TRACED_ROOTS_PER_SECOND - rpcz.TRACED_ROOTS_PER_SECOND_UNWATCHED
    # the process's own budget asks the profiler
    assert rpcz._budget.per_second == rpcz.TRACED_ROOTS_PER_SECOND
    assert rpcz._budget.unwatched == rpcz.TRACED_ROOTS_PER_SECOND_UNWATCHED
    assert rpcz._budget._watched is rpcz._saw_profiler


def test_roots_beyond_the_budget_stay_flat_records(monkeypatch):
    monkeypatch.setattr(rpcz, "_budget",
                        rpcz._Budget(2, clock=lambda: 5.0))
    roots = [rpcz.start_root("Echo", "Echo", "client", push=False)
             for _ in range(5)]
    for r in roots:
        rpcz.finish_root(r)
    assert [bool(r.trace_id) for r in roots] == [True, True, False, False,
                                                 False]
    assert len(rpcz.default_ring()) == 5            # all five are recorded


def test_what_falls_off_the_store_is_counted():
    ring = rpcz.SpanRing(capacity=4)
    for i in range(10):
        ring.append(rpcz.Span("S", f"m{i}"))
    assert len(ring) == 4
    assert ring.dropped.get_value() == 6
    assert "rpcz_spans_dropped" in obs.dump_exposed_dict()


def test_no_traced_root_no_child_span():
    before = len(rpcz.default_ring())
    assert rpcz.current() is None
    sp = rpcz.begin("dev.stage", 10, True)
    assert sp is None
    rpcz.end(sp)
    rpcz.record("dev.stage.h2d", 1, 2, 10)
    assert len(rpcz.default_ring()) == before


def test_children_are_kept_as_tuples_and_nest_by_containment():
    with obs.span("T", "work") as root:
        outer = rpcz.begin("dev.stage")
        assert rpcz.current() is root          # children are not current
        inner = rpcz.begin("dev.stage.tobytes", copy=True)
        rpcz.end(inner, 12)
        rpcz.end(inner, 99)                    # closed already: no-op
        t = time.monotonic_ns()
        rpcz.record("dev.stage.pool_copy", t, t + 1, 12, True)  # native
        time.sleep(0.001)
        rpcz.end(outer)
        rpcz.begin("ps.lock_wait")             # never ended: never there
        before = len(rpcz.default_ring())
    assert rpcz.current() is None
    assert len(rpcz.default_ring()) == before + 1    # one object a root
    (d,) = obs.dump_rpcz()
    assert d["name"] == "T.work" and d["trace_id"]
    (stage,) = d["children"]
    assert stage["name"] == "dev.stage"
    assert stage["trace_id"] == d["trace_id"]
    assert stage["parent_id"] == d["span_id"]
    kids = stage["children"]
    assert [(c["name"], c["nbytes"], c["copy"]) for c in kids] == [
        ("dev.stage.tobytes", 12, True), ("dev.stage.pool_copy", 12, True)]
    assert all(c["parent_id"] == stage["span_id"] for c in kids)
    # a second read hands out the same children
    (again,) = obs.dump_rpcz()
    assert again["children"][0]["span_id"] == stage["span_id"]
    text = rpcz.format_rpcz([d])
    assert text.splitlines()[1].startswith("  dev.stage ")
    assert text.splitlines()[2].startswith("    dev.stage.tobytes ")


def test_a_late_child_hangs_where_it_started_and_may_outlive_it(monkeypatch):
    """What the native core finishes after the call has returned (the H2D
    transfer) ends at the late stamp of its slot: left out until the stamp
    is in, then below the span that holds its start."""
    late = {}
    monkeypatch.setattr(rpcz, "_late_stamp", lambda slot: late.get(slot, 0))
    with obs.span("T", "work"):
        outer = rpcz.begin("dev.stage")
        t0 = time.monotonic_ns()
        rpcz.record_late("dev.stage.h2d", t0, 7, 4096)
        rpcz.end(outer)
        after = rpcz.begin("dev.execute.scatter_sub")
        rpcz.end(after)
    (d,) = obs.dump_rpcz()
    assert [c["name"] for c in d["children"]] == [
        "dev.stage", "dev.execute.scatter_sub"]
    assert "children" not in d["children"][0]       # not finished: left out
    late[7] = t0 - 1                                # a stale slot: not it
    (d,) = obs.dump_rpcz()
    assert "children" not in d["children"][0]
    late[7] = time.monotonic_ns()                   # done with the buffer
    (d,) = obs.dump_rpcz()
    stage, execute = d["children"]
    (h2d,) = stage["children"]
    assert (h2d["name"], h2d["nbytes"], h2d["copy"]) == (
        "dev.stage.h2d", 4096, False)
    assert (h2d["start_ns"], h2d["end_ns"]) == (t0, late[7])
    assert stage["start_ns"] <= h2d["start_ns"] <= stage["end_ns"]
    assert h2d["end_ns"] > execute["end_ns"]        # it outlived its call
    # whole now: a second read hands out the same children
    late[7] += 5
    (again,) = obs.dump_rpcz()
    assert again["children"][0]["children"][0]["span_id"] == h2d["span_id"]
    assert again["children"][0]["children"][0]["end_ns"] == h2d["end_ns"]


def test_last_profiler_session_is_set_aside():
    ring = rpcz.SpanRing(capacity=8)
    ring.append(rpcz.Span("S", "before", start_ns=5))
    ring.note_profiler(True, 10)
    ring.append(rpcz.Span("S", "inside", start_ns=15))
    ring.note_profiler(True, 16)                    # no edge: nothing
    ring.note_profiler(False, 20)
    for i in range(20):                             # later traffic
        ring.append(rpcz.Span("S", f"after{i}", start_ns=30 + i))
    assert (ring.session_start_ns, ring.session_end_ns) == (10, 20)
    assert [s.method for s in ring.session_spans()] == ["inside"]
    ring.clear()
    ring.append(rpcz.Span("S", "only", start_ns=1))
    assert [s.method for s in ring.session_spans()] == ["only"]


# ---------------------------------------------------------------------------
# the request path (native core + fake PJRT plug-in)
# ---------------------------------------------------------------------------

@pytest.fixture
def shard():
    from brpc_tpu import rpc
    from brpc_tpu.ps_remote import DevicePsShardServer

    dev = rpc.DeviceClient(rpc.fake_pjrt_plugin_path())
    srv = DevicePsShardServer(VOCAB, DIM, 0, 1, device_client=dev,
                              combine=True)
    ch = rpc.Channel(srv.address, timeout_ms=10000)
    try:
        yield srv, ch
    finally:
        ch.close()
        srv.close()
        dev.close()


def _traced_call(ch, method, request):
    """The spans of one call made under obs.span — its trace, whatever
    else the store holds — once the late stamps are in: the response
    written, every transfer done with its host buffer."""
    with obs.span("Test", method) as user:
        rsp = ch.call("Ps", method, request)
    deadline = time.monotonic() + 5
    while True:
        spans = [s for s in rpcz.default_ring().snapshot()
                 if s.trace_id == user.trace_id]
        names = collections.Counter(s.name for s in spans)
        if names["rpc.send"] and names["dev.stage.h2d"] == names["dev.stage"]:
            return rsp, spans
        assert time.monotonic() < deadline, "a late stamp never came"
        time.sleep(0.005)


def _tree(spans):
    by_id = {s.span_id: s for s in spans}
    kids = collections.defaultdict(list)
    for s in spans:
        if s.parent_id in by_id:
            kids[s.parent_id].append(s)
    return by_id, kids


def _names(spans):
    return sorted(s.name for s in spans)


LATE = {"dev.stage.h2d"}    # ends on the plug-in's thread, whenever it does


def _check_nesting(spans, slack_ns=0):
    """Every child inside its parent's interval, the children of one
    parent not overlapping (one thread ran them), their sum within the
    parent.  A late child starts inside its parent and ends when the
    native core says so."""
    by_id, kids = _tree(spans)
    for pid, below in kids.items():
        p = by_id[pid]
        for c in below:
            if c.name in LATE:
                assert p.start_ns <= c.start_ns <= p.end_ns, (p.name, c.name)
                assert c.start_ns <= c.end_ns
        below = sorted((s for s in below if s.name not in LATE),
                       key=lambda s: s.start_ns)
        for c in below:
            assert c.start_ns <= c.end_ns
            assert p.start_ns - slack_ns <= c.start_ns, (p.name, c.name)
            assert c.end_ns <= p.end_ns + slack_ns, (p.name, c.name)
        for a, b in zip(below, below[1:]):
            assert a.end_ns <= b.start_ns, (p.name, a.name, b.name)
        assert sum(c.end_ns - c.start_ns for c in below) <= \
            p.end_ns - p.start_ns + slack_ns


PHASES = ["rpc.copy_in", "rpc.copy_out", "rpc.gil_wait", "rpc.queue",
          "rpc.recv", "rpc.send"]


@pytest.mark.needs_native
def test_lookup_leaves_one_tree_with_exactly_these_spans(shard):
    from brpc_tpu.ps_remote import _pack_lookup_req

    _, ch = shard
    ids = np.arange(K, dtype=np.int32)
    req = bytes(_pack_lookup_req(ids))
    assert len(req) == 4 + 4 * K == 260
    ch.call("Ps", "Lookup", req)                    # compile the bucket
    rsp, spans = _traced_call(ch, "Lookup", req)
    rows = K * DIM * 4
    assert len(rsp) == rows == 2048

    # one trace id on both sides of the socket
    assert {s.side for s in spans} == {"user", "client", "server", "span"}
    by_id, kids = _tree(spans)
    (user,) = [s for s in spans if s.side == "user"]
    (client,) = [s for s in spans if s.side == "client"]
    (root,) = [s for s in spans if s.side == "server"]
    assert client.parent_id == user.span_id
    assert root.name == "ps.handler" and root.method == "Lookup"
    # across the socket: the server's phases and its handler root hang
    # from the client's call span
    assert root.parent_id == client.span_id
    beside = [s for s in kids[client.span_id] if s is not root]
    assert _names(beside) == PHASES
    assert _names(kids[root.span_id]) == sorted([
        "rpc.copy_in", "ps.lock_wait", "ps.lock_wait", "ps.lock_wait",
        "ps.pad", "dev.stage", "dev.execute.gather_rows", "dev.fetch"])
    (stage,) = [s for s in spans if s.name == "dev.stage"]
    (fetch,) = [s for s in spans if s.name == "dev.fetch"]
    assert _names(kids[stage.span_id]) == [
        "dev.stage.h2d", "dev.stage.pool_copy", "dev.stage.tobytes"]
    # the transfer: from the pool copy's end to the plug-in's
    # done-with-host-buffer callback, not to the stage call's return
    h2d, pool = (next(s for s in kids[stage.span_id] if s.name == n)
                 for n in ("dev.stage.h2d", "dev.stage.pool_copy"))
    assert h2d.start_ns == pool.end_ns < h2d.end_ns
    assert (h2d.nbytes, h2d.copy) == (4 * K, False)
    assert _names(kids[fetch.span_id]) == [
        "dev.fetch.copy_out", "dev.fetch.copy_out", "dev.fetch.d2h",
        "dev.fetch.repack"]
    assert len(spans) == 3 + 6 + 8 + 3 + 4

    # within the server: one thread, strict nesting; across the socket the
    # two sides stamp on one clock but not in one thread
    _check_nesting([s for s in spans if s is root or s.side == "span"
                    and s.parent_id != client.span_id])
    for s in beside + [root]:
        assert client.start_ns <= s.start_ns
    order = ["rpc.recv", "rpc.queue", "rpc.copy_in", "rpc.gil_wait"]
    phase = {s.name: s for s in beside}
    for a, b in zip(order, order[1:]):
        assert phase[a].end_ns == phase[b].start_ns
    assert phase["rpc.gil_wait"].end_ns == root.start_ns
    assert root.end_ns <= phase["rpc.copy_out"].start_ns
    assert phase["rpc.copy_out"].end_ns == phase["rpc.send"].start_ns

    # the bytes really copied, by hand: 64 ids, dim 8, float32
    copies = collections.Counter(
        (s.name, s.nbytes) for s in spans if s.copy)
    assert copies == collections.Counter({
        ("rpc.copy_in", 260): 2,          # IOBuf -> flat, flat -> bytes
        ("ps.pad", 4 * K): 1,             # the zeroed bucket of ids
        ("dev.stage.tobytes", 4 * K): 1,
        ("dev.stage.pool_copy", 4 * K): 1,
        ("dev.fetch.repack", 0): 1,       # the fake lands row-major
        ("dev.fetch.copy_out", rows): 2,  # IOBuf -> malloc, -> bytes
        ("rpc.copy_out", rows): 1})
    assert phase["rpc.recv"].nbytes == 260 + 12
    assert phase["rpc.send"].nbytes > rows
    assert by_id[root.span_id].request_bytes == 260
    assert by_id[root.span_id].response_bytes == rows


@pytest.mark.needs_native
def test_a_pinned_response_copies_nothing_on_its_way_out(shard):
    """At 4,096 bytes and over the handler's rows are borrowed into the
    response, not copied: rpc.copy_out is there and moved 0 bytes."""
    from brpc_tpu.ps_remote import _pack_lookup_req

    _, ch = shard
    req = bytes(_pack_lookup_req(np.arange(VOCAB, dtype=np.int32)))
    ch.call("Ps", "Lookup", req)
    rsp, spans = _traced_call(ch, "Lookup", req)
    assert len(rsp) == VOCAB * DIM * 4 == 8192
    (out,) = [s for s in spans if s.name == "rpc.copy_out"]
    assert (out.nbytes, out.copy) == (0, True)
    (send,) = [s for s in spans if s.name == "rpc.send"]
    assert send.nbytes > 8192


@pytest.mark.needs_native
def test_apply_leaves_one_tree_with_exactly_these_spans(shard):
    from brpc_tpu.ps_remote import _pack_apply_req

    srv, ch = shard
    ids = np.arange(K, dtype=np.int32)
    grads = np.ones((K, DIM), np.float32)
    req = bytes(_pack_apply_req(ids, grads))
    assert len(req) == 4 + 4 * K + 4 * K * DIM == 2308
    before = srv.table.copy()
    ch.call("Ps", "ApplyGrad", req)                 # compile the bucket
    _, spans = _traced_call(ch, "ApplyGrad", req)
    assert np.allclose(srv.table[:K], before[:K] - 2 * srv.lr * grads,
                       atol=1e-6)

    by_id, kids = _tree(spans)
    (client,) = [s for s in spans if s.side == "client"]
    (root,) = [s for s in spans if s.side == "server"]
    assert root.method == "ApplyGrad" and root.parent_id == client.span_id
    assert _names(s for s in kids[client.span_id] if s is not root) == PHASES
    assert _names(kids[root.span_id]) == ["ps.combine_wait", "rpc.copy_in"]
    (wait,) = [s for s in spans if s.name == "ps.combine_wait"]
    # the lone request leads its own batch: the batch's work is its wait
    assert _names(kids[wait.span_id]) == sorted([
        "ps.pad", "dev.stage", "dev.stage", "ps.lock_wait",
        "dev.execute.scatter_sub", "ps.lock_wait"])
    for stage in [s for s in spans if s.name == "dev.stage"]:
        assert _names(kids[stage.span_id]) == [
            "dev.stage.h2d", "dev.stage.pool_copy", "dev.stage.tobytes"]
    _check_nesting([s for s in spans if s is root or s.side == "span"
                    and s.parent_id != client.span_id])
    copies = collections.Counter(
        (s.name, s.nbytes) for s in spans if s.copy)
    assert copies == collections.Counter({
        ("rpc.copy_in", 2308): 2,
        ("ps.pad", 4 * K + 4 * K * DIM): 1,   # ids + gradients, one bucket
        ("dev.stage.tobytes", 4 * K): 1,
        ("dev.stage.pool_copy", 4 * K): 1,
        ("dev.stage.tobytes", 4 * K * DIM): 1,
        ("dev.stage.pool_copy", 4 * K * DIM): 1,
        ("rpc.copy_out", 0): 1})              # an empty acknowledgement


@pytest.mark.needs_native
def test_remote_embedding_root_joins_the_shards_trees(shard):
    from brpc_tpu.ps_remote import RemoteEmbedding

    srv, _ = shard
    emb = RemoteEmbedding([srv.address], VOCAB, DIM)
    try:
        ids = np.arange(K, dtype=np.int32)
        emb.lookup(ids)
        with obs.span("Test", "step") as user:
            emb.lookup(ids)
            emb.apply_gradients(ids, np.ones((K, DIM), np.float32))
    finally:
        emb.close()
    spans = [s for s in rpcz.default_ring().snapshot()
             if s.trace_id == user.trace_id]
    by_id, kids = _tree(spans)
    ops = {s.method: s for s in spans if s.service == "emb"}
    assert sorted(ops) == ["apply_gradients", "lookup"]
    for op, method in (("lookup", "Lookup"),
                       ("apply_gradients", "ApplyGradId")):
        (call,) = kids[ops[op].span_id]
        assert (call.side, call.method) == ("client", method)
        (root,) = [s for s in kids[call.span_id] if s.side == "server"]
        assert root.method == method
    # the operator's page shows it as one tree
    (top,) = [d for d in obs.dump_rpcz() if d["name"] == "Test.step"]
    assert [c["name"] for c in top["children"]] == [
        "emb.lookup", "emb.apply_gradients"]
    text = rpcz.format_rpcz([top])
    assert "      server Ps.Lookup" in text
    assert "        dev.execute.gather_rows" in text


@pytest.mark.needs_native
def test_obs_disabled_leaves_a_request_path_that_records_nothing(shard):
    from brpc_tpu.ps_remote import _pack_lookup_req

    _, ch = shard
    req = bytes(_pack_lookup_req(np.arange(K, dtype=np.int32)))
    ch.call("Ps", "Lookup", req)
    time.sleep(0.05)
    rpcz.default_ring().snapshot()                  # drain what is left
    rpcz.clear()
    obs.set_enabled(False)
    try:
        for _ in range(3):
            assert len(ch.call("Ps", "Lookup", req)) == K * DIM * 4
        time.sleep(0.05)
        assert rpcz.default_ring().snapshot() == []
    finally:
        obs.set_enabled(True)


@pytest.mark.needs_native
def test_a_root_the_budget_refuses_has_no_children(shard, monkeypatch):
    from brpc_tpu.ps_remote import _pack_lookup_req

    _, ch = shard
    req = bytes(_pack_lookup_req(np.arange(K, dtype=np.int32)))
    monkeypatch.setattr(rpcz, "_budget", rpcz._Budget(0))
    time.sleep(0.05)
    rpcz.default_ring().snapshot()                  # drain what is left
    rpcz.clear()
    ch.call("Ps", "Lookup", req)
    time.sleep(0.05)
    spans = rpcz.default_ring().snapshot()
    # the two flat call records rpcz always kept, and nothing below them
    assert sorted(s.side for s in spans) == ["client", "server"]
    assert not any(s.trace_id for s in spans)


@pytest.mark.needs_native
def test_ids_cross_the_socket_only_for_a_request_somebody_asked_to_see(
        shard, monkeypatch):
    """A client root the budget admitted keeps its ids to itself: the
    server behind it traces what its own budget admits, not what every
    client's does."""
    from brpc_tpu.ps_remote import _pack_lookup_req

    _, ch = shard
    req = bytes(_pack_lookup_req(np.arange(K, dtype=np.int32)))
    ch.call("Ps", "Lookup", req)
    time.sleep(0.05)
    rpcz.clear()
    # room for the client's root alone: the server's own budget is spent
    monkeypatch.setattr(rpcz, "_budget", rpcz._Budget(1, clock=lambda: 5.0))
    ch.call("Ps", "Lookup", req)
    time.sleep(0.05)
    spans = rpcz.default_ring().snapshot()
    (client,) = [s for s in spans if s.side == "client"]
    (root,) = [s for s in spans if s.side == "server"]
    assert client.trace_id and not client._spread
    assert not root.trace_id and len(spans) == 2
    # with room for both, each side has a trace of its own
    rpcz.clear()
    monkeypatch.setattr(rpcz, "_budget", rpcz._Budget(2, clock=lambda: 5.0))
    ch.call("Ps", "Lookup", req)
    time.sleep(0.05)
    spans = rpcz.default_ring().snapshot()
    (client,) = [s for s in spans if s.side == "client"]
    (root,) = [s for s in spans if s.side == "server"]
    assert root.trace_id and root.trace_id != client.trace_id
    assert root.parent_id == 0


@pytest.mark.needs_native
@pytest.mark.filterwarnings("ignore::pytest.PytestUnraisableExceptionWarning")
def test_a_failing_gate_leaves_the_pooled_thread_no_current_span(monkeypatch):
    """Whatever gets past the trampoline's handlers, the request's root
    is closed and recorded and stops being its thread's current span:
    later calls on that thread must not join a dead trace."""
    from brpc_tpu import rpc

    class Gate:
        max_concurrency = 1

        def admit(self):
            return True

        def on_responded(self, error_code, latency_us):
            raise RuntimeError("the gate's own failure")

    class Limiter:
        def gate(self, method):
            return Gate()

    seen = []

    def handler(method, request):
        seen.append(rpcz.current())
        return b"ok"

    srv = rpc.Server()
    srv.add_service("Echo", handler)
    ch = rpc.Channel(f"127.0.0.1:{srv.start('127.0.0.1:0')}",
                     timeout_ms=10000)
    try:
        with obs.span("Test", "first"):
            assert ch.call("Echo", "Echo", b"x") == b"ok"
        srv.set_concurrency_limiter(Limiter())
        with obs.span("Test", "gated") as user:
            assert ch.call("Echo", "Echo", b"x") == b"ok"
        srv.set_concurrency_limiter(None)
        # the handler threads are pooled: a later request nobody traces
        # finds no span there
        monkeypatch.setattr(rpcz, "_budget", rpcz._Budget(0))
        for _ in range(8):
            ch.call("Echo", "Echo", b"x")
    finally:
        ch.close()
        srv.close()
    assert seen[1].trace_id == user.trace_id        # it was traced
    assert len(seen) == 10 and seen[2:] == [None] * 8
    (root,) = [s for s in rpcz.default_ring().snapshot()
               if s.side == "server" and s.trace_id == user.trace_id]
    assert root.end_ns and not root._pushed


# ---------------------------------------------------------------------------
# the profiler's clock
# ---------------------------------------------------------------------------

def _host_events(path, prefix):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name[len(prefix):], e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


@pytest.mark.needs_native
def test_spans_land_in_the_profilers_trace_on_its_clock(shard, tmp_path):
    import jax
    import jax.profiler

    from brpc_tpu.ps_remote import _pack_lookup_req

    _, ch = shard
    req = bytes(_pack_lookup_req(np.arange(K, dtype=np.int32)))
    ch.call("Ps", "Lookup", req)
    jax.devices()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, spans = _traced_call(ch, "Lookup", req)
    finally:
        jax.profiler.stop_trace()
    ring = rpcz.default_ring()
    assert ring.session_start_ns and not ring.session_end_ns
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = _host_events(path, "brt/")
    by_name = collections.defaultdict(list)
    for name, t0, t1 in events:
        by_name[name].append((t0, t1))
    (handler,) = by_name["ps.handler"]
    (stage,) = by_name["dev.stage"]
    assert handler[0] <= stage[0] and stage[1] <= handler[1]
    # native spans are not mirrored: they carry the core's own stamps
    assert "rpc.recv" not in by_name and "dev.stage.h2d" not in by_name
    # one clock: the offset any mirrored span gives places every other
    # within a millisecond (the profiler's clock starts with its session)
    span = {s.name: s for s in spans if s.name in (
        "ps.handler", "dev.stage", "dev.fetch", "ps.pad",
        "dev.execute.gather_rows")}
    offset = span["ps.handler"].start_ns - handler[0]
    assert 0 < offset <= ring.session_start_ns + 1_000_000
    for name, s in span.items():
        (ev,) = by_name[name]
        assert abs((s.start_ns - offset) - ev[0]) < 1_000_000, name
        assert abs((s.end_ns - offset) - ev[1]) < 1_000_000, name

    # handed those spans and a device timeline with holes, the benchmark's
    # reduction names the holes by them (its reader changes one prefix)
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    try:
        import trace_reduce
    finally:
        sys.path.pop(0)
    (fetch,) = by_name["dev.fetch"]
    host = [(n, a * 1e-9, b * 1e-9) for n, a, b in events
            if n in ("dev.stage", "dev.execute.gather_rows", "dev.fetch")]
    s0, s1 = stage[0] * 1e-9, stage[1] * 1e-9
    f0, f1 = fetch[0] * 1e-9, fetch[1] * 1e-9
    # a device that idles exactly while the host stages and fetches
    busy = [(s0 - 1e-3, s0), (s1, f0), (f1, f1 + 1e-3)]
    device = {0: {"modules": [("brt_gather_rows(1)", a, b)
                              for a, b in busy],
                  "ops": [("%fusion = f32[64,8]{1,0} fusion()", a, b)
                          for a, b in busy]}}
    reduced = trace_reduce.reduce_planes(device, host,
                                         (s0 - 1e-3, f1 + 1e-3))
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert sorted(gaps) == ["dev.fetch", "dev.stage"]
    assert abs(gaps["dev.stage"] - (s1 - s0)) < 1e-9
    assert abs(gaps["dev.fetch"] - (f1 - f0)) < 1e-9
