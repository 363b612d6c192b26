"""The readers of the program's own span trees (benchmark/readers/
program.py): hand-worked values on a hand-built span list, and a traced
dry run of each candidate cell reports every metric that reads them (their
entries are ``candidates.json``'s since PR 36; PR 26, which changed the
program, had to keep them in a side manifest)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from readers import program  # noqa: E402

from brpc_tpu import obs  # noqa: E402
from brpc_tpu.obs import rpcz  # noqa: E402

with open(os.path.join(BENCH, "candidates.json")) as _f:
    CANDIDATES = json.load(_f)


def _reader(metric):
    with open(os.path.join(BENCH, "layer_metrics",
                           metric["name"] + ".json")) as f:
        return json.load(f)["reader"]


# the metrics that read the program's own span trees
NEW = [m for m in CANDIDATES["per_layer"]
       if _reader(m).startswith("program.")]
MS = 1_000_000


def _span(name, t0_ms, t1_ms, trace, sid, parent, nbytes=0, copy=False):
    layer, _, what = name.partition(".")
    return rpcz.Span(layer, what, "span", start_ns=t0_ms * MS,
                     end_ns=t1_ms * MS, trace_id=trace, span_id=sid,
                     parent_id=parent, nbytes=nbytes, copy=copy)


def _root(method, t0_ms, t1_ms, trace, sid, parent, req, rsp):
    return rpcz.Span("Ps", method, "server", start_ns=t0_ms * MS,
                     end_ns=t1_ms * MS, trace_id=trace, span_id=sid,
                     parent_id=parent, request_bytes=req, response_bytes=rsp)


@pytest.fixture
def hand_built():
    """Two requests. A Lookup (trace 1, its client's span 77 elsewhere):
    recv 2 ms, queue 1 ms, gil_wait 3 ms, handler 10..30 ms holding a
    lock wait of 1 ms, a stage of 4 ms (1 ms tobytes + 1 ms pool copy + 1
    ms h2d of 1000 B), an execute of 2 ms, a fetch of 8 ms (4 ms d2h of
    4000 B, 2 ms + 1 ms of copies), two overlapping children counted
    once; then a send of 5 ms. An ApplyGrad (trace 2, no client span):
    handler 100..140 ms holding only a combine wait of 30 ms and two lock
    waits of 2 and 3 ms."""
    obs.set_enabled(True)
    rpcz.clear()
    ring = rpcz.default_ring()
    for s in [
        _span("rpc.recv", 4, 6, 1, 10, 77, 1012),
        _span("rpc.queue", 6, 7, 1, 11, 77),
        _span("rpc.gil_wait", 7, 10, 1, 12, 77),
        _root("Lookup", 10, 30, 1, 20, 77, 1000, 4000),
        _span("ps.lock_wait", 10, 11, 1, 21, 20),
        _span("dev.stage", 11, 15, 1, 22, 20, 1000),
        _span("dev.stage.tobytes", 11, 12, 1, 23, 22, 1000, True),
        _span("dev.stage.pool_copy", 12, 13, 1, 24, 22, 1000, True),
        _span("dev.stage.h2d", 13, 14, 1, 25, 22, 1000),
        _span("dev.execute.gather_rows", 15, 17, 1, 26, 20),
        _span("dev.fetch", 17, 25, 1, 27, 20, 4000),
        _span("dev.fetch.d2h", 17, 21, 1, 28, 27, 4000),
        _span("dev.fetch.repack", 21, 23, 1, 29, 27, 4000, True),
        _span("dev.fetch.copy_out", 23, 24, 1, 30, 27, 4000, True),
        _span("ps.pad", 24, 26, 1, 31, 20, 0, True),      # overlaps fetch
        _span("rpc.send", 30, 35, 1, 13, 77, 4020),
        _root("ApplyGrad", 100, 140, 2, 40, 0, 6000, 0),
        _span("ps.combine_wait", 105, 135, 2, 41, 40),
        _span("ps.lock_wait", 101, 103, 2, 42, 40),
        _span("ps.lock_wait", 136, 139, 2, 43, 40),
        # not a request of this process: never read
        rpcz.Span("emb", "lookup", "user", start_ns=0, end_ns=MS,
                  trace_id=3, span_id=50),
        _span("dev.stage", 0, 1, 3, 51, 50, 999),
    ]:
        ring.append(s)
    yield {}
    rpcz.clear()


def test_readers_return_hand_worked_values(hand_built):
    run = hand_built
    q = program.span_ms_quantile
    assert q(run, name="rpc.recv", q=50) == 2.0
    assert q(run, name="rpc.recv", q=50, methods=["Lookup"]) == 2.0
    assert q(run, name="rpc.recv", q=50, methods=["ApplyGrad"]) is None
    assert q(run, name="rpc.send", q=50, methods=["Lookup"]) == 5.0
    assert q(run, name="rpc.gil_wait", q=95) == 3.0
    assert q(run, name="dev.execute.gather_rows", q=50) == 2.0
    assert q(run, name="ps.combine_wait", q=50) == 30.0
    assert q(run, name="dev.stage", q=50) == 4.0       # trace 3 not read
    # per request: 1 ms in the lookup, 2 + 3 in the apply
    lock = program.span_sum_ms_quantile
    assert lock(run, name="ps.lock_wait", q=50) == 1.0
    assert lock(run, name="ps.lock_wait", q=95) == 5.0
    # self time: 20 ms less [10,11] [11,15] [15,17] [17,25] and ps.pad's
    # [24,26], of which only [25,26] is not covered already: 20 - 16
    self_ms = program.root_self_ms_quantile
    assert self_ms(run, q=50, methods=["Lookup"]) == 4.0
    # 40 ms less 30 + 2 + 3
    assert self_ms(run, q=50, methods=["ApplyGrad"]) == 5.0
    share = program.child_share
    assert share(run, parent="dev.stage", children=[
        "dev.stage.tobytes", "dev.stage.pool_copy"]) == 50.0
    assert share(run, parent="dev.fetch", children=[
        "dev.fetch.repack", "dev.fetch.copy_out"]) == 37.5
    # the transfer outlives its call: h2d ends at 19, the stage at 15, so
    # the stage that covers it lasts 11..19 and the copies are 2 of 8 ms
    ring = rpcz.default_ring()
    ring.append(_span("dev.stage.h2d", 13, 19, 1, 32, 22, 1000))
    late = {}
    assert share(late, parent="dev.stage", children=[
        "dev.stage.tobytes", "dev.stage.pool_copy"],
        through=["dev.stage.h2d"]) == 25.0
    assert share(late, parent="dev.stage", children=[
        "dev.stage.tobytes", "dev.stage.pool_copy"]) == 50.0
    # copied 1000 + 1000 + 4000 + 4000 over 1000 + 4000 (+ 6000 + 0)
    assert program.copy_ratio(run, methods=["Lookup"]) == 2.0
    assert program.copy_ratio(run) == 10000 / 11000
    # 4000 B in 4 ms; 1000 B in 1 ms
    assert program.span_gbps(run, name="dev.fetch.d2h") == 1e-3
    assert program.span_gbps(run, name="dev.stage.h2d") == 1e-3
    assert program.covered_ns(0, 10, [(2, 4), (3, 6), (9, 20)]) == 5


def test_readers_read_nothing_where_there_are_no_spans():
    rpcz.clear()
    run = {}
    assert program.span_ms_quantile(run, name="rpc.recv", q=50) is None
    assert program.root_self_ms_quantile(run, q=50) is None
    assert program.copy_ratio(run) is None
    assert program.child_share(run, parent="dev.stage",
                               children=["dev.stage.h2d"]) is None
    assert program.span_gbps(run, name="dev.fetch.d2h") is None


def test_the_sixteen_and_no_others():
    assert len(NEW) == 16
    assert all(m["source"] == "program_span" for m in NEW)
    layers = {m["layer"] for m in NEW}
    assert layers == {"host RPC", "shard server (DevicePsShardServer)",
                      "native device tier (DeviceClient)"}
    # as candidates.json's own entries are held to: each moves what its
    # cells report, and its file names a reader and agrees with the entry
    e2e = {m["name"]: m for m in CANDIDATES["end_to_end"]}
    names = [m["name"] for m in CANDIDATES["per_layer"]]
    assert len(names) == len(set(names))
    for m in NEW:
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert callable(getattr(program, spec["reader"].split(".", 1)[1]))
        assert spec["reader"].startswith("program.")
        for key in ("unit", "layer", "moves", "workloads", "source"):
            assert spec[key] == m[key], (m["name"], key)


@pytest.mark.parametrize("cell", [w["name"] for w in CANDIDATES["workloads"]])
def test_a_traced_dry_run_would_report_every_new_metric(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 11), "--seconds", "1.5", "--cpu-dry-run",
         "--trace", "1"], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    want = sorted(m["name"] for m in NEW if cell in m["workloads"])
    assert want and set(want) <= set(out["dry_run"]["would_report"])
