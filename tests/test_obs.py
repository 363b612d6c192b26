"""brpc_tpu.obs: bvar-semantics reducers, windows over a fake clock,
latency percentile bounds, registry dumps, rpcz ring, and (native-gated)
the instrumented RPC fabric + the _status builtin service."""

import json
import threading

import numpy as np
import pytest

from brpc_tpu import obs
from brpc_tpu.obs import rpcz, status_service
from brpc_tpu.obs.vars import (
    Adder,
    LatencyRecorder,
    Maxer,
    PassiveStatus,
    PerSecond,
    Registry,
    Window,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# reducers
# ---------------------------------------------------------------------------

def test_adder_semantics():
    a = Adder()
    assert a.get_value() == 0
    a.add()
    a.add(4)
    a << 5
    assert a.get_value() == 10
    a.add(-3)
    assert a.get_value() == 7
    a.reset()
    assert a.get_value() == 0


def test_maxer_miner_semantics():
    mx = Maxer()
    assert mx.get_value() == 0  # empty -> 0, like bvar's default dump
    for v in (3, 9, 1):
        mx.update(v)
    assert mx.get_value() == 9


def test_adder_across_threads():
    a = Adder()
    n_threads, per = 8, 10_000

    def work():
        for _ in range(per):
            a.add(1)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert a.get_value() == n_threads * per


def test_passive_status():
    box = {"v": 3}
    p = PassiveStatus(lambda: box["v"])
    assert p.get_value() == 3
    box["v"] = 7
    assert p.get_value() == 7


# ---------------------------------------------------------------------------
# windows on a fake clock
# ---------------------------------------------------------------------------

def test_window_over_adder_fake_clock():
    clk = FakeClock()
    a = Adder()
    w = Window(a, window_size=3, clock=clk)
    for _ in range(5):       # 5 seconds, 10 units each
        a.add(10)
        clk.advance(1.0)
        w.get_value()        # lazy sampler: reads drive the per-second ticks
    # window covers the last 3 seconds: 30 units
    assert w.get_value() == 30
    clk.advance(10.0)        # quiet gap longer than the window
    assert w.get_value() == 0


def test_window_over_maxer_fake_clock():
    clk = FakeClock()
    m = Maxer()
    w = Window(m, window_size=3, clock=clk)
    m.update(100)            # second 0
    clk.advance(1.0)
    w.get_value()            # tick so the sample lands in its own slot
    m.update(7)              # second 1
    clk.advance(1.0)
    w.get_value()
    m.update(5)              # second 2
    clk.advance(1.0)
    assert w.get_value() == 100
    clk.advance(1.0)         # second 0's max ages out of the 3s window
    assert w.get_value() == 7
    clk.advance(2.0)         # everything ages out
    assert w.get_value() == 0


def test_per_second_fake_clock():
    clk = FakeClock()
    a = Adder()
    qps = PerSecond(a, window_size=10, clock=clk)
    for _ in range(10):      # 50 events/s for 10 seconds
        a.add(50)
        clk.advance(1.0)
    assert qps.get_value() == pytest.approx(50.0)
    for _ in range(10):      # rate drops to 0
        clk.advance(1.0)
        qps.get_value()
    assert qps.get_value() == pytest.approx(0.0)


def test_per_second_rejects_maxer():
    with pytest.raises(TypeError):
        PerSecond(Maxer(), clock=FakeClock()).get_value()


# ---------------------------------------------------------------------------
# latency recorder
# ---------------------------------------------------------------------------

def test_latency_recorder_percentile_bounds():
    rec = LatencyRecorder(clock=FakeClock())
    rng = np.random.default_rng(0)
    # lognormal latencies around 1ms
    samples_s = np.exp(rng.normal(np.log(1e-3), 1.0, 20_000))
    for s in samples_s:
        rec.record(float(s))
    assert rec.count == 20_000
    true_us = np.sort(samples_s * 1e6)
    # log-bucket quantisation: 20 buckets/decade -> ±12.2% relative error,
    # allow 2 bucket widths for rank-vs-midpoint slop
    for q in (0.50, 0.90, 0.99, 0.999):
        got = rec.percentile(q)
        want = float(true_us[min(int(q * 20_000), 19_999)])
        assert want / 1.3 <= got <= want * 1.3, (q, got, want)
    assert rec.avg_us == pytest.approx(float(np.mean(true_us)), rel=0.01)
    assert rec.max_us == pytest.approx(float(true_us[-1]), rel=0.01)


def test_latency_recorder_value_shape():
    rec = LatencyRecorder(clock=FakeClock())
    rec.record(0.001)
    v = rec.get_value()
    assert v["count"] == 1
    assert set(v) == {"count", "qps", "avg_us", "max_us", "p50_us",
                      "p90_us", "p99_us", "p999_us"}
    assert 800 < v["p50_us"] < 1250


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_dump_and_filtering():
    reg = Registry()
    a = Adder()
    a.add(42)
    reg.expose("rpc_client_echo_count", a)
    reg.expose("ps_server_keys", Adder())
    text = reg.dump_exposed()
    assert "rpc_client_echo_count : 42" in text
    assert "ps_server_keys : 0" in text
    # substring, glob, predicate filters
    assert "ps_server" not in reg.dump_exposed("rpc_")
    assert list(reg.dump_exposed_dict("rpc_*")) == ["rpc_client_echo_count"]
    assert reg.dump_exposed_dict(lambda n: n.startswith("ps_")) == {
        "ps_server_keys": 0}
    reg.hide("ps_server_keys")
    assert "ps_server_keys" not in reg.names()


def test_expose_default_registry():
    a = Adder()
    a.expose("test_obs_tmp_var")
    try:
        assert "test_obs_tmp_var" in obs.dump_exposed("test_obs_tmp_")
    finally:
        obs.default_registry().hide("test_obs_tmp_var")


# ---------------------------------------------------------------------------
# rpcz
# ---------------------------------------------------------------------------

def test_rpcz_ring_bounded():
    ring = rpcz.SpanRing(capacity=16)
    for i in range(100):
        ring.append(rpcz.Span("S", f"m{i}"))
    assert len(ring) == 16
    dumped = ring.dump(limit=100)
    assert len(dumped) == 16
    # newest first, oldest 84 fell off
    assert dumped[0]["method"] == "m99"
    assert dumped[-1]["method"] == "m84"
    ring.set_capacity(4)
    assert len(ring) == 4


def test_rpcz_dump_filters():
    ring = rpcz.SpanRing(capacity=64)
    ring.append(rpcz.Span("Echo", "Echo", side="client"))
    ring.append(rpcz.Span("Echo", "Echo", side="server"))
    ring.append(rpcz.Span("Ps", "Lookup", side="client", error_code=2001,
                          error_text="boom"))
    assert len(ring.dump(service="Echo")) == 2
    assert len(ring.dump(side="server")) == 1
    assert len(ring.dump(errors_only=True)) == 1
    assert len(ring.dump(limit=1)) == 1
    assert ring.dump(method="Lookup")[0]["error_text"] == "boom"


def test_span_context_manager_records_and_reraises():
    ring = rpcz.SpanRing(capacity=8)
    with rpcz.span("User", "ok", ring=ring) as sp:
        sp.annotate("phase1")
    with pytest.raises(ValueError):
        with rpcz.span("User", "bad", ring=ring):
            raise ValueError("nope")
    spans = ring.dump()
    assert [d["method"] for d in spans] == ["bad", "ok"]
    assert spans[0]["error_code"] == 2001 and "nope" in spans[0]["error_text"]
    assert spans[1]["annotations"] == ["phase1"]
    assert spans[1]["latency_us"] >= 0


def test_status_handler_without_rpc():
    """The _status handler is just a function — exercises the full wire
    mapping with no native server."""
    reg = Registry()
    counter = Adder()
    counter.add(5)
    reg.expose("demo_counter", counter)
    ring = rpcz.SpanRing(capacity=8)
    ring.append(rpcz.Span("Echo", "Echo", side="server"))
    h = status_service.make_status_handler(registry=reg, ring=ring)
    assert h("health", b"") == b"ok"
    assert h("vars", b"") == b"demo_counter : 5"
    assert json.loads(h("vars_json", b"")) == {"demo_counter": 5}
    spans = json.loads(h("rpcz", json.dumps({"limit": 10}).encode()))
    assert spans[0]["service"] == "Echo"
    assert b"Echo.Echo" in h("rpcz_text", b"")
    with pytest.raises(ValueError):
        h("rpcz", b'{"bogus": 1}')
    with pytest.raises(ValueError):
        h("nope", b"")


def test_disabled_gate():
    obs.set_enabled(False)
    try:
        assert not obs.enabled()
    finally:
        obs.set_enabled(True)
    assert obs.enabled()


# ---------------------------------------------------------------------------
# the instrumented fabric (needs the native core)
# ---------------------------------------------------------------------------

@pytest.mark.needs_native
def test_channel_call_records_spans_and_latency():
    from brpc_tpu import rpc

    obs.reset_fabric_vars()
    rpcz.clear()
    srv = rpc.Server()

    def echo(method, req):
        if method != "Echo":
            # unknown methods must FAIL (the error-span assertions below
            # drive the Boom call through the failure path)
            raise ValueError(f"no method {method}")
        return req

    srv.add_service("Echo", echo)
    srv.add_status_service()
    port = srv.start("127.0.0.1:0")
    ch = rpc.Channel(f"127.0.0.1:{port}")
    try:
        for _ in range(3):
            assert ch.call("Echo", "Echo", b"x" * 100) == b"x" * 100

        # matching client/server recorders with the same call count
        dump = obs.dump_exposed_dict("rpc_")
        assert dump["rpc_client_Echo_Echo"]["count"] == 3
        assert dump["rpc_server_Echo_Echo"]["count"] == 3
        assert dump["rpc_client_Echo_Echo"]["avg_us"] > 0
        assert obs.counter("rpc_client_out_bytes").get_value() == 300
        assert obs.counter("rpc_server_in_bytes").get_value() == 300

        # matching client/server spans for the same call
        client = obs.dump_rpcz(service="Echo", side="client")
        server = obs.dump_rpcz(service="Echo", side="server")
        assert len(client) == 3 and len(server) == 3
        assert client[0]["request_bytes"] == server[0]["request_bytes"] == 100
        assert client[0]["peer"] == f"127.0.0.1:{port}"
        # server time is contained in client time
        assert server[0]["latency_us"] <= client[0]["latency_us"]

        # the _status builtin serves both dumps over the fabric itself
        text = status_service.scrape_vars(ch, "rpc_client_Echo")
        assert "rpc_client_Echo_Echo : count=3" in text
        remote_spans = status_service.scrape_rpcz(ch, service="Echo",
                                                  side="server")
        assert len(remote_spans) == 3

        # failed calls carry the error through spans + error counters
        with pytest.raises(rpc.RpcError):
            ch.call("Echo", "Boom", b"")
        errs = obs.dump_rpcz(errors_only=True)
        assert any(d["side"] == "client" and d["method"] == "Boom"
                   for d in errs)
        assert any(d["side"] == "server" and d["method"] == "Boom"
                   for d in errs)
        assert obs.counter("rpc_client_errors").get_value() == 1
        assert obs.counter("rpc_server_errors").get_value() == 1
    finally:
        ch.close()
        srv.close()


@pytest.mark.needs_native
def test_a_handler_threads_cell_outlives_its_callbacks():
    """A native thread enters Python through a ctypes callback with a
    fresh thread state, and fresh thread-locals, every time; its id
    stays.  The reducers keep a cell a thread, not one a request."""
    from brpc_tpu import rpc

    obs.reset_fabric_vars()
    fresh, idents = [], set()
    local = threading.local()

    def echo(method, req):
        fresh.append(not hasattr(local, "seen"))
        local.seen = True
        idents.add(threading.get_ident())
        return req

    srv = rpc.Server()
    srv.add_service("Echo", echo)
    port = srv.start("127.0.0.1:0")
    ch = rpc.Channel(f"127.0.0.1:{port}")
    try:
        for _ in range(200):
            ch.call("Echo", "Echo", b"x")
    finally:
        ch.close()
        srv.close()
    assert all(fresh)           # (why a threading.local would not do)
    counter = obs.counter("rpc_server_in_bytes")
    assert counter.get_value() == 200
    assert len(counter._cells) <= len(idents) < 200


@pytest.mark.needs_native
def test_ps_path_records_counters():
    from brpc_tpu.ps_remote import PsShardServer, RemoteEmbedding

    obs.reset_fabric_vars()
    rpcz.clear()
    vocab, dim, shards = 32, 8, 2
    servers = [PsShardServer(vocab, dim, i, shards) for i in range(shards)]
    emb = RemoteEmbedding([s.address for s in servers], vocab, dim)
    try:
        ids = np.array([0, 5, 17, 31], np.int32)
        rows = emb.lookup(ids)
        assert rows.shape == (4, dim)
        emb.apply_gradients(ids, np.ones((4, dim), np.float32))

        assert obs.counter("ps_client_lookup_keys").get_value() == 4
        assert obs.counter("ps_client_apply_keys").get_value() == 4
        assert obs.counter("ps_server_keys").get_value() == 8  # both ops
        assert obs.counter("ps_server_bytes_out").get_value() > 0
        assert obs.recorder("ps_client_lookup").count == 1
        # per-shard recorders saw one Lookup + one ApplyGrad each
        dump = obs.dump_exposed_dict("ps_server_shard")
        assert dump["ps_server_shard0_Lookup"]["count"] == 1
        # apply_gradients rides the idempotent unary write method
        assert dump["ps_server_shard1_ApplyGradId"]["count"] == 1
        # dump_exposed shows live ps_* lines after the instrumented path
        assert "ps_client_lookup" in obs.dump_exposed("ps_")
    finally:
        emb.close()
        for s in servers:
            s.close()


def test_collective_channel_counters():
    import jax
    import jax.numpy as jnp

    from brpc_tpu.parallel import CollectiveChannel, make_mesh

    obs.reset_fabric_vars()
    mesh = make_mesh({"dp": 8})
    chan = CollectiveChannel(mesh, "dp")
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    jax.jit(chan.all_reduce)(x)
    assert obs.counter("collective_all_reduce_calls").get_value() == 1
    assert obs.counter("collective_all_reduce_bytes").get_value() == 64 * 4
    chan.all_gather(x)
    assert obs.counter("collective_all_gather_calls").get_value() == 1


def test_maxer_helper_cached_exposed_and_reset():
    obs.reset_fabric_vars()
    m = obs.maxer("test_high_water")
    assert obs.maxer("test_high_water") is m  # cached per name
    m.update(3)
    m.update(7)
    m.update(5)
    assert m.get_value() == 7
    assert "test_high_water" in obs.dump_exposed_dict()
    obs.reset_fabric_vars()
    assert "test_high_water" not in obs.dump_exposed_dict()
