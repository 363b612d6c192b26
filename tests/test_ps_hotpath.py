"""PS hot-path parallelism: native async fan-out (call_async/join),
read-parallel CPU shard serving (rwlock), and the device shard's
handle-generation scheme.  Pure-Python pieces (_bucket) run everywhere;
everything touching the native core is @needs_native; device-shard tests
additionally need a PJRT plugin (fake or real) and skip otherwise."""

import os
import struct

import numpy as np
import pytest

from brpc_tpu.ps_remote import (DevicePsShardServer, PsShardServer,
                                RemoteEmbedding)


# ---- _bucket (pure python) ----

@pytest.mark.parametrize("count,want", [
    (0, 1), (1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (7, 8), (8, 8),
    (9, 16), (1023, 1024), (1024, 1024), (1025, 2048),
])
def test_bucket_rounds_up_to_power_of_two(count, want):
    assert DevicePsShardServer._bucket(count) == want


def test_bucket_is_monotonic_and_covers():
    prev = 0
    for count in range(0, 300):
        b = DevicePsShardServer._bucket(count)
        assert b >= max(count, 1)          # covers the batch
        assert b & (b - 1) == 0            # power of two
        assert b >= prev                   # monotonic in count
        prev = b


# ---- call_async vs call (native) ----

@pytest.mark.needs_native
def test_call_async_matches_sequential_byte_for_byte():
    from brpc_tpu import rpc

    srv = rpc.Server()
    srv.add_service("Echo", lambda method, req: method.encode() + req)
    port = srv.start("127.0.0.1:0")
    ch = rpc.Channel(f"127.0.0.1:{port}")
    try:
        payloads = [b"", b"x", os.urandom(17), os.urandom(65536),
                    np.arange(4096, dtype=np.float32).tobytes()]
        sync = [ch.call("Echo", f"M{i}", p)
                for i, p in enumerate(payloads)]
        pending = [ch.call_async("Echo", f"M{i}", p)
                   for i, p in enumerate(payloads)]
        assert [c.join() for c in pending] == sync
    finally:
        ch.close()
        srv.close()


@pytest.mark.needs_native
def test_call_async_error_propagates_through_join():
    from brpc_tpu import rpc

    srv = rpc.Server()

    def handler(method, req):
        raise ValueError(f"boom on {method}")

    srv.add_service("Err", handler)
    port = srv.start("127.0.0.1:0")
    ch = rpc.Channel(f"127.0.0.1:{port}")
    try:
        call = ch.call_async("Err", "Kaboom", b"x")
        with pytest.raises(rpc.RpcError) as ei:
            call.join()
        assert "boom on Kaboom" in str(ei.value)
        # a joined (even failed) call is spent
        with pytest.raises(RuntimeError):
            call.join()
        # unknown-service failure also arrives at join, not at start
        bad = ch.call_async("Ghost", "Nope", b"")
        with pytest.raises(rpc.RpcError):
            bad.join()
    finally:
        ch.close()
        srv.close()


@pytest.mark.needs_native
def test_call_async_close_without_join_is_safe():
    from brpc_tpu import rpc

    srv = rpc.Server()
    srv.add_service("Echo", lambda method, req: req)
    port = srv.start("127.0.0.1:0")
    ch = rpc.Channel(f"127.0.0.1:{port}")
    try:
        calls = [ch.call_async("Echo", "Echo", b"abandoned")
                 for _ in range(4)]
        for c in calls:
            c.close()   # waits for completion, frees — no leak, no crash
        for c in calls:
            c.close()   # idempotent
        assert ch.call("Echo", "Echo", b"still alive") == b"still alive"
    finally:
        ch.close()
        srv.close()


# ---- parallel fan-out client (native) ----

VOCAB, DIM, SHARDS = 64, 16, 4


@pytest.mark.needs_native
def test_parallel_lookup_matches_sequential_client():
    """The concurrent fan-out answers what one shard after another would:
    the reference is the servers' own seeded tables, read and updated in
    numpy row by row in request order (duplicates accumulate through
    ``subtract.at`` exactly as each shard applies them)."""
    servers = [PsShardServer(VOCAB, DIM, i, SHARDS) for i in range(SHARDS)]
    emb = RemoteEmbedding([s.address for s in servers], VOCAB, DIM)
    try:
        # a copy, taken before the write
        table = np.concatenate([s.table for s in servers])
        rng = np.random.default_rng(7)
        ids = rng.integers(0, VOCAB, size=(5, 6)).astype(np.int32)
        np.testing.assert_array_equal(emb.lookup(ids), table[ids])
        grads = rng.standard_normal((5, 6, DIM)).astype(np.float32)
        emb.apply_gradients(ids, grads)   # all shards, concurrently
        np.subtract.at(table, ids.reshape(-1),
                       servers[0].lr * grads.reshape(-1, DIM))
        np.testing.assert_array_equal(emb.lookup(ids), table[ids])
    finally:
        emb.close()
        for s in servers:
            s.close()


# ---- concurrent stress: no torn rows ----

def _row_deltas_are_whole(rows, init_rows):
    """Every served row must be a CONSISTENT snapshot: the delta from the
    initial table is a constant vector per row (apply-grads subtract a
    constant from the whole row, so a mixed delta within one row == a
    torn read)."""
    d = rows - init_rows
    return np.allclose(d.max(axis=-1), d.min(axis=-1), atol=1e-5)


def _hammer_one_shard(emb, init, vocab, rounds=25, lookups=8, applies=2):
    """call_async fan-out of concurrent Lookups racing ApplyGrads against
    ONE shard; returns False at the first torn row."""
    all_ids = np.arange(vocab, dtype=np.int32)
    grad = np.ones((vocab, emb.dim), np.float32)
    req_ids = struct.pack("<i", vocab) + all_ids.tobytes()
    req_grad = req_ids + grad.tobytes()
    ch = emb.channels[0]
    for _ in range(rounds):
        pending = [ch.call_async("Ps", "Lookup", req_ids)
                   for _ in range(lookups)]
        pending += [ch.call_async("Ps", "ApplyGrad", req_grad)
                    for _ in range(applies)]
        for i, call in enumerate(pending):
            rsp = call.join()
            if i < lookups:
                rows = np.frombuffer(rsp, np.float32).reshape(
                    vocab, emb.dim)
                if not _row_deltas_are_whole(rows, init):
                    return False
    return True


@pytest.mark.needs_native
def test_cpu_shard_no_torn_rows_under_read_write_race():
    vocab, dim = 64, 32
    server = PsShardServer(vocab, dim, 0, 1, lr=0.25)
    emb = RemoteEmbedding([server.address], vocab, dim, timeout_ms=30000)
    try:
        init = server.table.copy()
        assert _hammer_one_shard(emb, init, vocab)
        # and the write lock lost no update: 25 rounds x 2 applies of
        # all-ones grads at lr=0.25 move every element by exactly -12.5
        np.testing.assert_allclose(server.table, init - 12.5, atol=1e-4)
    finally:
        emb.close()
        server.close()


def _device_client():
    from brpc_tpu import rpc
    return rpc.DeviceClient(rpc.fake_pjrt_plugin_path())


@pytest.mark.needs_native
def test_device_shard_no_torn_rows_and_racecheck_clean():
    """Lookups racing ApplyGrads on the HBM-resident shard: every served
    row is a whole generation (the handle-generation scheme makes torn
    rows impossible by construction), no update is lost, and RACECHECK
    no longer reports ps.device_shard held across blocking brt_device_*
    calls on the serving path."""
    from brpc_tpu.analysis import race

    vocab, dim = 16, 8
    dev = _device_client()
    race.clear()
    race.set_enabled(True)   # locks created by the server become checked
    try:
        server = DevicePsShardServer(vocab, dim, 0, 1, lr=1.0,
                                     device_client=dev)
        emb = RemoteEmbedding([server.address], vocab, dim,
                              timeout_ms=120000)
        try:
            init = server.table.copy()
            assert _hammer_one_shard(emb, init, vocab, rounds=10,
                                     lookups=4, applies=2)
            final = server.table
            assert _row_deltas_are_whole(final, init)
            # 10 rounds x 2 applies x lr=1.0 x grad=1: nothing lost
            np.testing.assert_allclose(final, init - 20.0, atol=1e-4)
        finally:
            emb.close()
            server.close()
        blocked = [f for f in race.findings()
                   if f.kind == "blocking-call"
                   and "ps.device_shard" in f.locks]
        assert blocked == [], race.report()
    finally:
        race.set_enabled(None)
        race.clear()
        dev.close()
