"""Durable fabric: per-shard checkpoint/restore + snapshot-hydrated
provisioning (ISSUE 16).

The store half runs everywhere (tier-1): on-disk frame parsers
(``ckpt_snap`` / ``ckpt_delta`` / ``ckpt_marker``) reject torn,
truncated and bit-flipped files with a clean ``WireError``; the
:class:`CheckpointStore` write/restore cycle is proven with an
EXACT-arithmetic ledger (manual numpy replay of the teed bodies), and
every crash-mid-checkpoint shape — mid-snapshot, mid-append,
mid-compaction — lands restore on the last complete record, never a
byte more or less.

The server half (native-gated) closes the loop end to end: the live
apply path tees into the store, a cold restart replays to the exact
acked generation through the server's own arithmetic, and new
replicas / split destinations hydrate from the snapshot + delta tail
instead of a wholesale Sync off the live source.
"""

import os
import struct
import threading
import time

import numpy as np
import pytest

from brpc_tpu import durable, fault, obs, rpc, wire
from brpc_tpu.durable import (CheckpointStore, _pack_delta, _pack_marker,
                              _pack_snapshot, _unpack_delta,
                              _unpack_marker, _unpack_snapshot)
from brpc_tpu.naming import ReplicaSet
from brpc_tpu.ps_remote import (PsShardServer, RemoteEmbedding,
                                _pack_apply_req, _pack_windows,
                                _unpack_apply)

ROWS, DIM = 16, 4


@pytest.fixture(autouse=True)
def _obs_on():
    was = obs.enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(was)
    fault.clear()


def _table(seed=0):
    rng = np.random.default_rng(seed)
    # exactly-representable values so replay comparisons are bit-exact
    return (rng.integers(-64, 64, (ROWS, DIM)).astype(np.float32)
            * np.float32(0.25))


def _body(ids, step, windows=None):
    """One verbatim replica_apply_body: dedup windows ++ apply_req with
    an exactly-representable per-step gradient (2**-step)."""
    ids = np.asarray(ids, np.int32)
    grads = np.full((ids.size, DIM), 2.0 ** -step, np.float32)
    return (_pack_windows(windows or {})
            + bytes(_pack_apply_req(ids, grads))), ids, grads


def _store_with_tail(root, nsteps=5, seed=0, **kw):
    """Base at gen 0 plus ``nsteps`` teed deltas; returns the store and
    the EXACT expected table after replaying every delta."""
    st = CheckpointStore(str(root), **kw)
    base = _table(seed)
    st.save_snapshot(7, 0, base, {"w": 3})
    expect = base.copy()
    for g in range(1, nsteps + 1):
        body, ids, grads = _body([g % ROWS, (g + 3) % ROWS], g,
                                 windows={"w": 3 + g})
        assert st.append_delta(g, body)
        np.subtract.at(expect, ids, grads)
    return st, base, expect


def _replay(point):
    """Manual replay of a RestorePoint through the same parse +
    arithmetic the server uses (lr folded at 1.0)."""
    out = point.table.copy()
    for _gen, body in point.deltas:
        _windows, off = durable._unpack_windows(body)
        ids, grads = _unpack_apply(memoryview(body)[off:], 0, ROWS, DIM)
        if ids.size:
            np.subtract.at(out, ids, grads)
    return out


# ---------------------------------------------------------------------------
# on-disk frame parsers: roundtrip + clean rejection
# ---------------------------------------------------------------------------

def test_snapshot_roundtrip_exact():
    tbl = _table(3)
    payload = _pack_snapshot(9, 42, tbl, {"writer-a": 5, "writer-b": 11})
    epoch, gen, out, windows, seeded = _unpack_snapshot(payload)
    assert (epoch, gen) == (9, 42)
    assert np.array_equal(out, tbl)
    assert windows == {"writer-a": 5, "writer-b": 11}
    assert seeded is False
    payload = _pack_snapshot(9, 0, tbl, {}, seeded=True)
    assert _unpack_snapshot(payload)[4] is True


def test_snapshot_rejects_truncation_everywhere():
    payload = _pack_snapshot(1, 2, _table(), {"w": 1})
    for cut in (0, 10, durable._SNAP_HDR - 1, len(payload) - 1):
        with pytest.raises(wire.WireError):
            _unpack_snapshot(payload[:cut])


def test_snapshot_rejects_bitflip_and_junk():
    payload = bytearray(_pack_snapshot(1, 2, _table(), {"w": 1}))
    flipped = bytearray(payload)
    flipped[durable._SNAP_HDR + 12] ^= 0x40      # body bit flip
    with pytest.raises(wire.WireError):
        _unpack_snapshot(bytes(flipped))
    with pytest.raises(wire.WireError):
        _unpack_snapshot(bytes(payload) + b"junk")   # crc covers length
    bad_magic = struct.pack("<i", 0) + bytes(payload[4:])
    with pytest.raises(wire.WireError):
        _unpack_snapshot(bad_magic)
    bad_version = bytes(payload[:4]) + struct.pack("<i", 99) \
        + bytes(payload[8:])
    with pytest.raises(wire.WireError):
        _unpack_snapshot(bad_version)


def test_delta_roundtrip_and_rejects():
    body, _, _ = _body([1, 2], 1, windows={"w": 7})
    rec = _pack_delta(5, body)
    gen, out, end = _unpack_delta(rec)
    assert (gen, out, end) == (5, body, len(rec))
    # two records back to back parse by offset
    rec2 = rec + _pack_delta(6, body)
    g1, _, off = _unpack_delta(rec2)
    g2, _, end2 = _unpack_delta(rec2, off)
    assert (g1, g2, end2) == (5, 6, len(rec2))
    for cut in (0, 3, durable._DELTA_HDR - 1, len(rec) - 1):
        with pytest.raises(wire.WireError):
            _unpack_delta(rec[:cut])
    flipped = bytearray(rec)
    flipped[durable._DELTA_HDR + 2] ^= 0x01
    with pytest.raises(wire.WireError):
        _unpack_delta(bytes(flipped))
    with pytest.raises(wire.WireError):
        _unpack_delta(struct.pack("<i", 0x7777) + rec[4:])


def test_marker_roundtrip_and_rejects():
    rec = _pack_marker(123)
    assert _unpack_marker(rec) == 123
    for cut in (0, 7, len(rec) - 1):
        with pytest.raises(wire.WireError):
            _unpack_marker(rec[:cut])
    with pytest.raises(wire.WireError):
        _unpack_marker(struct.pack("<i", 1) + rec[4:])
    with pytest.raises(wire.WireError):
        _unpack_marker(rec[:4] + struct.pack("<i", 99) + rec[8:])


# ---------------------------------------------------------------------------
# store cycle: exact ledger, chain discipline, tail_since
# ---------------------------------------------------------------------------

def test_store_cycle_exact_ledger(tmp_path):
    st, _base, expect = _store_with_tail(tmp_path, nsteps=5)
    st.close()
    st2 = CheckpointStore(str(tmp_path))
    point = st2.restore()
    assert point is not None
    assert (point.epoch, point.base_gen, point.gen) == (7, 0, 5)
    assert point.windows == {"w": 3}
    assert len(point.deltas) == 5
    assert np.array_equal(_replay(point), expect)   # bit-exact ledger
    st2.close()


def test_append_requires_chain_and_fresh_base(tmp_path):
    st = CheckpointStore(str(tmp_path))
    body, _, _ = _body([1], 1)
    assert not st.append_delta(1, body)             # no base yet
    st.save_snapshot(0, 0, _table(), {})
    assert not st.append_delta(2, body)             # gap: 0 -> 2
    assert st.append_delta(1, body)
    assert not st.append_delta(3, body)             # gap: 1 -> 3
    assert st.append_delta(2, body)
    st.restore()
    # a recovered tail is never appended to in place
    assert not st.append_delta(3, body)
    st.save_snapshot(0, 2, _table(), {})
    assert st.append_delta(3, body)
    st.close()


def test_tail_since_semantics(tmp_path):
    st, _, _ = _store_with_tail(tmp_path, nsteps=3)
    assert [g for g, _ in st.tail_since(0)] == [1, 2, 3]
    assert [g for g, _ in st.tail_since(2)] == [3]
    assert st.tail_since(3) == []
    assert st.tail_since(-1) is None                # predates the base
    st.close()


def test_counters_advance(tmp_path):
    snaps0 = int(obs.counter("ps_ckpt_snapshots").get_value())
    deltas0 = int(obs.counter("ps_ckpt_deltas").get_value())
    restores0 = int(obs.counter("ps_ckpt_restores").get_value())
    st, _, _ = _store_with_tail(tmp_path, nsteps=4)
    st.restore()
    st.close()
    assert int(obs.counter("ps_ckpt_snapshots").get_value()) == snaps0 + 1
    assert int(obs.counter("ps_ckpt_deltas").get_value()) == deltas0 + 4
    assert int(obs.counter("ps_ckpt_restores").get_value()) == restores0 + 1


def test_compaction_folds_tail_and_retires(tmp_path):
    st, _, expect = _store_with_tail(tmp_path, nsteps=3, keep_bases=1)
    st.save_snapshot(7, 3, expect, {"w": 6})        # compact at gen 3
    names = sorted(os.listdir(tmp_path))
    assert "base-%016d.snap" % 0 not in names       # old base retired
    assert "base-%016d.snap" % 3 in names
    assert "delta-%016d.log" % 0 not in names       # old segment retired
    point = st.restore()
    assert (point.base_gen, point.gen) == (3, 3)
    assert np.array_equal(point.table, expect)
    st.close()


def test_should_compact_threshold(tmp_path):
    st = CheckpointStore(str(tmp_path), compact_bytes=64)
    st.save_snapshot(0, 0, _table(), {})
    assert not st.should_compact()
    body, _, _ = _body(list(range(8)), 1)
    st.append_delta(1, body)
    assert st.should_compact()
    st.save_snapshot(0, 1, _table(), {})
    assert not st.should_compact()                  # tail folded
    st.close()


# ---------------------------------------------------------------------------
# crash-mid-checkpoint: every torn shape restores the last complete record
# ---------------------------------------------------------------------------

def _latest_segment(root):
    segs = sorted(n for n in os.listdir(root)
                  if n.startswith("delta-") and n.endswith(".log"))
    return os.path.join(root, segs[-1])


def test_crash_mid_append_torn_tail(tmp_path):
    st, base, _ = _store_with_tail(tmp_path, nsteps=5)
    st.close()
    seg = _latest_segment(tmp_path)
    with open(seg, "r+b") as f:                     # kill mid-write of rec 5
        f.truncate(os.path.getsize(seg) - 7)
    point = CheckpointStore(str(tmp_path)).restore()
    assert point.gen == 4                           # last COMPLETE record
    expect = base.copy()
    for g in range(1, 5):
        _, ids, grads = _body([g % ROWS, (g + 3) % ROWS], g)
        np.subtract.at(expect, ids, grads)
    assert np.array_equal(_replay(point), expect)


def test_crash_mid_snapshot_falls_back_to_prior_base(tmp_path):
    st, _base, expect = _store_with_tail(tmp_path, nsteps=3)
    st.save_snapshot(7, 3, expect, {"w": 6})        # compaction: base 3
    st.close()
    # the new base is torn mid-write AND a stray .tmp is left behind
    newest = os.path.join(tmp_path, "base-%016d.snap" % 3)
    with open(newest, "r+b") as f:
        f.truncate(os.path.getsize(newest) // 2)
    with open(newest + ".tmp", "wb") as f:
        f.write(b"\x00" * 10)
    point = CheckpointStore(str(tmp_path)).restore()
    # falls back to base 0 and replays its retained segment chain 1..3
    assert (point.base_gen, point.gen) == (0, 3)
    assert np.array_equal(_replay(point), expect)


def test_crash_mid_compaction_stale_marker_tolerated(tmp_path):
    st, _base, expect = _store_with_tail(tmp_path, nsteps=3)
    st.save_snapshot(7, 3, expect, {"w": 6})
    st.close()
    # crash between writing the base and the marker: marker still names
    # the OLD base — restore trusts the scan, not the marker
    with open(os.path.join(tmp_path, "compact.marker"), "wb") as f:
        f.write(_pack_marker(0))
    point = CheckpointStore(str(tmp_path)).restore()
    assert (point.base_gen, point.gen) == (3, 3)
    assert np.array_equal(point.table, expect)


def test_bitflip_mid_segment_stops_chain_cleanly(tmp_path):
    st, base, _ = _store_with_tail(tmp_path, nsteps=4)
    st.close()
    seg = _latest_segment(tmp_path)
    rec_len = durable._DELTA_HDR + len(_body([0, 1], 1,
                                             windows={"w": 4})[0])
    with open(seg, "r+b") as f:                     # flip a byte in rec 2
        f.seek(rec_len + durable._DELTA_HDR + 5)
        b = f.read(1)
        f.seek(rec_len + durable._DELTA_HDR + 5)
        f.write(bytes([b[0] ^ 0x10]))
    point = CheckpointStore(str(tmp_path)).restore()
    assert point.gen == 1                           # nothing past the flip
    expect = base.copy()
    _, ids, grads = _body([1 % ROWS, 4 % ROWS], 1)
    np.subtract.at(expect, ids, grads)
    assert np.array_equal(_replay(point), expect)


def test_restore_none_without_usable_base(tmp_path):
    st = CheckpointStore(str(tmp_path))
    assert st.restore() is None
    with open(os.path.join(tmp_path, "base-%016d.snap" % 5), "wb") as f:
        f.write(b"garbage")
    with open(os.path.join(tmp_path, "README"), "wb") as f:
        f.write(b"not a checkpoint file")
    assert st.restore() is None
    assert st.load_base() is None
    st.close()


def test_load_base_skips_corrupt_and_lying_files(tmp_path):
    st, base, _ = _store_with_tail(tmp_path, nsteps=1)
    st.close()
    # a newer base whose content says a DIFFERENT gen than its name
    lying = _pack_snapshot(7, 8, _table(1), {})
    with open(os.path.join(tmp_path, "base-%016d.snap" % 9), "wb") as f:
        f.write(lying)
    epoch, gen, tbl, _, _seeded = CheckpointStore(str(tmp_path)).load_base()
    assert (epoch, gen) == (7, 0)
    assert np.array_equal(tbl, base)


# ---------------------------------------------------------------------------
# server integration (native-gated): tee, cold restart, hydration
# ---------------------------------------------------------------------------

VOCAB = 64


def _apply(addr, ids, step, timeout_ms=5000):
    ids = np.asarray(ids, np.int32)
    grads = np.full((ids.size, DIM), 2.0 ** -step, np.float32)
    ch = rpc.Channel(addr, timeout_ms=timeout_ms)
    try:
        ch.call("Ps", "ApplyGrad", bytes(_pack_apply_req(ids, grads)))
    finally:
        ch.close()
    return ids, grads


def _wait(pred, deadline_s=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        if pred():
            return True
        time.sleep(0.02)
    return False


def _fleet(root, nshards, nrep):
    """``nshards`` x ``nrep`` servers on one seed, each attached to a
    store of its own under ``root`` before replication is configured,
    replica 0 the declared primary.  ``points[s][r]`` is what the
    attach recovered (None from an empty store)."""
    servers, stores, sets, points = [], [], [], []
    for s in range(nshards):
        row = [PsShardServer(VOCAB, DIM, s, nshards, lr=1.0, seed=3)
               for _ in range(nrep)]
        srow = [CheckpointStore(os.path.join(str(root), f"s{s}r{r}"))
                for r in range(nrep)]
        points.append([sv.attach_checkpoint(st)
                       for sv, st in zip(row, srow)])
        rs = ReplicaSet(tuple(sv.address for sv in row), primary=0)
        if nrep > 1:
            for r, sv in enumerate(row):
                sv.configure_replication(rs, r)
        servers.append(row)
        stores.append(srow)
        sets.append(rs)
    return servers, stores, sets, points


def _close_fleet(servers, stores):
    for row in servers:
        for sv in row:
            sv.close()
    for srow in stores:
        for st in srow:
            st.close()


@pytest.mark.needs_native
@pytest.mark.parametrize("nshards,nrep,midload", [
    (1, 1, False),      # one server, closed at rest
    (2, 2, True),       # every server of a replicated fleet, mid-load
])
def test_server_tee_and_cold_restart_exact(tmp_path, nshards, nrep,
                                           midload):
    """Every server dies; fresh servers on the same stores replay base
    + deltas to the exact acked ledger.  Mid-load the one write in
    flight at the kill was never acknowledged, so a shard may hold it
    or not: that, and nothing else, is allowed either way."""
    grad = np.float32(2.0 ** -6)
    batch, enough = 8, 12
    servers, stores, sets, points = _fleet(tmp_path, nshards, nrep)
    assert points == [[None] * nrep] * nshards      # nothing to recover
    init = np.concatenate([row[0].table.copy() for row in servers])
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=5000)
    counts = np.zeros(VOCAB, np.int64)              # acked occurrences
    acked = [0]
    unacked = [None]                                # the batch in flight
    stop = threading.Event()

    def writer():
        rng = np.random.default_rng(4)
        while not stop.is_set():
            ids = rng.integers(0, VOCAB, batch).astype(np.int32)
            try:
                emb.apply_gradients(ids, np.full((batch, DIM), grad))
            except rpc.RpcError:
                unacked[0] = ids
                return
            np.add.at(counts, ids, 1)
            acked[0] += 1
            if not midload and acked[0] == enough:
                return

    wt = threading.Thread(target=writer, daemon=True)
    try:
        wt.start()
        if midload:
            assert _wait(lambda: acked[0] >= enough or not wt.is_alive())
            # The kill: every endpoint dead at one instant, so that no
            # client failover can promote a backup inside the kill and
            # take an acked write the declared primary's store lacks;
            # then every server closes under the running writer.
            fault.install(fault.FaultPlan(fault.kill_rules(
                *[sv.address for row in servers for sv in row]), seed=5))
        else:
            wt.join(timeout=30)
        gens = [row[0]._install_gen for row in servers]
    finally:
        _close_fleet(servers, stores)
        stop.set()
        wt.join(timeout=30)
        emb.close()
        fault.clear()
    assert not wt.is_alive() and acked[0] >= enough
    assert midload or unacked[0] is None

    # cold restart: fresh process state, same store roots
    servers2, stores2, sets2, points = _fleet(tmp_path, nshards, nrep)
    emb2 = RemoteEmbedding(sets2, VOCAB, DIM, timeout_ms=5000)
    try:
        expect = init.copy()
        for step in range(int(counts.max())):
            expect[counts > step] -= grad
        rows_per = VOCAB // nshards
        for s, row in enumerate(servers2):
            prim, point, lo = row[0], points[s][0], s * rows_per
            assert point is not None and prim._install_gen == point.gen
            allowed = [expect[lo:lo + rows_per]]
            if unacked[0] is None:
                assert point.gen == gens[s]
            else:
                mine = unacked[0][(unacked[0] >= lo)
                                  & (unacked[0] < lo + rows_per)] - lo
                landed = allowed[0].copy()
                np.subtract.at(landed, mine, grad)
                allowed.append(landed)
            assert any(np.array_equal(prim.table, t) for t in allowed), \
                f"shard {s}: restored table is neither the acked " \
                f"ledger nor the ledger plus the unacked batch"
        # the tee re-armed on a fresh base: the restored fleet keeps
        # taking acked writes, keeps checkpointing them, and every
        # backup converges on its primary
        before = [row[0].table.copy() for row in servers2]
        ids = np.arange(VOCAB, dtype=np.int32)
        emb2.apply_gradients(ids, np.full((VOCAB, DIM), grad))
        for s, row in enumerate(servers2):
            assert np.array_equal(row[0].table, before[s] - grad)
            assert stores2[s][0].last_gen == row[0]._install_gen
            for backup in row[1:]:
                assert _wait(lambda: np.array_equal(backup.table,
                                                    row[0].table))
    finally:
        emb2.close()
        _close_fleet(servers2, stores2)


@pytest.mark.needs_native
def test_server_cold_restart_torn_tail_lands_short(tmp_path):
    from brpc_tpu.ps_remote import PsShardServer
    sv = PsShardServer(VOCAB, DIM, 0, 1, lr=1.0, seed=3)
    store = CheckpointStore(str(tmp_path))
    try:
        sv.attach_checkpoint(store)
        for g in range(1, 5):
            _apply(sv.address, [g, g + 1], g)
        before_last = sv.table.copy()               # state at gen 4
        _apply(sv.address, [9, 11], 5)
    finally:
        sv.close()
        store.close()
    seg = _latest_segment(tmp_path)
    with open(seg, "r+b") as f:
        f.truncate(os.path.getsize(seg) - 3)        # tear record 5
    sv2 = PsShardServer(VOCAB, DIM, 0, 1, lr=1.0, seed=3)
    store2 = CheckpointStore(str(tmp_path))
    try:
        point = sv2.attach_checkpoint(store2)
        assert point.gen == 4                       # last complete record
        assert np.array_equal(sv2.table, before_last)
    finally:
        sv2.close()
        store2.close()


@pytest.mark.needs_native
def test_hydrate_replica_ships_tail_not_wholesale(tmp_path):
    from brpc_tpu.naming import ReplicaSet
    from brpc_tpu.ps_remote import PsShardServer
    a = PsShardServer(VOCAB, DIM, 0, 1, lr=1.0, seed=5)
    b = PsShardServer(VOCAB, DIM, 0, 1, lr=1.0, seed=5)
    store = CheckpointStore(str(tmp_path))
    try:
        a.attach_checkpoint(store)
        for g in range(1, 5):
            _apply(a.address, [g, g + 2], g)
        # re-base so the snapshot sits at gen 4 with an empty tail...
        a.attach_checkpoint(store, recover=False)
        for g in range(5, 8):                       # ...then grow gen 5..7
            _apply(a.address, [g, g + 2], g)
        rs = ReplicaSet((a.address, b.address), primary=0)
        b.configure_replication(rs, 1)
        seeded = durable.hydrate_replica(store, b.address)
        assert seeded == 4                          # the base generation
        hyd0 = int(obs.counter("ps_replica_hydrates").get_value())
        syncs0 = int(obs.counter("ps_replica_syncs").get_value())
        a.configure_replication(rs, 0)
        assert _wait(lambda: b._install_gen == a._install_gen)
        a.flush_replication()
        assert np.array_equal(a.table, b.table)
        assert int(obs.counter(
            "ps_replica_hydrates").get_value()) == hyd0 + 1
        # the live primary never shipped a wholesale table image
        assert int(obs.counter(
            "ps_replica_syncs").get_value()) == syncs0
        # writes keep replicating through the hydrated stream
        ids, grads = _apply(a.address, [1, 3], 9)
        a.flush_replication()
        assert np.array_equal(a.table, b.table)
    finally:
        a.close()
        b.close()
        store.close()


@pytest.mark.needs_native
def test_hydrate_destination_split_ships_tail(tmp_path):
    from brpc_tpu.naming import PartitionScheme, ReplicaSet
    from brpc_tpu.ps_remote import PsShardServer, RemoteEmbedding
    from brpc_tpu.reshard import MigrationDriver
    from brpc_tpu import resilience
    src = PsShardServer(VOCAB, DIM, 0, 1, lr=1.0, seed=8, stream=True)
    dst = [PsShardServer(VOCAB, DIM, s, 2, lr=1.0, seed=8, stream=True,
                         importing=True, scheme_version=1)
           for s in range(2)]
    store = CheckpointStore(str(tmp_path))
    sc0 = PartitionScheme(0, (ReplicaSet.of(src.address),))
    sc1 = PartitionScheme(1, tuple(ReplicaSet.of(sv.address)
                                   for sv in dst))
    emb = RemoteEmbedding([sc0], VOCAB, DIM, timeout_ms=10000,
                          retry=resilience.RetryPolicy(
                              max_attempts=4,
                              backoff=resilience.Backoff(base_ms=1,
                                                         max_ms=10),
                              attempt_timeout_ms=500))
    drv = MigrationDriver(sc0, sc1, VOCAB)
    ids = np.arange(VOCAB, dtype=np.int32)
    before = src.table.copy()
    try:
        src.attach_checkpoint(store)
        emb.apply_gradients(ids, np.full((VOCAB, DIM), 0.5, np.float32))
        src.attach_checkpoint(store, recover=False)   # base at gen 1
        emb.apply_gradients(ids, np.full((VOCAB, DIM), 0.25,
                                         np.float32))
        half = VOCAB // 2
        for s, sv in enumerate(dst):
            g = durable.hydrate_destination(
                store, sv.address, 1, src.address, 0, s * half, half)
            assert g == 1
        hyd0 = int(obs.counter("ps_migrate_hydrates").get_value())
        syncs0 = int(obs.counter("ps_migrate_syncs_out").get_value())
        drv.start()
        emb.apply_gradients(ids, np.full((VOCAB, DIM), 0.125,
                                         np.float32))
        drv.wait_caught_up(deadline_s=20)
        drv.cutover()
        emb.set_schemes([sc0.with_(state="draining", weight=0.0), sc1])
        emb.apply_gradients(ids, np.full((VOCAB, DIM), 0.0625,
                                         np.float32))
        expect = before.copy()
        for d in (0.5, 0.25, 0.125, 0.0625):
            expect[ids] -= np.float32(d)
        assert np.array_equal(
            np.concatenate([sv.table for sv in dst]), expect)
        assert int(obs.counter(
            "ps_migrate_hydrates").get_value()) == hyd0 + 2
        # neither destination needed a wholesale range sync
        assert int(obs.counter(
            "ps_migrate_syncs_out").get_value()) == syncs0
    finally:
        drv.close()
        emb.close()
        src.close()
        for sv in dst:
            sv.close()
        store.close()


def test_append_delta_epoch_mismatch_rebases(tmp_path):
    """An epoch bump WITHOUT a wholesale install (a promotion: the
    generation chain continues, only the epoch moves) must re-base —
    restoring the old base would resurrect the stale epoch and
    un-fence retired writers.  ``append_delta(..., epoch=)`` refuses
    the mismatched record; the caller snapshots under the new epoch
    and the chain resumes."""
    st = CheckpointStore(str(tmp_path))
    base = _table(3)
    st.save_snapshot(7, 0, base, {})
    body1, _, _ = _body([1], 1)
    assert st.append_delta(1, body1, epoch=7)
    body2, _, _ = _body([2], 2)
    # promotion bumped the epoch; gen 2 IS the next chain link, yet
    # the record must be refused — the base was written under epoch 7
    assert not st.append_delta(2, body2, epoch=8)
    # the caller's response: fold the current table into a new base
    st.save_snapshot(8, 2, base, {})
    body3, _, _ = _body([3], 3)
    assert st.append_delta(3, body3, epoch=8)
    # epoch-blind callers (legacy) keep appending on the chain
    body4, _, _ = _body([4], 4)
    assert st.append_delta(4, body4)
    point = st.restore()
    assert point is not None
    assert point.epoch == 8 and point.base_gen == 2 and point.gen == 4
    st.close()
