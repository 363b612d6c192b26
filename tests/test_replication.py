"""Replicated PS shards + the redirecting breaker: fail over, don't
fail fast.

Covers the tentpole end to end, everything driven by deterministic
:class:`brpc_tpu.fault.FaultPlan` rules (``fault.kill_rules`` is the
kill-primary / kill-replica lever):

- replica read parity — after the sync-ack apply barrier, ANY replica
  answers a Lookup byte-identical to the primary (the propagated
  batches replay the primary's exact float ops);
- primary kill → client-driven fenced promotion → ZERO failed lookups
  under sustained load (reads redirect to the surviving replica while
  the breaker isolates the corpse; writes fail over to the promoted
  backup);
- fenced stale-primary rejection — a demoted-but-unaware primary's
  propagation is refused with EFENCED and it demotes itself, so a
  write accepted by a stale primary is never ACKED;
- redirect-vs-reject breaker behavior — the same open breaker re-routes
  in redirect mode and raises ``EBREAKEROPEN`` in legacy mode;
- idempotent framed push replay — the per-writer seq window makes a
  reconnect's replayed frame a no-op instead of a double apply;
- prober revival returns a demoted replica to the read set.
"""

import struct
import time

import numpy as np
import pytest

from brpc_tpu import fault, obs, resilience, rpc
from brpc_tpu.naming import ReplicaSet, parse_shard_tag, shard_tag
from brpc_tpu.ps_remote import (PsShardServer, RemoteEmbedding,
                                _pack_apply_req, _pack_lookup_req,
                                _pack_stream_frame)

pytestmark = pytest.mark.needs_native

VOCAB, DIM = 256, 8


@pytest.fixture(autouse=True)
def _obs_on():
    was = obs.enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(was)
    fault.clear()


def _cluster(nshards=2, nrep=2, **kw):
    """nshards x nrep replicated cluster, replication configured with
    replica 0 as boot primary.  Returns (servers[s][r], replica_sets)."""
    servers = [[PsShardServer(VOCAB, DIM, s, nshards, **kw)
                for _ in range(nrep)] for s in range(nshards)]
    sets = []
    for s in range(nshards):
        rs = ReplicaSet(tuple(sv.address for sv in servers[s]), primary=0)
        sets.append(rs)
        for r, sv in enumerate(servers[s]):
            sv.configure_replication(rs, r)
    return servers, sets


def _close_all(servers):
    for row in servers:
        for sv in row:
            sv.close()


def _retry_policy(attempts=3, attempt_ms=300):
    return resilience.RetryPolicy(
        max_attempts=attempts,
        backoff=resilience.Backoff(base_ms=1, max_ms=10),
        attempt_timeout_ms=attempt_ms)


def _wait_for(cond, what, deadline_s=30.0):
    """Poll ``cond`` until it holds; a wait that runs out fails the test
    by the name of the condition, not by whatever it leaves broken."""
    deadline = time.monotonic() + deadline_s
    while not cond():
        assert time.monotonic() < deadline, \
            f"timed out after {deadline_s:g}s waiting for: {what}"
        time.sleep(0.01)


def _delta_stream_up(primary):
    """The primary's delta stream to a backup is connected and synced:
    from here on a pair's write acks only once the backup holds it."""
    return any(p.stream is not None and not p.need_sync
               for p in primary._replicator._peers)


# ---------------------------------------------------------------------------
# naming: replica tags
# ---------------------------------------------------------------------------

def test_shard_tag_roundtrip():
    assert shard_tag(1, 4) == "1/4"                    # legacy form
    assert shard_tag(1, 4, 2) == "1/4/2"
    assert parse_shard_tag("1/4") == (1, 4, 0)
    assert parse_shard_tag("1/4/2") == (1, 4, 2)
    assert parse_shard_tag("not-a-tag") is None
    assert parse_shard_tag("1/4/x") is None


def test_replica_set_validation():
    with pytest.raises(ValueError):
        ReplicaSet(())
    with pytest.raises(ValueError):
        ReplicaSet(("a",), primary=1)
    rs = ReplicaSet.of("127.0.0.1:1")
    assert rs.addresses == ("127.0.0.1:1",) and rs.primary == 0
    assert ReplicaSet.of(rs) is rs
    assert ReplicaSet.of(["a", "b"]).addresses == ("a", "b")


# ---------------------------------------------------------------------------
# read parity + propagation
# ---------------------------------------------------------------------------

def test_replica_read_parity_after_apply_barrier():
    servers, sets = _cluster(nshards=2, nrep=2)
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=10000)
    try:
        ids = np.arange(64, dtype=np.int32) * 4
        # First write: the backups' delta streams establish (full Sync)
        # — propagation is EVENTUAL until then, so poll for parity.
        emb.apply_gradients(ids, np.ones((64, DIM), np.float32))
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not all(
                np.array_equal(servers[s][0].table, servers[s][1].table)
                for s in range(2)):
            time.sleep(0.01)
        # Steady state: the unary apply IS the barrier (sync
        # replication over the established streams) — every replica
        # answers byte-identical rows the moment the apply returns.
        emb.apply_gradients(ids, np.full((64, DIM), 2.0, np.float32))
        for s in range(2):
            owned = np.arange(s * 128, s * 128 + 128, dtype=np.int32)
            req = bytes(_pack_lookup_req(owned))
            answers = []
            for sv in servers[s]:
                ch = rpc.Channel(sv.address, timeout_ms=5000)
                try:
                    answers.append(ch.call("Ps", "Lookup", req))
                finally:
                    ch.close()
            assert answers[0] == answers[1]
            assert np.array_equal(servers[s][0].table,
                                  servers[s][1].table)
    finally:
        emb.close()
        _close_all(servers)


def test_streamed_push_propagates_and_stays_byte_identical():
    servers, sets = _cluster(nshards=2, nrep=2, stream=True)
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=10000,
                          retry=_retry_policy())
    try:
        ids = np.arange(VOCAB, dtype=np.int32)
        for k in range(4):
            emb.push_gradients(ids, np.full((VOCAB, DIM), float(k + 1),
                                            np.float32))
        emb.flush_gradients()   # applied everywhere; first sync may lag
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not all(
                np.array_equal(servers[s][0].table, servers[s][1].table)
                for s in range(2)):
            time.sleep(0.01)
        for s in range(2):
            assert np.array_equal(servers[s][0].table,
                                  servers[s][1].table)
        assert servers[0][0]._install_gen > 0
        assert servers[0][0]._install_gen == servers[0][1]._install_gen
    finally:
        emb.close()
        _close_all(servers)


def test_backup_rejects_direct_write():
    servers, sets = _cluster(nshards=1, nrep=2)
    try:
        backup = servers[0][1]
        ch = rpc.Channel(backup.address, timeout_ms=5000)
        try:
            with pytest.raises(rpc.RpcError) as ei:
                ch.call("Ps", "ApplyGrad", bytes(_pack_apply_req(
                    np.arange(4, dtype=np.int32),
                    np.ones((4, DIM), np.float32))))
            assert ei.value.code == resilience.ENOTPRIMARY
        finally:
            ch.close()
    finally:
        _close_all(servers)


# ---------------------------------------------------------------------------
# kill-primary: promotion under sustained load
# ---------------------------------------------------------------------------

def test_primary_kill_promotion_zero_failed_lookups():
    servers, sets = _cluster(nshards=2, nrep=2)
    emb = RemoteEmbedding(
        sets, VOCAB, DIM, timeout_ms=10000, retry=_retry_policy(),
        breakers=resilience.BreakerRegistry(
            resilience.BreakerOptions(short_window=4, min_samples=2,
                                      min_isolation_ms=50),
            redirect=True),
        health_check=True, health_interval_ms=20)
    ids = np.arange(128, dtype=np.int32) * 2
    grads = np.ones((128, DIM), np.float32)
    try:
        # warm: streams + replicas (a primary killed before its delta
        # stream came up leaves a backup behind the acked gen, which
        # the client refuses to promote)
        _wait_for(lambda: _delta_stream_up(servers[0][0]),
                  "the primary's delta stream to its backup is up")
        emb.apply_gradients(ids, grads)
        prim = servers[0][0].address
        fault.install(fault.FaultPlan(fault.kill_rules(prim), seed=3))
        # sustained load with the primary dead: every batch must
        # succeed — redirect + failover, never an exception
        t_end = time.monotonic() + 1.0
        reads = writes = 0
        while time.monotonic() < t_end:
            emb.lookup(ids)
            reads += 1
            emb.apply_gradients(ids, grads)
            writes += 1
        assert reads > 10 and writes > 10
        # the backup was promoted with a fencing epoch...
        assert servers[0][1].is_primary
        assert servers[0][1].epoch >= 1
        assert int(obs.counter("ps_client_failovers").get_value()) >= 1
        # ...and reads were REDIRECTED around the corpse, not failed
        assert int(obs.counter("rpc_breaker_redirects").get_value()) > 0
        fault.clear()
        # the prober revives the killed replica back into the read set
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline and emb._isolated(prim):
            time.sleep(0.02)
        assert not emb._isolated(prim)
        # the revived replica is fenced into the backup role by the new
        # primary's propagation; writes keep landing everywhere
        emb.apply_gradients(ids, grads)
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline and servers[0][0].is_primary:
            time.sleep(0.02)
        assert not servers[0][0].is_primary
    finally:
        fault.clear()
        emb.close()
        _close_all(servers)


def test_promotion_preserves_acked_updates_exactly():
    """Zero lost updates: everything the client was ACKED before,
    during, and after a failover is present in the final tables —
    exact-arithmetic sums make a single lost delta detectable."""
    servers, sets = _cluster(nshards=1, nrep=2, lr=1.0)
    emb = RemoteEmbedding(
        sets, VOCAB, DIM, timeout_ms=10000, retry=_retry_policy(),
        breakers=resilience.BreakerRegistry(
            resilience.BreakerOptions(short_window=4, min_samples=2,
                                      min_isolation_ms=50),
            redirect=True))
    ids = np.arange(VOCAB, dtype=np.int32)
    delta = np.full((VOCAB, DIM), 0.5, np.float32)  # exactly representable
    try:
        before = servers[0][0].table.copy()
        acked = 0
        emb.apply_gradients(ids, delta)
        acked += 1
        # let the backup's first full Sync land (propagation is eventual
        # until the delta stream is established) before the kill
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not np.array_equal(
                servers[0][0].table, servers[0][1].table):
            time.sleep(0.01)
        prim = servers[0][0].address
        fault.install(fault.FaultPlan(fault.kill_rules(prim), seed=5))
        for _ in range(3):
            emb.apply_gradients(ids, delta)   # fails over, then lands
            acked += 1
        fault.clear()
        for _ in range(2):
            emb.apply_gradients(ids, delta)
            acked += 1
        # flush barrier on the CURRENT primary, then exact parity
        cur = sets[0].addresses[emb._primary_idx[0]]
        ch = rpc.Channel(cur, timeout_ms=5000)
        try:
            ch.call("Ps", "Flush", b"")
        finally:
            ch.close()
        # replicate the server's per-apply float32 op exactly: each
        # acked batch was ONE in-place subtract of 0.5 (lr=1.0)
        expect = before.copy()
        for _ in range(acked):
            expect[ids] -= np.float32(0.5)
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline and not np.array_equal(
                servers[0][0].table, servers[0][1].table):
            time.sleep(0.02)
        assert np.array_equal(servers[0][1].table, expect)
        assert np.array_equal(servers[0][0].table, servers[0][1].table)
    finally:
        fault.clear()
        emb.close()
        _close_all(servers)


# ---------------------------------------------------------------------------
# fencing
# ---------------------------------------------------------------------------

def test_fenced_stale_primary_rejected_and_demoted():
    servers, sets = _cluster(nshards=1, nrep=2)
    old, new = servers[0][0], servers[0][1]
    try:
        # wait for the (eagerly connected) delta stream: the fence
        # notification rides its reply half
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not any(
                p.stream is not None and not p.need_sync
                for p in old._replicator._peers):
            time.sleep(0.01)
        # Partition the old primary's replication CONTROL plane so the
        # new primary cannot inform it (otherwise the eager propagation
        # demotes it instantly) — the old data stream stays up.
        fault.install(fault.FaultPlan([
            fault.FaultRule(action="error", side="server", service="Ps",
                            method="Sync", endpoint=old.address,
                            error_code=1009),
            fault.FaultRule(action="error", side="server", service="Ps",
                            method="ReplicaApply", endpoint=old.address,
                            error_code=1009)], seed=1))
        # Out-of-band promotion (epoch 1): the old primary doesn't know.
        ch_new = rpc.Channel(new.address, timeout_ms=5000)
        try:
            ch_new.call("Ps", "Promote", struct.pack("<q", 1))
        finally:
            ch_new.close()
        assert new.is_primary and new.epoch == 1
        assert old.is_primary            # stale, unaware
        # A write to the stale primary must NOT be acked: its
        # propagation is fenced (EFENCED) and it demotes itself.
        ch_old = rpc.Channel(old.address, timeout_ms=5000)
        try:
            with pytest.raises(rpc.RpcError) as ei:
                ch_old.call("Ps", "ApplyGrad", bytes(_pack_apply_req(
                    np.arange(4, dtype=np.int32),
                    np.ones((4, DIM), np.float32))))
            assert ei.value.code == resilience.EFENCED
            # demoted: the next write is refused outright
            with pytest.raises(rpc.RpcError) as ei2:
                ch_old.call("Ps", "ApplyGrad", bytes(_pack_apply_req(
                    np.arange(4, dtype=np.int32),
                    np.ones((4, DIM), np.float32))))
            assert ei2.value.code == resilience.ENOTPRIMARY
        finally:
            ch_old.close()
        # demoted by the fence; it adopts the new EPOCH later, from the
        # new primary's first Sync (nothing has shipped yet)
        assert not old.is_primary
        assert int(obs.counter("ps_replica_fenced").get_value()) >= 1
    finally:
        _close_all(servers)


def test_stale_promote_epoch_rejected():
    servers, _ = _cluster(nshards=1, nrep=2)
    try:
        ch = rpc.Channel(servers[0][1].address, timeout_ms=5000)
        try:
            ch.call("Ps", "Promote", struct.pack("<q", 2))
            with pytest.raises(rpc.RpcError) as ei:
                ch.call("Ps", "Promote", struct.pack("<q", 2))
            assert ei.value.code == resilience.EFENCED
        finally:
            ch.close()
    finally:
        _close_all(servers)


# ---------------------------------------------------------------------------
# redirect vs reject
# ---------------------------------------------------------------------------

def test_redirect_vs_reject_breaker_behavior():
    servers, sets = _cluster(nshards=1, nrep=2)
    ids = np.arange(16, dtype=np.int32)
    prim = servers[0][0].address
    try:
        # REDIRECT mode: an open breaker on the primary re-routes reads
        # to the live sibling instead of raising.
        reg = resilience.BreakerRegistry(min_working=1, redirect=True)
        emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=5000,
                              breakers=reg)
        try:
            reg.breaker_for(prim).isolate()
            before = int(
                obs.counter("rpc_breaker_redirects").get_value())
            out = emb.lookup(ids)
            assert out.shape == (16, DIM)
            assert int(obs.counter("rpc_breaker_redirects").get_value()
                       ) > before
        finally:
            emb.close()
        # REJECT mode (redirect=False): same topology, same open
        # breaker — the legacy fail-fast contract.
        reg2 = resilience.BreakerRegistry(min_working=1, redirect=False)
        emb2 = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=5000,
                               breakers=reg2)
        try:
            reg2.breaker_for(prim).isolate()
            with pytest.raises(rpc.RpcError) as ei:
                emb2.lookup(ids)
            assert ei.value.code == resilience.EBREAKEROPEN
        finally:
            emb2.close()
        # every replica isolated: redirect has nowhere to go and rejects
        reg3 = resilience.BreakerRegistry(min_working=0, redirect=True)
        emb3 = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=5000,
                               breakers=reg3)
        try:
            for a in sets[0].addresses:
                reg3.breaker_for(a).isolate()
            with pytest.raises(rpc.RpcError) as ei:
                emb3.lookup(ids)
            assert ei.value.code == resilience.EBREAKEROPEN
        finally:
            emb3.close()
    finally:
        _close_all(servers)


def test_reads_route_by_score_across_replicas():
    """The locality-aware LB half: with a slow primary, the scorer
    shifts read traffic to the fast replica (no breaker involved)."""
    servers, sets = _cluster(nshards=1, nrep=2)
    prim = servers[0][0].address
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=10000)
    ids = np.arange(32, dtype=np.int32)
    try:
        fault.install(fault.FaultPlan([fault.FaultRule(
            action="delay", side="server", service="Ps",
            method="Lookup", endpoint=prim, delay_ms=25)], seed=11))
        for _ in range(12):
            emb.lookup(ids)
        snap = emb.scorer.snapshot()
        backup = servers[0][1].address
        assert snap[backup]["ewma_ms"] < snap[prim]["ewma_ms"]
        # the slow replica's share collapses but it still gets probed
        assert emb.scorer.pick(list(sets[0].addresses)) == backup
    finally:
        fault.clear()
        emb.close()
        _close_all(servers)


# ---------------------------------------------------------------------------
# idempotent framed push (satellite: at-least-once -> exactly-once)
# ---------------------------------------------------------------------------

def test_framed_push_replay_is_idempotent():
    servers, sets = _cluster(nshards=1, nrep=1, stream=True, lr=1.0)
    sv = servers[0][0]
    before = sv.table.copy()
    ids = np.arange(8, dtype=np.int32)
    body = bytes(_pack_apply_req(ids, np.full((8, DIM), 0.5,
                                              np.float32)))
    ch = rpc.Channel(sv.address, timeout_ms=5000)
    try:
        st = ch.stream("Ps", "StreamApply", b"writer-1")
        (high,) = struct.unpack("<q", st.response)
        assert high == 0
        st.write(_pack_stream_frame(1, 0, 0, body))
        st.close()
        assert st.join(timeout_s=5)
        # reconnect: the server answers the seq high-water mark...
        st2 = ch.stream("Ps", "StreamApply", b"writer-1")
        (high2,) = struct.unpack("<q", st2.response)
        assert high2 == 1
        # ...and a replayed frame 1 is DROPPED, not double-applied
        drops0 = int(obs.counter("ps_stream_dedup_drops").get_value())
        st2.write(_pack_stream_frame(1, 0, 0, body))
        st2.write(_pack_stream_frame(2, 0, 0, body))
        st2.close()
        assert st2.join(timeout_s=5)
        assert int(obs.counter("ps_stream_dedup_drops").get_value()) \
            == drops0 + 1
        # exactly two applies of -0.5 (lr=1.0): exact arithmetic,
        # replayed per-apply (two in-place subtracts, like the server)
        expect = before.copy()
        expect[ids] -= np.float32(0.5)
        expect[ids] -= np.float32(0.5)
        assert np.array_equal(sv.table, expect)
    finally:
        ch.close()
        _close_all(servers)


def test_push_gradients_dedups_across_reconnect():
    """The client replays the in-doubt frame after a dropped-setup
    reconnect; the per-writer window means the table ends EXACTLY one
    apply per push, never two, whichever side the break fell on."""
    servers, sets = _cluster(nshards=1, nrep=1, stream=True, lr=1.0)
    sv = servers[0][0]
    before = sv.table.copy()
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=10000,
                          retry=_retry_policy(attempts=4))
    ids = np.arange(16, dtype=np.int32)
    delta = np.full((16, DIM), 0.25, np.float32)
    try:
        emb.push_gradients(ids, delta)     # opens the stream
        emb.flush_gradients()
        # kill the NEXT setup once: the push after flush must reconnect
        fault.install(fault.FaultPlan([fault.FaultRule(
            action="error", side="client", service="Ps",
            method="StreamApply", error_code=1009, max_hits=1)],
            seed=2))
        pushes = 4
        for _ in range(pushes):
            emb.push_gradients(ids, delta)
        emb.flush_gradients()
        expect = before.copy()
        for _ in range(pushes + 1):
            expect[ids] -= np.float32(0.25)
        assert np.array_equal(sv.table, expect)
    finally:
        fault.clear()
        emb.close()
        _close_all(servers)


# ---------------------------------------------------------------------------
# zombie fencing + window inheritance + lossy-promotion refusal
# ---------------------------------------------------------------------------

def test_zombie_primary_push_stream_fenced_no_lost_acks():
    """A primary demoted WHILE carrying a push stream must not keep
    applying frames into a table the new primary's Sync will erase: the
    per-frame fence drops them, the flush barrier detects the applied-
    window shortfall on the live primary, replays the unacked tail onto
    it, and only then acks — exact arithmetic proves every pushed delta
    landed exactly once."""
    servers, sets = _cluster(nshards=1, nrep=2, stream=True, lr=1.0)
    old, new = servers[0][0], servers[0][1]
    before = old.table.copy()
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=10000,
                          retry=_retry_policy(attempts=4))
    ids = np.arange(VOCAB, dtype=np.int32)
    delta = np.full((VOCAB, DIM), 0.5, np.float32)
    try:
        emb.push_gradients(ids, delta)
        emb.flush_gradients()            # frame 1 acked everywhere
        # "everywhere" is the pair's connected-backups barrier: a flush
        # that beats the backup's delta stream coming up acks on the
        # primary alone, and promoting the backup then is a lossy
        # promotion the client rightly refuses (2008).
        _wait_for(lambda: new._install_gen >= old._install_gen >= 1,
                  "the backup holds frame 1 before it is promoted")
        # Out-of-band promotion: the old primary still holds the
        # client's push stream and may not know it is a zombie yet.
        ch = rpc.Channel(new.address, timeout_ms=5000)
        try:
            ch.call("Ps", "Promote", struct.pack("<q", 1))
        finally:
            ch.close()
        emb.push_gradients(ids, delta)   # frames 2..3 race the fence
        emb.push_gradients(ids, delta)
        emb.flush_gradients()            # must fail over + replay
        expect = before.copy()
        for _ in range(3):
            expect[ids] -= np.float32(0.5)
        assert np.array_equal(new.table, expect)
        assert emb._primary_idx[0] == 1
    finally:
        emb.close()
        _close_all(servers)


def test_seq_window_survives_failover_no_double_apply():
    """The per-writer dedup window is replicated WITH the batches it
    covers: after an out-of-band promotion the backup's inherited
    window already spans both unflushed frames, so the client's flush
    barrier confirms without resending — no double apply, no replay."""
    servers, sets = _cluster(nshards=1, nrep=2, stream=True, lr=1.0)
    prim, backup = servers[0][0], servers[0][1]
    before = prim.table.copy()
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=10000,
                          retry=_retry_policy())
    ids = np.arange(16, dtype=np.int32)
    delta = np.full((16, DIM), 0.25, np.float32)
    try:
        emb.push_gradients(ids, delta)
        emb.push_gradients(ids, delta)
        # the wire writer key is scheme- and shard-qualified (seq
        # spaces must not collide inside migrated dedup windows)
        wkey = emb._stream_writer_key(emb._wv, 0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                backup._writer_applied.get(wkey, 0) < 2:
            time.sleep(0.01)
        assert backup._writer_applied.get(wkey, 0) == 2
        assert backup._writer_seqs.get(wkey, 0) == 2
        ch = rpc.Channel(backup.address, timeout_ms=5000)
        try:
            ch.call("Ps", "Promote", struct.pack("<q", 1))
        finally:
            ch.close()
        replays0 = int(obs.counter("ps_push_replays").get_value())
        emb.flush_gradients()
        assert int(obs.counter("ps_push_replays").get_value()) \
            == replays0
        expect = before.copy()
        expect[ids] -= np.float32(0.25)
        expect[ids] -= np.float32(0.25)
        assert np.array_equal(backup.table, expect)
    finally:
        emb.close()
        _close_all(servers)


def test_failover_refuses_gen_behind_promotion():
    """Single-fault loss window closed client-side: writes acked by the
    primary alone (backup partitioned from replication) raise the
    client's acked-gen floor; when the primary then dies, promoting the
    gen-behind backup would lose those acks — the failover REFUSES
    loudly instead of promoting silently."""
    servers = [[PsShardServer(VOCAB, DIM, 0, 1, lr=1.0)
                for _ in range(2)]]
    prim, backup = servers[0][0], servers[0][1]
    rs = ReplicaSet((prim.address, backup.address), primary=0)
    # Partition the backup's replication plane BEFORE the replica set
    # is configured, so the primary acks every write alone.
    fault.install(fault.FaultPlan([
        fault.FaultRule(action="error", side="server", service="Ps",
                        method="Sync", endpoint=backup.address,
                        error_code=1009),
        fault.FaultRule(action="error", side="server", service="Ps",
                        method="ReplicaApply", endpoint=backup.address,
                        error_code=1009)], seed=7))
    prim.configure_replication(rs, 0)
    backup.configure_replication(rs, 1)
    emb = RemoteEmbedding(
        [rs], VOCAB, DIM, timeout_ms=2000, retry=_retry_policy(),
        breakers=resilience.BreakerRegistry(
            resilience.BreakerOptions(short_window=4, min_samples=2,
                                      min_isolation_ms=50),
            redirect=True))
    ids = np.arange(8, dtype=np.int32)
    grads = np.ones((8, DIM), np.float32)
    try:
        for _ in range(3):
            emb.apply_gradients(ids, grads)
        assert emb._gen_seen[0] >= 1
        assert backup._install_gen == 0
        # primary dies with the backup still partitioned: the only
        # candidate is gen-behind
        fault.install(fault.FaultPlan(
            list(fault.kill_rules(prim.address)) + [
                fault.FaultRule(action="error", side="server",
                                service="Ps", method="Sync",
                                endpoint=backup.address,
                                error_code=1009),
                fault.FaultRule(action="error", side="server",
                                service="Ps", method="ReplicaApply",
                                endpoint=backup.address,
                                error_code=1009)], seed=7))
        refusal = None
        for _ in range(40):
            try:
                emb.apply_gradients(ids, grads)
            except rpc.RpcError as e:
                if e.code == resilience.EBREAKEROPEN and \
                        "refusing" in str(e):
                    refusal = e
                    break
        assert refusal is not None
        assert backup._install_gen == 0      # never lossily promoted
        assert not backup.is_primary
    finally:
        fault.clear()
        emb.close()
        _close_all(servers)


# ---------------------------------------------------------------------------
# concurrent retry re-fan (satellite: max(shard), not sum)
# ---------------------------------------------------------------------------

def test_failed_shards_refan_concurrently():
    nshards = 4
    servers = [PsShardServer(VOCAB, DIM, s, nshards)
               for s in range(nshards)]
    addrs = [sv.address for sv in servers]
    emb = RemoteEmbedding(addrs, VOCAB, DIM, timeout_ms=10000,
                          retry=_retry_policy(attempts=3))
    ids = np.arange(128, dtype=np.int32) * 2   # touches all shards
    try:
        # shards 1 and 2: first attempt errors instantly, the RETRY
        # (the first call that reaches the server) is slow — if retries
        # ran sequentially the batch would pay 2 x delay.
        delay_ms = 120
        rules = []
        for a in (addrs[1], addrs[2]):
            rules.append(fault.FaultRule(
                action="error", side="client", endpoint=a,
                error_code=1009, max_hits=1))
            rules.append(fault.FaultRule(
                action="delay", side="server", service="Ps",
                method="Lookup", endpoint=a, delay_ms=delay_ms))
        fault.install(fault.FaultPlan(rules, seed=9))
        retries0 = int(obs.counter("rpc_retries").get_value())
        t0 = time.perf_counter()
        out = emb.lookup(ids)
        elapsed_ms = (time.perf_counter() - t0) * 1000.0
        assert out.shape == (128, DIM)
        assert int(obs.counter("rpc_retries").get_value()) \
            == retries0 + 2
        # concurrent: ~1x delay + overhead; sequential would be >= 2x
        assert elapsed_ms < 2 * delay_ms - 20, elapsed_ms
    finally:
        fault.clear()
        emb.close()
        for sv in servers:
            sv.close()


# ---------------------------------------------------------------------------
# registry-driven replica discovery
# ---------------------------------------------------------------------------

def test_from_registry_builds_replica_sets():
    from brpc_tpu.naming import NamingClient

    servers, sets = _cluster(nshards=2, nrep=2)
    reg_server = rpc.Server()
    reg_server.add_naming_registry()
    port = reg_server.start("127.0.0.1:0")
    try:
        nc = NamingClient(f"127.0.0.1:{port}")
        for s in range(2):
            for r in range(2):
                nc.register("ps", servers[s][r].address,
                            tag=shard_tag(s, 2, r), heartbeat=False)
        emb = RemoteEmbedding.from_registry(
            f"127.0.0.1:{port}", "ps", VOCAB, DIM, timeout_ms=5000)
        try:
            assert emb.n == 2
            for s in range(2):
                assert emb.replica_sets[s].addresses == \
                    sets[s].addresses
                assert emb.replica_sets[s].primary == 0
            assert emb.replicated
            out = emb.lookup(np.arange(32, dtype=np.int32))
            assert out.shape == (32, DIM)
        finally:
            emb.close()
        nc.close()
    finally:
        reg_server.close()
        _close_all(servers)


# ---------------------------------------------------------------------------
# quorum replication (ISSUE 13): majority-ack writes + majority promotion
# ---------------------------------------------------------------------------

def test_quorum_auto_resolution():
    """configure_replication(quorum="auto") resolves to the majority
    for >=3-replica groups and to the legacy connected-only barrier
    for pairs; explicit forms pass through / validate."""
    servers, _ = _cluster(nshards=1, nrep=3, lr=1.0)
    try:
        assert all(sv._quorum == 2 for sv in servers[0])
    finally:
        _close_all(servers)
    servers, _ = _cluster(nshards=1, nrep=2, lr=1.0)
    try:
        assert all(sv._quorum is None for sv in servers[0])
    finally:
        _close_all(servers)
    sv = PsShardServer(VOCAB, DIM, 0, 1, lr=1.0)
    try:
        rs = ReplicaSet((sv.address, "127.0.0.1:9", "127.0.0.1:10"))
        with pytest.raises(ValueError):
            sv.configure_replication(rs, 0, quorum=7)
        sv.configure_replication(rs, 0, quorum="majority")
        assert sv._quorum == 2
    finally:
        sv.close()


def test_quorum_bootstrap_kill_loses_nothing():
    """THE bootstrap loss window: with 3 replicas and a majority
    quorum, the very first acked write already sits on >=2 replicas —
    killing the primary right after it can no longer lose it (the
    legacy connected-only barrier acked on the primary alone until the
    backups' first Sync landed)."""
    servers, sets = _cluster(nshards=1, nrep=3, lr=1.0)
    flat = [sv for row in servers for sv in row]
    prim = servers[0][0]
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=10000,
                          retry=_retry_policy(attempts=4))
    ids = np.arange(16, dtype=np.int32)
    before = prim.table.copy()
    try:
        # the FIRST write: the quorum barrier blocks until a backup
        # really holds it (its connect Sync covers the gen)
        emb.apply_gradients(ids, np.full((16, DIM), 0.5, np.float32))
        fault.install(fault.FaultPlan(
            fault.kill_rules(prim.address), seed=13))
        # failover must find the acked write on a surviving replica
        emb.apply_gradients(ids, np.full((16, DIM), 0.25, np.float32))
        expect = before.copy()
        for d in (0.5, 0.25):
            expect[ids] -= np.float32(d)
        new_prim = next(sv for sv in flat
                        if sv is not prim and sv.is_primary)
        assert np.array_equal(new_prim.table, expect)
        assert np.array_equal(emb.lookup(ids), expect[ids])
    finally:
        fault.clear()
        emb.close()
        _close_all(servers)


def test_quorum_unreachable_fails_loudly_never_acks():
    """With every backup black-holed a quorum write must FAIL (loud
    unavailability) — and the failed write must not have mutated the
    acked state observable after the backups return."""
    servers, sets = _cluster(nshards=1, nrep=3, lr=1.0)
    prim = servers[0][0]
    prim.repl_ack_timeout_s = 0.4
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=3000,
                          retry=_retry_policy(attempts=2,
                                              attempt_ms=1500))
    ids = np.arange(8, dtype=np.int32)
    try:
        emb.apply_gradients(ids, np.full((8, DIM), 0.5, np.float32))
        fault.install(fault.FaultPlan(
            fault.kill_rules(servers[0][1].address)
            + fault.kill_rules(servers[0][2].address), seed=17))
        # sever the ESTABLISHED propagation streams too (fault rules
        # only gate call paths): acks stop flowing and reconnects die
        rpc.debug_fail_connections(servers[0][1].address)
        rpc.debug_fail_connections(servers[0][2].address)
        with pytest.raises(rpc.RpcError):
            emb.apply_gradients(ids, np.full((8, DIM), 0.25,
                                             np.float32))
    finally:
        fault.clear()
        emb.close()
        _close_all(servers)


def test_promotion_requires_majority_sweep():
    """For a 3-replica group, losing TWO replicas leaves a minority —
    promotion must refuse loudly (a sub-majority sweep cannot prove it
    intersects the write quorum); with exactly a majority reachable it
    proceeds."""
    servers, sets = _cluster(nshards=1, nrep=3, lr=1.0)
    emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=3000,
                          retry=_retry_policy(attempts=2,
                                              attempt_ms=400))
    ids = np.arange(8, dtype=np.int32)
    try:
        emb.apply_gradients(ids, np.full((8, DIM), 0.5, np.float32))
        # kill primary AND one backup: 1 of 3 reachable < majority 2
        fault.install(fault.FaultPlan(
            fault.kill_rules(servers[0][0].address)
            + fault.kill_rules(servers[0][1].address), seed=19))
        with pytest.raises(rpc.RpcError):
            emb.apply_gradients(ids, np.full((8, DIM), 0.25,
                                             np.float32))
        # the surviving minority was not promoted behind our back
        assert not servers[0][2].is_primary
        # majority restored (primary still dead): promotion proceeds
        fault.install(fault.FaultPlan(
            fault.kill_rules(servers[0][0].address), seed=19))
        emb.apply_gradients(ids, np.full((8, DIM), 0.25, np.float32))
        assert servers[0][1].is_primary or servers[0][2].is_primary
    finally:
        fault.clear()
        emb.close()
        _close_all(servers)


def test_staggered_bringup_no_self_demotion():
    """THE bring-up race the churn bench found: with real delays
    between the replicas' configure_replication calls, the primary's
    eager connect used to hit a NOT-YET-CONFIGURED backup, read its
    default primary flag as a stale-primary EFENCED, demote itself,
    and stop(join=False) closed its channel set under a sibling
    worker's in-flight Sync — a native use-after-free.  Now an
    unconfigured backup rejects retriably, the primary stays primary,
    and teardown always joins workers before closing channels."""
    for _ in range(3):   # the race was timing-dependent: iterate
        servers = [[PsShardServer(VOCAB, DIM, s, 2, lr=1.0)
                    for _ in range(3)] for s in range(2)]
        try:
            sets = []
            for s in range(2):
                rs = ReplicaSet(tuple(sv.address for sv in servers[s]),
                                primary=0)
                sets.append(rs)
                for r, sv in enumerate(servers[s]):
                    sv.configure_replication(rs, r)
                    time.sleep(0.003)   # the staggered bring-up
            time.sleep(0.3)             # eager connects settle
            assert all(servers[s][0].is_primary for s in range(2))
            assert not any(sv.is_primary
                           for s in range(2) for sv in servers[s][1:])
            emb = RemoteEmbedding(sets, VOCAB, DIM, timeout_ms=10000,
                                  retry=_retry_policy(attempts=4))
            try:
                ids = np.arange(8, dtype=np.int32)
                before = servers[0][0].table.copy()
                emb.apply_gradients(ids, np.full((8, DIM), 0.5,
                                                 np.float32))
                expect = before.copy()
                expect[ids] -= np.float32(0.5)
                assert np.array_equal(servers[0][0].table, expect)
            finally:
                emb.close()
        finally:
            _close_all(servers)
