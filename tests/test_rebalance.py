"""Self-driving elasticity: the rebalancer policy (fake clock, no
servers) and the daemon end to end (ISSUE 13).

The policy half is the tier-1 bounded coverage the CI satellite asks
for: split/merge/failback decisions, sustain windows, the hysteresis
band, min-interval cooldown and flap-freedom are proven against an
injected clock — no live servers, no wall time.  The daemon half
(native-gated) drives a real failback and a real policy-decided split
through ``Rebalancer.step()``.
"""

import json
import struct
import time

import numpy as np
import pytest

from brpc_tpu import fault, obs
from brpc_tpu.rebalance import (Decision, RebalanceOptions,
                                RebalancePolicy, Rebalancer)


@pytest.fixture(autouse=True)
def _obs_on():
    was = obs.enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(was)
    fault.clear()


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _policy(**kw):
    clock = FakeClock()
    opts = RebalanceOptions(split_qps=100.0, merge_qps=10.0,
                            sustain_s=1.0, min_interval_s=5.0,
                            max_shards=8, **kw)
    return RebalancePolicy(opts, clock=clock), clock


# ---------------------------------------------------------------------------
# the decision function under a fake clock
# ---------------------------------------------------------------------------

def test_options_validate_hysteresis_band():
    with pytest.raises(ValueError):
        RebalanceOptions(split_qps=100.0, merge_qps=80.0)
    with pytest.raises(ValueError):
        RebalanceOptions(min_shards=0)
    RebalanceOptions(split_qps=100.0, merge_qps=50.0)   # exactly half


def test_split_requires_sustain():
    pol, clock = _policy()
    assert pol.decide(2, [150.0, 20.0]) is None        # first sight
    clock.advance(0.5)
    assert pol.decide(2, [150.0, 20.0]) is None        # not yet
    clock.advance(0.6)
    d = pol.decide(2, [150.0, 20.0])                   # sustained
    assert d is not None and d.kind == "split" and d.num_shards == 4


def test_flapping_signal_never_acts():
    pol, clock = _policy()
    for _ in range(20):
        assert pol.decide(2, [150.0, 0.0]) is None     # hot...
        clock.advance(0.6)
        assert pol.decide(2, [5.0, 0.0]) is None       # ...cold: reset
        clock.advance(0.6)


def test_min_interval_cooldown_and_merge_hysteresis():
    pol, clock = _policy()
    clock.advance(1.1)
    pol.decide(2, [150.0, 20.0])
    clock.advance(1.1)
    d = pol.decide(2, [150.0, 20.0])
    assert d.kind == "split"
    pol.note_action()
    # immediately cold on the NEW topology: merge may not fire inside
    # the cooldown, and its sustain only starts counting fresh
    clock.advance(1.2)
    assert pol.decide(4, [1.0, 1.0, 1.0, 1.0]) is None
    clock.advance(1.2)   # sustain satisfied but still in cooldown
    assert pol.decide(4, [1.0, 1.0, 1.0, 1.0]) is None
    clock.advance(3.0)   # cooldown over (5s), sustain long since held
    d = pol.decide(4, [1.0, 1.0, 1.0, 1.0])
    assert d is not None and d.kind == "merge" and d.num_shards == 2
    # a load INSIDE the band (between merge and split) decides nothing
    pol.note_action()
    clock.advance(10.0)
    for _ in range(5):
        assert pol.decide(2, [50.0, 50.0]) is None
        clock.advance(1.0)


def test_split_respects_max_shards_merge_respects_min():
    pol, clock = _policy()
    for _ in range(3):
        clock.advance(1.1)
        assert pol.decide(8, [500.0] * 8) is None      # 16 > max 8
    pol2, clock2 = _policy()
    for _ in range(3):
        clock2.advance(1.1)
        assert pol2.decide(1, [1.0]) is None           # min reached
    # odd shard counts cannot halve
    pol3, clock3 = _policy()
    for _ in range(3):
        clock3.advance(1.1)
        assert pol3.decide(3, [1.0, 1.0, 1.0]) is None


def test_failback_decision_beats_split_and_has_own_sustain():
    pol, clock = _policy()
    mis = [(1, "10.0.0.1:7")]
    assert pol.decide(2, [150.0, 0.0], misplaced=mis) is None
    clock.advance(0.6)                                 # > 0.5s sustain
    d = pol.decide(2, [150.0, 0.0], misplaced=mis)
    assert d is not None and d.kind == "failback"
    assert d.shard == 1 and d.addr == "10.0.0.1:7"
    # a misplacement that heals itself resets the sustain window
    pol2, clock2 = _policy()
    pol2.decide(2, [0.0, 0.0], misplaced=mis)
    clock2.advance(0.3)
    pol2.decide(2, [0.0, 0.0])                         # healed
    clock2.advance(0.3)
    assert pol2.decide(2, [0.0, 0.0], misplaced=mis) is None


def test_failback_can_be_disabled():
    clock = FakeClock()
    pol = RebalancePolicy(RebalanceOptions(failback=False),
                          clock=clock)
    mis = [(0, "10.0.0.1:7")]
    for _ in range(4):
        clock.advance(1.0)
        # rates inside the hysteresis band: the ONLY candidate action
        # would be the failback, and it is disabled
        assert pol.decide(2, [50.0, 50.0], misplaced=mis) is None


# ---------------------------------------------------------------------------
# tail-pressure signals: p99 / shed rate as split triggers (ISSUE 16)
# ---------------------------------------------------------------------------

def test_tail_pressure_p99_splits_without_qps():
    pol, clock = _policy(split_p99_ms=50.0)
    # qps WELL below the split threshold: only the p99 signal is hot
    assert pol.decide(2, [10.0, 5.0],
                      shard_p99_ms=[80.0, 1.0]) is None   # first sight
    clock.advance(1.1)
    d = pol.decide(2, [10.0, 5.0], shard_p99_ms=[80.0, 1.0])
    assert d is not None and d.kind == "split" and d.num_shards == 4
    assert "tail pressure" in d.reason


def test_tail_pressure_shed_rate_splits_without_qps():
    pol, clock = _policy(split_shed_per_s=5.0)
    assert pol.decide(2, [10.0, 5.0],
                      shed_per_s=[20.0, 0.0]) is None
    clock.advance(1.1)
    d = pol.decide(2, [10.0, 5.0], shed_per_s=[20.0, 0.0])
    assert d is not None and d.kind == "split"
    assert "tail pressure" in d.reason


def test_tail_pressure_requires_sustain_like_qps():
    pol, clock = _policy(split_p99_ms=50.0)
    for _ in range(10):
        # flapping p99 never acts: hot sample, then a cold one resets
        assert pol.decide(2, [10.0, 5.0],
                          shard_p99_ms=[80.0, 1.0]) is None
        clock.advance(0.6)
        assert pol.decide(2, [10.0, 5.0],
                          shard_p99_ms=[5.0, 1.0]) is None
        clock.advance(0.6)


def test_tail_pressure_vetoes_merge():
    clock = FakeClock()
    pol = RebalancePolicy(
        RebalanceOptions(split_qps=100.0, merge_qps=10.0, sustain_s=1.0,
                         min_interval_s=5.0, max_shards=4,
                         split_p99_ms=50.0), clock=clock)
    clock.advance(10.0)
    # 4 shards, qps cold enough to merge — but one shard's tail is on
    # fire: shrinking the fleet under pressure would make it worse
    for _ in range(4):
        assert pol.decide(4, [1.0, 1.0, 1.0, 1.0],
                          shard_p99_ms=[80.0, 1.0, 1.0, 1.0]) is None
        clock.advance(1.1)
    # pressure clears: merge sustain starts fresh, then fires
    assert pol.decide(4, [1.0, 1.0, 1.0, 1.0],
                      shard_p99_ms=[5.0, 1.0, 1.0, 1.0]) is None
    clock.advance(1.1)
    d = pol.decide(4, [1.0, 1.0, 1.0, 1.0],
                   shard_p99_ms=[5.0, 1.0, 1.0, 1.0])
    assert d is not None and d.kind == "merge"


def test_tail_pressure_knobs_default_off():
    pol, clock = _policy()                 # both thresholds at 0.0
    for _ in range(4):
        clock.advance(1.1)
        # enormous signals are IGNORED until a threshold is configured
        assert pol.decide(2, [10.0, 5.0], shard_p99_ms=[9999.0, 0.0],
                          shed_per_s=[9999.0, 0.0]) is None


# ---------------------------------------------------------------------------
# the daemon end to end (native)
# ---------------------------------------------------------------------------

VOCAB, DIM = 256, 8


def _registry(rpc):
    srv = rpc.Server()
    srv.add_naming_registry()
    port = srv.start("127.0.0.1:0")
    return srv, f"127.0.0.1:{port}"


@pytest.mark.needs_native
def test_rebalancer_fails_back_revived_primary():
    """A shard whose primary moved to a backup (failure-driven
    promotion) and whose declared primary is back and caught up: the
    rebalancer promotes the declared primary back — clients converge
    exactly as in a failure failover."""
    from brpc_tpu import rpc
    from brpc_tpu.naming import (NamingClient, PartitionScheme,
                                 ReplicaSet, publish_scheme)
    from brpc_tpu.ps_remote import PsShardServer
    reg_server, reg_addr = _registry(rpc)
    servers = [PsShardServer(VOCAB, DIM, 0, 1, lr=1.0)
               for _ in range(3)]
    rs = ReplicaSet(tuple(s.address for s in servers), primary=0)
    for i, s in enumerate(servers):
        s.configure_replication(rs, i)
    scheme = PartitionScheme(1, (rs,))
    nc = NamingClient(reg_addr)
    publish_scheme(nc, "ps", scheme)
    for s in servers:
        nc.register("ps", s.address, ttl_ms=500, tag_fn=s.claim_tag)
    reb = Rebalancer(reg_addr, "ps", VOCAB,
                     policy=RebalancePolicy(RebalanceOptions(
                         failback_sustain_s=0.0)))
    try:
        # failure-style promotion of replica 1
        ch = rpc.Channel(servers[1].address, timeout_ms=3000)
        try:
            ch.call("Ps", "Promote", struct.pack("<q", 1))
        finally:
            ch.close()
        assert servers[1].is_primary
        # replica 0 learns it was usurped on the next propagation —
        # poke it with a write so the Sync fences it
        ids = np.arange(8, dtype=np.int32)
        ch = rpc.Channel(servers[1].address, timeout_ms=3000)
        try:
            from brpc_tpu.ps_remote import _pack_apply_req
            ch.call("Ps", "ApplyGrad", bytes(_pack_apply_req(
                ids, np.full((8, DIM), 0.5, np.float32))))
        finally:
            ch.close()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and servers[0].is_primary:
            time.sleep(0.02)
        assert not servers[0].is_primary
        fb0 = int(obs.counter("ps_failbacks").get_value())
        # two steps: the first may only start the sustain window
        decided = None
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and decided is None:
            decided = reb.step()
            time.sleep(0.05)
        assert decided is not None and decided.kind == "failback"
        assert int(obs.counter("ps_failbacks").get_value()) == fb0 + 1
        assert servers[0].epoch >= 2
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not servers[0].is_primary:
            time.sleep(0.02)
        assert servers[0].is_primary
    finally:
        reb.stop()
        nc.close()
        for s in servers:
            s.close()
        reg_server.close()


@pytest.mark.needs_native
@pytest.mark.parametrize("kind,n_old,n_new,thresholds,reads,pause_s", [
    # sustained per-shard rate above the split threshold
    ("split", 2, 4, dict(split_qps=30.0, merge_qps=1.0), 10, 0.0),
    # a cold load: every shard below the merge threshold, held there
    ("merge", 4, 2, dict(split_qps=1e6, merge_qps=25.0, min_shards=2),
     1, 0.1),
])
def test_rebalancer_splits_on_sustained_load_end_to_end(
        kind, n_old, n_new, thresholds, reads, pause_s):
    """The full autonomous loop on real servers, both directions:
    a sustained per-shard rate past its threshold -> the rebalancer
    provisions the successor through its provisioner, drives the
    migration, retires the old scheme, and hands the old servers to
    on_retired — no operator call anywhere, and the acked ledger exact
    across the migration."""
    from brpc_tpu import rpc
    from brpc_tpu.naming import (NamingClient, PartitionScheme,
                                 ReplicaSet, parse_schemes,
                                 publish_scheme)
    from brpc_tpu.ps_remote import PsShardServer, RemoteEmbedding
    reg_server, reg_addr = _registry(rpc)
    old = [PsShardServer(VOCAB, DIM, s, n_old, lr=1.0, stream=True)
           for s in range(n_old)]
    sc1 = PartitionScheme(1, tuple(ReplicaSet.of(s.address)
                                   for s in old))
    nc = NamingClient(reg_addr)
    publish_scheme(nc, "ps", sc1)
    spawned = []
    retired = []

    def provisioner(version, num_shards):
        servers = [PsShardServer(VOCAB, DIM, s, num_shards, lr=1.0,
                                 stream=True, importing=True,
                                 scheme_version=version)
                   for s in range(num_shards)]
        spawned.extend(servers)
        return PartitionScheme(version, tuple(
            ReplicaSet.of(s.address) for s in servers))

    pol = RebalancePolicy(RebalanceOptions(
        sustain_s=0.2, min_interval_s=0.5, **thresholds))
    reb = Rebalancer(reg_addr, "ps", VOCAB, policy=pol,
                     provisioner=provisioner,
                     on_retired=retired.append,
                     migrate_deadline_s=30.0, drain_deadline_s=8.0)
    emb = RemoteEmbedding.from_registry(reg_addr, "ps", VOCAB, DIM,
                                        timeout_ms=10000, watch=True)
    ids = np.arange(VOCAB, dtype=np.int32)
    before = np.concatenate([s.table.copy() for s in old])
    try:
        emb.apply_gradients(ids, np.full((VOCAB, DIM), 0.5,
                                         np.float32))
        # the read load held while stepping
        decided = None
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and decided is None:
            for _ in range(reads):
                emb.lookup(ids[:64])
            time.sleep(pause_s)
            decided = reb.step()
        assert decided is not None and decided.kind == kind
        assert decided.num_shards == n_new
        assert int(obs.counter(f"ps_rebalance_{kind}s").get_value()) >= 1
        # the migration completed: the registry's active scheme is v2
        # and the ledger is exact across it
        nodes, _ = nc.list("ps")
        schemes = parse_schemes(nodes)
        assert schemes[2].state == "active"
        assert schemes[1].state == "retired"
        assert retired and retired[0].version == 1
        assert len(spawned) == n_new
        emb.apply_gradients(ids, np.full((VOCAB, DIM), 0.25,
                                         np.float32))
        expect = before.copy()
        for d in (0.5, 0.25):
            expect[ids] -= np.float32(d)
        assert np.array_equal(
            np.concatenate([s.table for s in spawned]), expect)
        assert np.array_equal(emb.lookup(ids), expect)
    finally:
        reb.stop()
        emb.close()
        nc.close()
        for s in old + spawned:
            s.close()
        reg_server.close()


@pytest.mark.needs_native
def test_rebalancer_split_auto_hydrates_from_checkpoint_stores(tmp_path):
    """A policy-decided split on sources with attached checkpoint
    stores seeds every destination from the on-disk base BEFORE the
    copy phase: ps_rebalance_hydrations counts the seeded
    destinations and no source ships a wholesale range snapshot
    (ps_migrate_syncs_out stays flat)."""
    from brpc_tpu import rpc
    from brpc_tpu.durable import CheckpointStore
    from brpc_tpu.naming import (NamingClient, PartitionScheme,
                                 ReplicaSet, publish_scheme)
    from brpc_tpu.ps_remote import PsShardServer, RemoteEmbedding
    reg_server, reg_addr = _registry(rpc)
    old = [PsShardServer(VOCAB, DIM, s, 2, lr=1.0, stream=True)
           for s in range(2)]
    stores = {s: CheckpointStore(str(tmp_path / f"shard{s}"))
              for s in range(2)}
    for s, srv in enumerate(old):
        srv.attach_checkpoint(stores[s])   # arms the tee + first base
    sc1 = PartitionScheme(1, tuple(ReplicaSet.of(s.address)
                                   for s in old))
    nc = NamingClient(reg_addr)
    publish_scheme(nc, "ps", sc1)
    spawned = []

    def provisioner(version, num_shards):
        servers = [PsShardServer(VOCAB, DIM, s, num_shards, lr=1.0,
                                 stream=True, importing=True,
                                 scheme_version=version)
                   for s in range(num_shards)]
        spawned.extend(servers)
        return PartitionScheme(version, tuple(
            ReplicaSet.of(s.address) for s in servers))

    pol = RebalancePolicy(RebalanceOptions(
        split_qps=30.0, merge_qps=1.0, sustain_s=0.2,
        min_interval_s=0.5))
    reb = Rebalancer(reg_addr, "ps", VOCAB, policy=pol,
                     provisioner=provisioner,
                     migrate_deadline_s=30.0, drain_deadline_s=8.0,
                     checkpoint_stores=stores)
    emb = RemoteEmbedding.from_registry(reg_addr, "ps", VOCAB, DIM,
                                        timeout_ms=10000, watch=True)
    ids = np.arange(VOCAB, dtype=np.int32)
    before = np.concatenate([s.table.copy() for s in old])
    hyd0 = int(obs.counter("ps_rebalance_hydrations").get_value())
    errs0 = int(obs.counter("ps_rebalance_hydrate_errors").get_value())
    syncs0 = int(obs.counter("ps_migrate_syncs_out").get_value())
    try:
        emb.apply_gradients(ids, np.full((VOCAB, DIM), 0.5,
                                         np.float32))
        decided = None
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and decided is None:
            for _ in range(10):
                emb.lookup(ids[:64])
            decided = reb.step()
        assert decided is not None and decided.kind == "split"
        assert decided.num_shards == 4
        # 2 sources x 2 overlapping destinations each, all seeded from
        # disk, none via a live wholesale range snapshot
        assert int(obs.counter(
            "ps_rebalance_hydrations").get_value()) == hyd0 + 4
        assert int(obs.counter(
            "ps_rebalance_hydrate_errors").get_value()) == errs0
        assert int(obs.counter(
            "ps_migrate_syncs_out").get_value()) == syncs0
        emb.apply_gradients(ids, np.full((VOCAB, DIM), 0.25,
                                         np.float32))
        expect = before.copy()
        for d in (0.5, 0.25):
            expect[ids] -= np.float32(d)
        assert np.array_equal(
            np.concatenate([s.table for s in spawned]), expect)
    finally:
        reb.stop()
        emb.close()
        nc.close()
        for s in old + spawned:
            s.close()
        for st in stores.values():
            st.close()
        reg_server.close()
