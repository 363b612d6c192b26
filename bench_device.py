#!/usr/bin/env python3
"""Device-tier bench: the TPU north-star numbers (BASELINE.md:19-22).

Run BY bench.py as a child. Prints ONE JSON object:
  h2d_gbps / d2h_gbps   — zero-copy staging through the registered block
                          pool (cpp/device/pjrt_device.cc), the RDMA-verbs
                          analog path;
  ps_lookup_qps         — device-resident PS shard: embedding rows served
                          from HBM via compiled gather;
  step_time_ms / achieved_tflops / mxu_utilization
                        — single-chip compiled train step, sized to be
                          matmul-bound (hidden 2048, seq 1024 — a tiny
                          config is overhead-bound by construction and
                          reports a meaningless MFU). Utilization is
                          against the bf16 peak of the device JAX reports
                          (PEAK_BF16_TFLOPS); a device not in that table is
                          an error, not a default.

Modes (--mode):
  real  — the chip (default): the native client on the installed libtpu,
          then JAX. Anything that fails ends the process non-zero; nothing
          falls back to the CPU.
  sim   — no chip: staging/PS against the in-repo fake N-device PJRT
          plugin (cpp/device/fake_pjrt_plugin.cc) and the train step on
          host CPU. Clearly labeled — these numbers exercise the path
          (handle lifecycle, DMA pool, compiled gather) every round so it
          cannot silently rot, but say nothing about TPU speed.

Blocks (--block):
  baseline — the north-star numbers above (default).
  parity   — the ISSUE 20 device-tier parity scenario: an HBM-serving
             replicated pair under sustained load through kill-primary →
             failover → revival → failback, then a LIVE 1→2 device
             split — availability over EVERY op and the exact
             zero-lost-acked-update ledger at the end.  Refreshes
             BENCH_device.json.  The scenario proves fabric control flow,
             not chip speed — bench.py runs it in sim mode.
"""

import argparse
import json
import os
import struct
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Peak dense bf16 TFLOP/s per chip, keyed by jax's device_kind. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}


def bench_staging(dev, out):
    mb = 64
    blob = b"x" * (mb << 20)
    # Warm-up (first transfer sets up the pool).
    h = dev.stage(blob)
    dev.fetch(h)
    dev.release(h)
    reps = 5
    t0 = time.monotonic()
    handles = []
    for _ in range(reps):
        handles.append(dev.stage(blob))
    t1 = time.monotonic()
    for h in handles:
        got = dev.fetch(h)
        assert len(got) == len(blob)
        dev.release(h)
    t2 = time.monotonic()
    out["h2d_gbps"] = round(reps * mb / 1024 / (t1 - t0), 2)
    out["d2h_gbps"] = round(reps * mb / 1024 / (t2 - t1), 2)


def bench_ps(dev, out):
    import numpy as np

    from brpc_tpu.ps_remote import DevicePsShardServer, RemoteEmbedding

    vocab, dim = 65536, 128
    s = DevicePsShardServer(vocab, dim, 0, 1, lr=0.1, device_client=dev)
    emb = RemoteEmbedding([s.address], vocab, dim, timeout_ms=120000)
    ids = np.arange(256, dtype=np.int64) * 13 % vocab
    emb.lookup(ids)  # warm (compiles the gather)
    n = 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < 3.0:
        emb.lookup(ids)
        n += 1
    dt = time.monotonic() - t0
    out["ps_lookup_qps"] = round(n / dt, 1)
    out["ps_rows_per_s"] = round(n * len(ids) / dt, 0)
    emb.close()
    s.close()


def bench_step(out, sim: bool):
    import jax
    import jax.numpy as jnp
    import optax

    from brpc_tpu import compile_cache
    from brpc_tpu.models import llama
    from brpc_tpu.parallel import make_mesh, shard_batch, shard_params

    compile_cache.enable()
    device = jax.devices()[0]
    if not sim and device.device_kind not in PEAK_BF16_TFLOPS:
        raise RuntimeError(
            f"no bf16 peak on record for device kind "
            f"{device.device_kind!r} (platform {device.platform}); add it "
            f"to PEAK_BF16_TFLOPS with its source")
    if sim:
        # Host CPU: keep the measured path identical but the shapes small
        # enough that 10 steps finish inside the parent deadline.
        cfg = llama.LlamaConfig(
            vocab_size=2048, hidden=256, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=64, intermediate=1024)
        batch, seq, reps = 4, 256, 10
    else:
        # Matmul-bound by construction: ~570M params, 8K tokens/step →
        # ~28 TFLOP/step, far past the regime where dispatch overhead or
        # HBM-bound embedding lookups can dominate the timing.
        cfg = llama.LlamaConfig(
            vocab_size=16384, hidden=2048, n_layers=8, n_heads=16,
            n_kv_heads=8, head_dim=128, intermediate=8192)
        batch, seq, reps = 8, 1024, 10
    mesh = make_mesh({}, devices=[device])
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    params = shard_params(params, llama.param_specs(cfg), mesh)
    optimizer = optax.adamw(1e-3)
    opt_state = optimizer.init(params)
    tokens = shard_batch(
        jnp.zeros((batch, seq), jnp.int32), llama.batch_specs(), mesh)
    # Donated: without it the old and new params + AdamW state are both
    # live, which at real widths does not fit a chip.
    step = jax.jit(llama.make_train_step(cfg, optimizer, None),
                   donate_argnums=(0, 1))
    with mesh:
        params, opt_state, loss = step(params, opt_state, tokens)  # compile
        jax.block_until_ready(loss)
        t0 = time.monotonic()
        for _ in range(reps):
            params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready(loss)
        dt = (time.monotonic() - t0) / reps
    nparams = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    # Training step ≈ 6 * params * tokens FLOPs (fwd 2x + bwd 4x).
    flops = 6.0 * nparams * batch * seq
    out["step_platform"] = device.platform
    out["step_device_kind"] = device.device_kind
    out["step_time_ms"] = round(dt * 1000, 2)
    out["model_params"] = nparams
    out["achieved_tflops"] = round(flops / dt / 1e12, 3)
    # MFU is only meaningful against a known accelerator peak.
    out["mxu_utilization"] = (
        None if sim else round(
            flops / dt / (PEAK_BF16_TFLOPS[device.device_kind] * 1e12), 4))
    out["loss"] = round(float(loss), 4)


def parity_main(sim: bool) -> int:  # noqa: C901 — one scenario, inline
    """Device-tier parity scenario (ISSUE 20).  One replicated device
    pair (primary serving from HBM, backup on its host mirror) under
    sustained read+write load:

      kill primary → client-driven failover (backup stages its mirror
      into HBM) → revival (the corpse is fenced back to a host-mirror
      backup) → FAILBACK (out-of-band re-promotion stages the original
      again) → a LIVE 1→2 device split (generation-pinned device
      snapshots through unchanged MigrateSync framing) → cutover.

    Measures availability over every op and closes with the exact
    zero-lost-acked-update ledger: the destination DEVICE tables must
    equal the seed minus exactly one GRAD per acked batch, replayed in
    the servers' own float order."""
    # 7 in-process servers with quorum-ack handlers share the process-
    # global fiber pool; the 1-core default of 4 workers starves into a
    # timeout spiral (same sizing note as bench_churn.py).
    os.environ.setdefault("BRT_WORKERS", "16")
    import threading

    import numpy as np

    from brpc_tpu import fault, obs, resilience, rpc
    from brpc_tpu.naming import (NamingClient, PartitionScheme,
                                 ReplicaSet, publish_scheme)
    from brpc_tpu.ps_remote import DevicePsShardServer, RemoteEmbedding
    from brpc_tpu.reshard import MigrationDriver

    obs.set_enabled(True)
    t0_bench = time.monotonic()
    # sim names the fake plug-in; real takes the installed libtpu. Either
    # way a client that does not come up ends the bench non-zero.
    dev = rpc.DeviceClient(rpc.fake_pjrt_plugin_path() if sim else None)

    VOCAB, DIM, GRAD, BATCH = 256, 8, 2.0 ** -6, 32
    out = {"mode": "sim" if sim else "real", "vocab": VOCAB, "dim": DIM}
    a = DevicePsShardServer(VOCAB, DIM, 0, 1, lr=1.0, seed=7,
                            device_client=dev)
    b = DevicePsShardServer(VOCAB, DIM, 0, 1, lr=1.0, seed=7,
                            device_client=dev)
    seed_table = a.table.copy()          # identical on both (same seed)
    rs = ReplicaSet((a.address, b.address), primary=0)
    a.configure_replication(rs, 0)
    b.configure_replication(rs, 1)
    sc0 = PartitionScheme(0, (rs,))
    # Registry-published schemes + a watching client: the cutover is
    # self-announcing (a writer racing it refreshes on ESCHEMEMOVED and
    # re-splits exactly-once instead of failing an op).
    reg_server = rpc.Server()
    reg_server.add_naming_registry()
    reg_addr = f"127.0.0.1:{reg_server.start('127.0.0.1:0')}"
    nc = NamingClient(reg_addr)
    publish_scheme(nc, "ps", sc0)
    emb = RemoteEmbedding.from_registry(
        reg_addr, "ps", VOCAB, DIM, timeout_ms=10000, watch=True,
        retry=resilience.RetryPolicy(
            max_attempts=6,
            backoff=resilience.Backoff(base_ms=1, max_ms=20),
            attempt_timeout_ms=1000),
        breakers=resilience.BreakerRegistry(
            resilience.BreakerOptions(short_window=4, min_samples=2,
                                      min_isolation_ms=50),
            redirect=True),
        health_check=True, health_interval_ms=20)

    perm = np.random.default_rng(7).permutation(VOCAB).astype(np.int32)
    batches = [np.sort(perm[i:i + BATCH]) for i in
               range(0, VOCAB, BATCH)]
    grads = np.full((BATCH, DIM), GRAD, np.float32)
    read_ids = np.arange(VOCAB, dtype=np.int32)
    stop = threading.Event()
    mu = threading.Lock()
    ok_ops = [0]
    failed_ops = []
    acked = []                          # batch index per acked write

    def _reader():
        while not stop.is_set():
            try:
                emb.lookup(read_ids)
                with mu:
                    ok_ops[0] += 1
            except Exception as e:  # noqa: BLE001 — the verdict
                with mu:
                    failed_ops.append("read: " + repr(e)[:120])
            time.sleep(0.002)

    def _writer():
        i = 0
        while not stop.is_set():
            bi = i % len(batches)
            try:
                emb.apply_gradients(batches[bi], grads)
                with mu:
                    ok_ops[0] += 1
                    acked.append(bi)
            except Exception as e:  # noqa: BLE001 — taints the ledger
                with mu:
                    failed_ops.append("write: " + repr(e)[:120])
            i += 1
            time.sleep(0.002)

    def _wait(pred, deadline_s):
        t_end = time.monotonic() + deadline_s
        while time.monotonic() < t_end:
            if pred():
                return True
            time.sleep(0.02)
        return pred()

    new = []
    drv = None
    try:
        emb.apply_gradients(batches[0], grads)   # warm streams+replicas
        acked.append(0)
        ok_ops[0] += 1
        threads = [threading.Thread(target=_reader),
                   threading.Thread(target=_writer)]
        for t in threads:
            t.start()
        time.sleep(0.5)                          # steady state

        # -- kill-primary -> failover ---------------------------------
        t_kill = time.monotonic()
        fault.install(fault.FaultPlan(fault.kill_rules(a.address),
                                      seed=3))
        rpc.debug_fail_connections(a.address)    # sever live streams too
        out["failover"] = _wait(
            lambda: b.is_primary and b._dev_serving, 15.0)
        out["failover_ms"] = round((time.monotonic() - t_kill) * 1e3, 1)
        time.sleep(0.5)                          # load on the new primary

        # -- revival: the corpse is fenced back to a backup ------------
        fault.clear()
        out["revived"] = _wait(lambda: not emb._isolated(a.address), 5.0)
        out["fenced_down"] = _wait(
            lambda: not a.is_primary and not a._dev_serving, 10.0)

        # -- failback: re-promote the original (the rebalancer's move) -
        # Freshness gate first (rebalance.py:_observe): sample the
        # USURPER's gen before the declared primary's — promoting a
        # backup that hasn't acked everything the usurper holds would
        # strand an acked update (the client's 2008 guard screams).
        def _caught_up():
            gen_b = b._install_gen          # usurper first
            return not a.is_primary and a._install_gen >= gen_b

        out["failback_gate"] = _wait(_caught_up, 10.0)
        ch = rpc.Channel(a.address, timeout_ms=5000)
        try:
            ch.call("Ps", "Promote",
                    struct.pack("<q", max(a.epoch, b.epoch) + 1))
        finally:
            ch.close()
        out["failback"] = _wait(
            lambda: a.is_primary and a._dev_serving, 15.0)
        time.sleep(0.5)                          # load after failback

        # -- live 1->2 device split under the same load ---------------
        new = [DevicePsShardServer(VOCAB, DIM, s, 2, lr=1.0, seed=7,
                                   importing=True, scheme_version=1,
                                   device_client=dev)
               for s in range(2)]
        sc1 = PartitionScheme(1, tuple(ReplicaSet.of(sv.address)
                                       for sv in new))
        t_split = time.monotonic()
        drv = MigrationDriver(sc0, sc1, VOCAB, registry_addr=reg_addr,
                              cluster="ps")
        drv.start()
        drv.wait_caught_up(deadline_s=60)
        drv.cutover()                            # publishes sc1 + drain
        out["split_ms"] = round((time.monotonic() - t_split) * 1e3, 1)
        out["split_serving"] = all(sv._dev_serving for sv in new)
        time.sleep(0.5)                          # load on the new tier

        stop.set()
        for t in threads:
            t.join(30)
        for sv in new:                           # drain in-flight applies
            ch = rpc.Channel(sv.address, timeout_ms=5000)
            try:
                ch.call("Ps", "Flush", b"")
            finally:
                ch.close()

        # -- exact ledger ---------------------------------------------
        # Replay the servers' own float order: every acked batch was ONE
        # float32 in-place subtract of lr*GRAD (lr=1.0, GRAD=2^-6 — the
        # device scatter's f32 multiply is exact for these values).
        expect = seed_table.copy()
        for bi in acked:
            expect[batches[bi]] -= np.float32(GRAD)
        final = np.concatenate([sv.table for sv in new])
        tainted = [f for f in failed_ops if f.startswith("write")]
        out["ledger_exact"] = bool(np.array_equal(final, expect))
        out["ledger_tainted"] = bool(tainted)
        total = ok_ops[0] + len(failed_ops)
        out["ops"] = total
        out["acked_writes"] = len(acked)
        out["failed_ops"] = failed_ops[:20]
        out["availability"] = round(ok_ops[0] / max(1, total), 6)
        for c in ("ps_client_failovers", "ps_device_promote_stages",
                  "ps_device_mirror_downs", "ps_device_wasted_launches",
                  "ps_migrate_hydrates"):
            out[c] = int(obs.counter(c).get_value())
        out["criteria"] = {
            "availability_ge_0p999": out["availability"] >= 0.999,
            "failover": bool(out["failover"]),
            "revival_and_fence": bool(out["revived"]
                                      and out["fenced_down"]),
            "failback": bool(out["failback"]),
            "live_device_split": bool(out["split_serving"]),
            "zero_lost_acked_updates": out["ledger_exact"],
        }
        out["ok"] = all(out["criteria"].values())
        out["wall_s"] = round(time.monotonic() - t0_bench, 2)
    except Exception as e:  # noqa: BLE001 — report, don't die
        out["error"] = f"{type(e).__name__}: {e}"[:300]
        out["ok"] = False
    finally:
        stop.set()
        fault.clear()
        if drv is not None:
            drv.close()
        emb.close()
        nc.close()
        for sv in [a, b] + new:
            try:
                sv.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        reg_server.close()
        dev.close()

    with open(os.path.join(ROOT, "BENCH_device.json"), "w",
              encoding="utf-8") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("real", "sim"), default="real")
    ap.add_argument("--block", choices=("baseline", "parity"),
                    default="baseline")
    args = ap.parse_args()
    sim = args.mode == "sim"
    if args.block == "parity":
        return parity_main(sim)
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if sim:
        os.environ["JAX_PLATFORMS"] = "cpu"   # before jax is imported
    elif platforms and "tpu" not in platforms.split(","):
        # Refused before libtpu loads: without a chip it retries for
        # minutes before client creation fails.
        raise SystemExit(f"bench_device: JAX_PLATFORMS={platforms} keeps "
                         f"this off the TPU (--mode sim is the CPU run)")

    from brpc_tpu import rpc

    out = {"mode": args.mode}
    # The native client first, closed before JAX starts its own. On a v5e
    # libtpu lets one PROCESS hold the chip and start clients in it one
    # after another, or side by side (chip runs of PR 21, PERF.md); it is
    # a second process that cannot have the chip.
    dev = rpc.DeviceClient(rpc.fake_pjrt_plugin_path() if sim else None)
    try:
        out["device_count"] = dev.device_count
        out["pjrt_platform"] = dev.platform
        out["device_kind"] = dev.device_kind(0)
        bench_staging(dev, out)
        bench_ps(dev, out)
    finally:
        dev.close()
    bench_step(out, sim)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
