#!/usr/bin/env python3
"""PS hot-path benchmark: native async fan-out + read-parallel serving.

Run BY bench.py in a deadline-guarded child (same pattern as
bench_device.py); standalone `python bench_ps.py` works too.  Emits
BENCH_ps.json next to the BENCH_obs/BENCH_analysis series and prints ONE
JSON object.  Without the native core it degrades to {"skipped": ...}.

What it measures (all loopback, CPU shards):

  fanout        — ONE lookup batch whose ids span all shards, issued by
                  the sequential per-shard call loop vs the call_async
                  fan-out, at 1/4/8 shards.  Reports whole-batch mean/p99
                  latency + keys/s and the parallel/sequential latency
                  ratio — the fan-out's point is max(shard) vs
                  sum(shard), so the ratio should approach 1/shards.
  single_shard  — ONE shard hammered with Lookups by 1 vs 8 concurrent
                  client threads, served under the pre-PR mutex
                  (lock_mode="mutex") vs the read-parallel rwlock.
                  Reports keys/s each way and the rwlock/mutex ratio at
                  8 clients — reader parallelism is the whole difference.
  native_read   — the same 1/8-client hammer against the NATIVE Lookup
                  handler (PsShardServer(native_read=True): zero Python,
                  no GIL, no trampoline in the read loop) vs the Python
                  rwlock path.  native_over_python_8clients is the
                  headline: the rwlock path capped out at ~0.96x mutex
                  because request framing held the GIL; the native path
                  has no GIL to hold.
  write         — the WRITE-path mirror (--block write, run by bench.py
                  as the "ps_write" child): one native_read CPU shard
                  hammered with ApplyGrads by 1/4/8 writers through the
                  unary path (per-call write lock + whole-table snapshot
                  install) vs the server-side combiner (one
                  subtract.at + ONE install per drained batch) vs the
                  streaming push (framed deltas over one ordered
                  flow-controlled stream per writer, no per-call
                  dispatch), plus a device-shard fan-in cell counting
                  wasted optimistic-install scatter launches with and
                  without the combiner.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


def bench_fanout(nshards: int, vocab: int = 65536, dim: int = 64,
                 batch: int = 4096, secs: float = 2.0) -> dict:
    from brpc_tpu.ps_remote import PsShardServer, RemoteEmbedding

    servers = [PsShardServer(vocab, dim, i, nshards)
               for i in range(nshards)]
    addrs = [s.address for s in servers]
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, batch).astype(np.int32)  # spans all shards
    out = {}
    try:
        for mode, parallel in (("sequential", False), ("parallel", True)):
            emb = RemoteEmbedding(addrs, vocab, dim, timeout_ms=60000,
                                  parallel=parallel)
            try:
                emb.lookup(ids)  # warm
                lat = []
                t_end = time.monotonic() + secs
                while time.monotonic() < t_end:
                    t0 = time.perf_counter_ns()
                    emb.lookup(ids)
                    lat.append((time.perf_counter_ns() - t0) / 1e6)
            finally:
                emb.close()
            lat.sort()
            mean_ms = sum(lat) / len(lat)
            out[mode] = {
                "mean_ms": round(mean_ms, 3),
                "p50_ms": round(_percentile(lat, 0.50), 3),
                "p99_ms": round(_percentile(lat, 0.99), 3),
                "keys_per_s": round(batch * 1000.0 / mean_ms, 0),
                "batches": len(lat),
            }
    finally:
        for s in servers:
            s.close()
    out["latency_ratio"] = round(
        out["parallel"]["mean_ms"] / out["sequential"]["mean_ms"], 3)
    return out


def bench_single_shard(clients: int, lock_mode: str, vocab: int = 65536,
                       dim: int = 128, batch: int = 2048,
                       secs: float = 2.0,
                       native_read: bool = False) -> dict:
    import struct

    from brpc_tpu import rpc
    from brpc_tpu.ps_remote import PsShardServer

    server = PsShardServer(vocab, dim, 0, 1, lock_mode=lock_mode,
                           native_read=native_read)
    counts = [0] * clients
    stop = threading.Event()
    ready = threading.Barrier(clients + 1, timeout=30)

    def worker(i: int) -> None:
        ch = rpc.Channel(server.address, timeout_ms=60000)
        rng = np.random.default_rng(i)
        ids = rng.integers(0, vocab, batch).astype(np.int32)
        req = struct.pack("<i", batch) + ids.tobytes()
        try:
            ch.call("Ps", "Lookup", req)  # warm
            ready.wait()
            while not stop.is_set():
                ch.call("Ps", "Lookup", req)
                counts[i] += 1
        finally:
            ch.close()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(clients)]
    try:
        for t in threads:
            t.start()
        ready.wait()
        t0 = time.monotonic()
        time.sleep(secs)
        stop.set()
        for t in threads:
            t.join(30)
        dt = time.monotonic() - t0
        native_served = int(server.native_lookups)
    finally:
        stop.set()
        server.close()
    total = sum(counts)
    out = {
        "lookups_per_s": round(total / dt, 1),
        "keys_per_s": round(total * batch / dt, 0),
    }
    if native_read:
        out["native_lookups"] = native_served  # proves the path served
    return out


def bench_write_path(writers: int, mode: str, vocab: int = 32768,
                     dim: int = 64, batch: int = 64,
                     secs: float = 2.0) -> dict:
    """One native_read CPU shard hammered with ApplyGrads by `writers`
    concurrent threads.  mode: "unary" (per-call lock+install),
    "combined" (server-side GradCombiner: one subtract.at + one install
    per drained batch) or "stream" (framed deltas over one ordered
    flow-controlled stream per writer, feeding the combiner).  The
    elapsed window INCLUDES the stream drain (close+join = applied
    barrier), so keys/s is applied-throughput for every mode.

    Geometry is the big-table / small-delta regime (8MB shard, 64 keys
    per apply — production embedding shape): under native_read the unary
    write path pays a whole-table snapshot install PER CALL, which is
    exactly the cost the combiner amortizes across a drained batch."""
    import struct

    from brpc_tpu import rpc
    from brpc_tpu.ps_remote import PsShardServer

    server = PsShardServer(vocab, dim, 0, 1, native_read=True,
                           combine=(mode != "unary"),
                           stream=(mode == "stream"))
    counts = [0] * writers
    stop = threading.Event()
    ready = threading.Barrier(writers + 1, timeout=60)

    def worker(i: int) -> None:
        ch = rpc.Channel(server.address, timeout_ms=60000)
        rng = np.random.default_rng(i)
        ids = rng.integers(0, vocab, batch).astype(np.int32)
        grads = (rng.integers(-2, 3, (batch, dim))).astype(np.float32)
        req = struct.pack("<i", batch) + ids.tobytes() + grads.tobytes()
        try:
            if mode == "stream":
                st = ch.stream("Ps", "StreamApply")
                st.write(req)  # warm
                ready.wait()
                while not stop.is_set():
                    st.write(req)
                    counts[i] += 1
                st.close()
                st.join(timeout_s=120)
            else:
                ch.call("Ps", "ApplyGrad", req)  # warm
                ready.wait()
                while not stop.is_set():
                    ch.call("Ps", "ApplyGrad", req)
                    counts[i] += 1
        finally:
            ch.close()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(writers)]
    try:
        for t in threads:
            t.start()
        ready.wait()
        t0 = time.monotonic()
        time.sleep(secs)
        stop.set()
        for t in threads:
            t.join(180)
        # join AFTER the streams drained: applied throughput, not
        # buffered throughput
        dt = time.monotonic() - t0
    finally:
        stop.set()
        server.close()
    total = sum(counts)
    return {
        "applies_per_s": round(total / dt, 1),
        "keys_per_s": round(total * batch / dt, 0),
    }


def bench_device_write(writers: int, combine: bool, vocab: int = 8192,
                      dim: int = 64, batch: int = 256,
                      rounds: int = 15) -> dict:
    """Device-shard write fan-in: `writers` threads each apply `rounds`
    unary ApplyGrads.  Counts wasted optimistic-install scatter launches
    (lost-swap redos — ~linear in writers without the combiner) and, with
    the combiner, drained batches.  Uses the in-repo fake PJRT plugin;
    obs stays ON here because the counters ARE the metric."""
    import struct

    from brpc_tpu import obs, rpc
    from brpc_tpu.ps_remote import DevicePsShardServer

    dev = rpc.DeviceClient(rpc.fake_pjrt_plugin_path())
    obs.set_enabled(True)
    wasted0 = obs.counter("ps_device_wasted_launches").get_value()
    applies0 = obs.counter("ps_combined_applies").get_value()
    server = DevicePsShardServer(vocab, dim, 0, 1, device_client=dev,
                                 combine=combine)
    try:
        def worker(i: int) -> None:
            ch = rpc.Channel(server.address, timeout_ms=120000)
            rng = np.random.default_rng(i)
            ids = rng.integers(0, vocab, batch).astype(np.int32)
            grads = rng.standard_normal((batch, dim)).astype(np.float32)
            req = struct.pack("<i", batch) + ids.tobytes() + grads.tobytes()
            try:
                for _ in range(rounds):
                    ch.call("Ps", "ApplyGrad", req)
            finally:
                ch.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(writers)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        dt = time.monotonic() - t0
    finally:
        server.close()
        dev.close()
    wasted = int(obs.counter("ps_device_wasted_launches").get_value()
                 - wasted0)
    batches = int(obs.counter("ps_combined_applies").get_value() - applies0)
    total = writers * rounds
    out = {
        "applies": total,
        "wasted_launches": wasted,
        "applies_per_s": round(total / dt, 1),
    }
    if combine:
        out["drained_batches"] = batches
        out["wasted_per_batch"] = round(wasted / max(batches, 1), 3)
    return out


def run_write_block() -> dict:
    """The ps_write bench.py child: unary vs combined vs stream applied
    throughput at 1/4/8 writers on one CPU shard, plus the device
    wasted-launch cell with/without the combiner."""
    from brpc_tpu import obs

    obs.set_enabled(False)  # throughput cells measure the fabric
    write: dict = {}
    for mode in ("unary", "combined", "stream"):
        write[mode] = {str(w): bench_write_path(w, mode)
                       for w in (1, 4, 8)}
    for key in ("combined", "stream"):
        write[f"{key}_over_unary_8writers"] = round(
            write[key]["8"]["keys_per_s"] /
            max(write["unary"]["8"]["keys_per_s"], 1.0), 3)
    try:
        device = {
            "unary": bench_device_write(8, combine=False),
            "combined": bench_device_write(8, combine=True),
        }
    except Exception as e:  # noqa: BLE001 — no plugin/device reachable
        device = {"skipped": f"{type(e).__name__}: {e}"[:200]}
    finally:
        obs.set_enabled(False)
    write["device_wasted_launches_8writers"] = device
    return write


def _merge_result(out_path: str, result: dict) -> None:
    """Keep the blocks the other --block run wrote (the hot and write
    children both land in BENCH_ps.json)."""
    try:
        with open(out_path) as f:
            old = json.load(f)
    except (OSError, ValueError):
        old = {}
    old.update(result)
    result.clear()
    result.update(old)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--block", choices=("all", "hot", "write"),
                        default="all",
                        help="hot = fanout/lock/native_read read-path "
                             "cells; write = combiner/stream write-path "
                             "cells")
    args = parser.parse_args()
    out_path = os.path.join(ROOT, "BENCH_ps.json")
    # cpu_count matters for reading the numbers: on a 1-core host there
    # is no idle time to overlap, so both ratios sit near 1.0 regardless
    # of implementation — the fan-out/rwlock wins show with cores.
    result: dict = {"metric": "ps_hot_path", "cpu_count": os.cpu_count()}
    # 8 concurrent handlers need >= 8 fiber workers regardless of host
    # size; must land before the first rpc call initializes the runtime.
    os.environ.setdefault("BRT_WORKERS", str(max(8, os.cpu_count() or 1)))
    try:
        from brpc_tpu import obs, rpc

        if not rpc.native_core_available():
            result = {"metric": "ps_hot_path",
                      "skipped": rpc._load_error or
                      "native core unavailable"}
        elif args.block == "write":
            result["write"] = run_write_block()
        else:
            obs.set_enabled(False)  # measure the fabric, not the meters
            result["fanout"] = {
                str(n): bench_fanout(n) for n in (1, 4, 8)}
            result["fanout_latency_ratio_4shards"] = \
                result["fanout"]["4"]["latency_ratio"]
            single = {}
            for lock_mode in ("mutex", "rw"):
                single[lock_mode] = {
                    str(c): bench_single_shard(c, lock_mode)
                    for c in (1, 8)}
            single["rw_over_mutex_8clients"] = round(
                single["rw"]["8"]["keys_per_s"] /
                max(single["mutex"]["8"]["keys_per_s"], 1.0), 3)
            result["single_shard_lookup"] = single
            # Native zero-Python read path vs the Python rwlock path.
            # Serving-style geometry (dim=16, batch=256 — the small
            # recommendation-lookup regime) so per-REQUEST overhead — the
            # GIL-held trampoline/framing the native path deletes — is
            # what gets measured, not response memcpy bandwidth; both
            # paths run the SAME geometry and client hammer.  On a 1-core
            # host this is the native path's WORST case (no handler
            # parallelism to win back), so the ratio is a floor.
            nr_kw = dict(dim=16, batch=256)

            def best_of(n, clients, native):
                # Shared 1-core hosts swing ~25% with neighbor noise
                # (same rationale as bench.py's best-of-3 headline):
                # noise only ever subtracts, so keep the best sample.
                return max((bench_single_shard(clients, "rw",
                                               native_read=native,
                                               **nr_kw)
                            for _ in range(n)),
                           key=lambda r: r["keys_per_s"])

            nat_block = {}
            for mode, native in (("python_rw", False), ("native", True)):
                nat_block[mode] = {
                    str(c): best_of(2, c, native) for c in (1, 8)}
            nat_block["native_over_python_8clients"] = round(
                nat_block["native"]["8"]["keys_per_s"] /
                max(nat_block["python_rw"]["8"]["keys_per_s"], 1.0), 3)
            result["native_read"] = nat_block
            if args.block == "all":
                result["write"] = run_write_block()
    except Exception as e:  # noqa: BLE001
        result = {"metric": "ps_hot_path",
                  "skipped": f"{type(e).__name__}: {e}"[:300]}
    if "skipped" not in result:
        _merge_result(out_path, result)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
