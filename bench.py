#!/usr/bin/env python3
"""Headline benchmark: same-host echo RPC throughput, large payloads.

Mirrors the reference's headline number (docs/cn/benchmark.md:104 — up to
2.3 GB/s same-host multi-connection echo on 2×E5-2620).  Runs the native
echo benchmark (client+server in one process over loopback) and prints ONE
JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
BUILD = os.path.join(ROOT, "cpp", "build")
BASELINE_GBPS = 2.3  # reference same-host multi-connection echo throughput


def ensure_built() -> str:
    # Always run the (incremental, no-op when fresh) build: a stale binary
    # from an older tree would silently miss newer flags/JSON fields.
    bench = os.path.join(BUILD, "echo_bench")
    os.makedirs(BUILD, exist_ok=True)
    if not os.path.exists(os.path.join(BUILD, "build.ninja")):
        subprocess.run(
            ["cmake", "-G", "Ninja", "-DCMAKE_BUILD_TYPE=Release", ".."],
            cwd=BUILD, check=True, capture_output=True,
        )
    else:
        # Re-run cmake: the build uses file globs, so an existing ninja file
        # would silently miss sources added since it was generated.
        subprocess.run(["cmake", "."], cwd=BUILD, check=True,
                       capture_output=True)
    subprocess.run(["ninja", "echo_bench", "fiber_pingpong"], cwd=BUILD,
                   check=True, capture_output=True)
    return bench


def _run_json_child(script: str, label: str, deadline_s: int,
                    extra_args=(), verdict: str = "skipped") -> dict:
    """Runs a python bench child that prints ONE JSON line (the
    bench_ps/bench_fault pattern: degrades itself to {"skipped": ...}
    without the native core; the deadline guards a wedged build/run).
    A child that does not deliver is reported under ``verdict``."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, script), *extra_args],
            capture_output=True, text=True, timeout=deadline_s, cwd=ROOT,
        )
    except subprocess.TimeoutExpired:
        return {verdict: f"{label} bench exceeded {deadline_s}s deadline"}
    if proc.returncode != 0 or not proc.stdout.strip():
        tail = (proc.stderr or "").strip()[-200:]
        return {verdict: f"{label} bench failed rc={proc.returncode}: "
                         f"{tail}"}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except ValueError:
        return {verdict: f"{label} bench emitted no JSON"}


def run_ps_bench(deadline_s: int = 420) -> dict:
    """PS hot-path numbers (bench_ps.py child): sequential-vs-parallel
    fan-out latency, mutex-vs-rwlock single-shard throughput, and the
    native_read block (zero-Python Lookup vs the Python rwlock path —
    its best-of-2 cells push the child past the old 300s budget on a
    noisy host)."""
    return _run_json_child("bench_ps.py", "ps", deadline_s,
                           extra_args=("--block", "hot"))


def run_ps_write_bench(deadline_s: int = 420) -> dict:
    """PS write-path numbers (bench_ps.py --block write child): unary vs
    combined vs streaming-push applied throughput at 1/4/8 writers on
    one CPU shard, plus the device-shard wasted-scatter-launch cell with
    and without the combiner.  Merges into the same BENCH_ps.json."""
    out = _run_json_child("bench_ps.py", "ps_write", deadline_s,
                          extra_args=("--block", "write"))
    # the child's JSON carries every merged block; the ps_write section
    # of the host line is just the write block
    return out.get("write", out)


def run_reshard_bench(deadline_s: int = 300) -> dict:
    """Elastic-resharding numbers (bench_reshard.py child): a live 4→8
    shard split under sustained lookup+push load — zero failed
    lookups, bounded p99 through the migration window, post-split
    throughput over pre-split, the exact zero-lost-acked-updates
    ledger, and the retirement handle-release proof (also refreshes
    BENCH_reshard.json)."""
    return _run_json_child("bench_reshard.py", "reshard", deadline_s)


def run_scenarios_bench(deadline_s: int = 300) -> dict:
    """Overload-control SLO matrix (bench_scenarios.py child): the
    press harness (zipf skew, read/write mix, open-loop bursts) against
    the limiter/deadline config matrix — availability, p99 of
    successes, and goodput per scenario x config, plus trace
    record/replay determinism (also refreshes BENCH_scenarios.json)."""
    return _run_json_child("bench_scenarios.py", "scenarios",
                           deadline_s)


def run_churn_bench(deadline_s: int = 420) -> dict:
    """Self-driving elasticity (bench_churn.py child): a long-running
    churn scenario — quorum-replicated shards under press-driven load
    with seeded kills, an autonomous rebalancer split + merge, a
    failure-driven failover and an autonomous failback — holding
    availability >= 0.999 with the exact zero-lost-acked-update
    ledger intact end to end (also refreshes BENCH_churn.json)."""
    return _run_json_child("bench_churn.py", "churn", deadline_s)


def run_durable_bench(deadline_s: int = 300) -> dict:
    """Durable fabric (bench_durable.py child): full-fleet kill
    mid-load + checkpoint restore with the exact acked-update ledger
    and a measured recovery-time bound, plus snapshot-hydrated
    replica/split provisioning vs wholesale Sync source-side bytes
    (also refreshes BENCH_durable.json)."""
    return _run_json_child("bench_durable.py", "durable", deadline_s)


def run_zerocopy_bench(deadline_s: int = 300) -> dict:
    """Zero-copy buffer currency (bench_zerocopy.py child): brt_iobuf
    borrow path vs the copy path, A/B in one run — large-payload echo
    GB/s, stream-push throughput, 16-byte echo qps, end-to-end
    push_gradients, and the bytes-copied-per-request ledger (also
    refreshes BENCH_zerocopy.json)."""
    return _run_json_child("bench_zerocopy.py", "zerocopy", deadline_s)


def run_fault_bench(deadline_s: int = 300) -> dict:
    """Fault-tolerance numbers (bench_fault.py child): backup-request
    p99 bounding under an injected slow shard, breaker availability and
    error latency under a flapping shard (also refreshes
    BENCH_fault.json)."""
    return _run_json_child("bench_fault.py", "fault", deadline_s)


def run_device_bench(deadline_s: int = 900) -> dict:
    """The device tier on the chip (bench_device.py --mode real child).
    One process owns a chip at a time, so this parent touches neither JAX
    nor the native client.  A child that fails or finds no chip is
    reported as ``{"failed": ...}`` — and fails this bench — never
    replaced by numbers from the fake plug-in or the CPU (``python
    bench_device.py --mode sim`` is that run, under its own name)."""
    return _run_json_child("bench_device.py", "device", deadline_s,
                           extra_args=("--mode", "real"), verdict="failed")


def run_device_parity_bench(deadline_s: int = 300) -> dict:
    """Device-tier parity scenario (bench_device.py --block parity
    child): an HBM-serving replicated pair under sustained load through
    kill-primary → failover → revival → failback, then a live 1→2
    device split — availability over every op and the exact
    zero-lost-acked-update ledger (also refreshes BENCH_device.json).
    Runs against the fake PJRT plugin: the scenario proves fabric
    control flow, not chip speed."""
    return _run_json_child("bench_device.py", "device_parity",
                           deadline_s,
                           extra_args=("--block", "parity",
                                       "--mode", "sim"))


def main() -> int:
    try:
        bench = ensure_built()
        ncpu = os.cpu_count() or 1
        # Sweep shapes x transports (the reference's headline is also its
        # best multi-connection config, docs/cn/benchmark.md:104): small
        # hosts prefer low depth, big hosts more connections; unix-domain
        # sockets skip the TCP/IP stack for the same-host path.
        shapes = [
            (256 * 1024, 1, 1),   # serial: the per-op floor
            (256 * 1024, 2, 2),
            (256 * 1024, min(4, max(2, ncpu)), 4),
            (256 * 1024, min(8, max(2, ncpu)), 8),
            (512 * 1024, min(4, max(2, ncpu)), 4),
            (1024 * 1024, min(4, max(2, ncpu)), 4),
            (1024 * 1024, min(8, max(2, ncpu)), 8),
        ]
        def run(payload, conns, depth, uds, seconds=3, ssl=0):
            env = dict(os.environ)
            # Inflight calls bound usable parallelism: extra workers only
            # add context switches (biggest effect on small hosts).
            env.setdefault("BRT_WORKERS",
                           str(min(ncpu, max(1, conns * depth))))
            out = subprocess.run(
                [bench, "--payload", str(payload), "--connections",
                 str(conns), "--depth", str(depth), "--seconds",
                 str(seconds), "--uds", str(uds), "--ssl", str(ssl)],
                check=True, capture_output=True, text=True, timeout=300,
                env=env,
            ).stdout
            return json.loads(out.strip().splitlines()[-1])

        best = None
        for payload, conns, depth in shapes:
            for uds in (0, 1):
                stats = run(payload, conns, depth, uds)
                if best is None or stats["gbps"] > best["gbps"]:
                    best = stats

        # Re-measure the winning shape best-of-3: on a shared host single
        # 3s samples swing ~25% with neighbor noise; the headline should
        # reflect the framework, not the noisiest co-tenant moment.
        for _ in range(2):
            stats = run(best["payload"], best["connections"],
                        best["depth"], best["uds"])
            if stats["gbps"] > best["gbps"]:
                best = stats

        # Small-payload envelope (docs/cn/benchmark.md:7 — the 1M-5M QPS
        # regime): trivial 16B echo. Serial shape gives the latency floor;
        # a client sweep shows QPS scaling with concurrency (the
        # reference's defining multi-client property, benchmark.md:142).
        serial = run(16, 1, 1, 1)
        small_best = serial
        scaling = [{"connections": 1, "depth": 1, "qps": serial["qps"]}]
        for conns in (2, 4, 8, 16):
            depth = 16
            stats = run(16, conns, depth, 1)
            scaling.append({"connections": conns, "depth": depth,
                            "qps": stats["qps"]})
            if stats["qps"] > small_best["qps"]:
                small_best = stats

        # Fiber ping-pong: the park/wake context-switch floor underneath
        # every sync RPC (ref test/bthread_ping_pong_unittest.cpp).
        try:
            pp = subprocess.run(
                [os.path.join(BUILD, "fiber_pingpong"), "200000"],
                check=True, capture_output=True, text=True, timeout=120,
            ).stdout
            pingpong = json.loads(pp.strip().splitlines()[-1])
        except Exception as e:  # noqa: BLE001
            pingpong = {"error": f"{type(e).__name__}: {e}"[:200]}

        # TLS row: the winning shape, encrypted, over TCP — paired with a
        # plaintext TCP run of the SAME shape so the delta is the crypto
        # tax alone (the sweep winner may have been uds).
        try:
            plain_tcp = run(best["payload"], best["connections"],
                            best["depth"], 0, ssl=0)
            tls = run(best["payload"], best["connections"], best["depth"],
                      0, ssl=1)
            tls_stats = {"gbps": tls["gbps"], "qps": tls["qps"],
                         "p50_us": tls["p50_us"],
                         "plain_tcp_gbps": plain_tcp["gbps"]}
        except Exception as e:  # noqa: BLE001
            tls_stats = {"error": f"{type(e).__name__}: {e}"[:200]}

        # Device tier (BASELINE north stars): measured by bench_device.py
        # on the chip, in a child. Without a chip the block says
        # `failed` and this bench exits non-zero.
        device_block = run_device_bench()

        # Device-tier parity (ISSUE 20): failover/failback + live
        # device split with the exact ledger (bench_device.py --block
        # parity child; refreshes BENCH_device.json).
        device_parity_block = run_device_parity_bench()

        # PS hot path (ISSUE 4): fan-out + read-parallel serving, measured
        # by bench_ps.py in a child (also refreshes BENCH_ps.json).
        ps_block = run_ps_bench()

        # PS write path (ISSUE 7): server-side gradient combiner +
        # streaming push vs the unary write path (bench_ps.py --block
        # write child; same BENCH_ps.json, "write" block).
        ps_write_block = run_ps_write_bench()

        # Fault tolerance (ISSUE 5): backup requests + circuit breaker
        # under injected faults (bench_fault.py child).
        fault_block = run_fault_bench()

        # Elastic resharding (ISSUE 10): live 4→8 split under traffic
        # (bench_reshard.py child).
        reshard_block = run_reshard_bench()

        # Overload control (ISSUE 12): scenario SLO matrix under the
        # limiter/deadline config cross (bench_scenarios.py child).
        scenarios_block = run_scenarios_bench()

        # Durable fabric (ISSUE 16): fleet-kill restore + hydrated
        # provisioning (bench_durable.py child).
        durable_block = run_durable_bench()

        # Zero-copy buffer currency (ISSUE 19): brt_iobuf borrow path
        # vs the copy path, A/B in one run (bench_zerocopy.py child).
        zerocopy_block = run_zerocopy_bench()

        gbps = best["gbps"]
        print(json.dumps({
            "metric": "same_host_echo_throughput",
            "value": round(gbps, 3),
            "unit": "GB/s",
            "vs_baseline": round(gbps / BASELINE_GBPS, 3),
            "qps": best["qps"],
            "p50_us": best["p50_us"],
            "p99_us": best["p99_us"],
            "config": {k: best[k] for k in
                       ("payload", "connections", "depth", "uds")},
            "small_qps": small_best["qps"],
            "small_p50_us": serial["p50_us"],
            "small_p99_us": serial["p99_us"],
            "small_config": {k: small_best[k] for k in
                             ("payload", "connections", "depth", "uds")},
            "small_scaling": scaling,
            "fiber_pingpong": pingpong,
            "tls": tls_stats,
            "ps": ps_block,
            "ps_write": ps_write_block,
            "fault": fault_block,
            "reshard": reshard_block,
            "scenarios": scenarios_block,
            "durable": durable_block,
            "zerocopy": zerocopy_block,
            "device_parity": device_parity_block,
            "device": device_block,
        }))
        return 1 if "failed" in device_block else 0
    except Exception as e:  # noqa: BLE001
        detail = f"{type(e).__name__}: {e}"
        stderr = getattr(e, "stderr", None)
        if stderr:
            if isinstance(stderr, bytes):
                stderr = stderr.decode(errors="replace")
            detail += " | stderr: " + stderr.strip()[-300:]
        print(json.dumps({
            "metric": "same_host_echo_throughput",
            "value": 0.0,
            "unit": "GB/s",
            "vs_baseline": 0.0,
            "error": detail[:400],
        }))
        return 1


if __name__ == "__main__":
    sys.exit(main())
