"""Driver of the embedding-shard cells: ``DevicePsShardServer`` shards in
HBM behind ``RemoteEmbedding`` over loopback sockets.

One process (this one) owns the chip: JAX (for the device report and the
profiler) beside the native PJRT client that the shards serve from. The
load comes from ``ps_worker.py`` children. What is timed is the program's
own path, ``RemoteEmbedding.lookup`` / ``apply_gradients`` -> socket ->
shard handler -> stage / execute / fetch; what is compared is what that
path returned, against ``reference.RowLedger`` over the same seeded table
and the same acknowledged updates.
"""

from __future__ import annotations

import itertools
from concurrent.futures import ThreadPoolExecutor
import json
import os
import pickle
import struct
import subprocess
import sys
import time

import numpy as np

import gen
import harness
import reference
from drivers import ps_worker

SETUP_WORKER = 1000        # the stream of the set-up's own apply


class SpanClient:
    """``rpc.DeviceClient`` with the benchmark's spans around the calls the
    shard server makes into the native device tier (traced runs only)."""

    def __init__(self, dev, log):
        self._dev, self._log, self._kinds = dev, log, {}

    def __getattr__(self, name):
        return getattr(self._dev, name)

    def stage(self, data, *a, **k):
        nbytes = getattr(data, "nbytes", None) or len(data)
        with self._log.span("stage", nbytes):
            return self._dev.stage(data, *a, **k)

    def fetch(self, handle):
        with self._log.span("fetch") as s:
            raw = self._dev.fetch(handle)
            s.nbytes = len(raw)
            return raw

    def mlir(self, kind, *p):
        text = self._dev.mlir(kind, *p)
        self._kinds[text] = kind
        return text

    def compile(self, text, **k):
        return _SpanExe(self._dev.compile(text, **k), self._log,
                        self._kinds.get(text, "program"))


class _SpanExe:
    def __init__(self, exe, log, kind):
        self._exe, self._log, self._name = exe, log, "execute." + kind

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def execute(self, *a, **k):
        with self._log.span(self._name):
            return self._exe.execute(*a, **k)


class _GivenTable:
    """Stands in for numpy's Generator while the shards are constructed, so
    that a shard's own init arithmetic, ``(normals * 0.02).astype(f32)``,
    runs on the benchmark's seeded normals instead of drawing its own in
    float64. The program has no way to be given a table (PERF.md, Open
    questions)."""

    def __init__(self, part):
        self.part = part

    def standard_normal(self, shape):
        if tuple(shape) != self.part.shape:
            raise ValueError(f"shard asked for {shape}, given "
                             f"{self.part.shape}")
        return self.part


def _make_shards(cls, normals, vocab, dim, n, lr, dev, combine):
    """The cell's shards, constructed side by side (a shard's construction
    is mostly copies and the stage-up of its table, which release the
    interpreter lock). A shard seeds its generator with ``seed +
    shard_index``; with seed 0 that names the shard whose rows it gets."""
    rows = vocab // n
    real = np.random.default_rng
    np.random.default_rng = lambda i: _GivenTable(
        normals[i * rows:(i + 1) * rows])
    try:
        with ThreadPoolExecutor(n) as pool:
            return list(pool.map(
                lambda i: cls(vocab, dim, i, n, lr=lr, seed=0,
                              device_client=dev, device_index=0,
                              combine=combine), range(n)))
    finally:
        np.random.default_rng = real


def _spawn(params: dict):
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(ps_worker.__file__)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    proc.stdin.write((json.dumps(params) + "\n").encode())
    proc.stdin.flush()
    return proc


def _collect(proc) -> dict:
    (n,) = struct.unpack("<q", proc.stdout.read(8))
    out = pickle.loads(proc.stdout.read(n))
    proc.stdin.close()
    proc.wait(timeout=60)
    return out


def _row_gap(ledger, ids, got, maybe, control):
    """The widest gap of one looked-up block from the reference, in units
    of the reduction-order bound. ``maybe`` lists the contributions of
    applies that were in flight while the lookup ran: each row may hold
    any subset of those that touch it (a lookup pins one generation, and
    which one the clock cannot say)."""
    idx = ledger.index_of(ids)
    base = ledger.rows[idx]
    abs_sum = ledger.sum_abs[idx].copy()
    terms = ledger.terms[idx].copy()
    touching = [[] for _ in ids]
    for c_idx, sums, abs_sums, counts in maybe:
        pos = {int(v): j for j, v in enumerate(c_idx)}
        for r, li in enumerate(idx):
            j = pos.get(int(li))
            if j is not None:
                touching[r].append(sums[j])
                abs_sum[r] += abs_sums[j]
                terms[r] += counts[j]
    if control == "lowprec":
        got = reference.to_bfloat16(base)
    bound = np.maximum((terms[:, None] + 1) * reference.EPS32 * abs_sum, 1e-45)
    worst = 0.0
    for r in range(len(ids)):
        cands = touching[r][:8]
        best = np.inf
        for n_in in range(len(cands) + 1):
            for subset in itertools.combinations(cands, n_in):
                want = base[r] - sum(subset) if subset else base[r]
                best = min(best, float(np.max(np.abs(got[r] - want)
                                              / bound[r])))
        worst = max(worst, best)
    return worst


def run(ctx: harness.Context) -> harness.Outcome:
    log, seed, mix = ctx.spans, ctx.seed, ctx.mix
    vocab, dim = ctx.size("vocab_size"), ctx.size("hidden_size")
    n_shards = ctx.size("shards_on_chip")
    lr = ctx.config["learning_rate"]
    k = mix("ids_per_call")
    closed = mix("loop") == "closed"
    n_workers = mix("workers")
    warm_s = mix("warm_seconds")

    # -- set-up: table, chip, shards ----------------------------------------
    table = gen.normal_table(seed, vocab, dim)
    ctx.lap("table_from_seed")
    sys.path.insert(0, harness.ROOT)
    from brpc_tpu import rpc
    rpc.native_core_available()
    ctx.lap("native_build_or_load")
    devices = harness.jax_devices(ctx.cell["chips"], ctx.dry)
    dev = rpc.DeviceClient(rpc.fake_pjrt_plugin_path() if ctx.dry else None)
    if dev.platform != ("brt_fake" if ctx.dry else "tpu"):
        raise harness.NoChip(f"benchmark: PJRT reports {dev.platform!r}")
    from brpc_tpu.ps_remote import DevicePsShardServer, RemoteEmbedding
    ctx.lap("jax_and_native_client")
    client = SpanClient(dev, log) if ctx.trace else dev
    shards = _make_shards(DevicePsShardServer, table, vocab, dim, n_shards, lr,
                          client, ctx.config["combine"])
    # The reference's copy: the same float32 product the shards just made.
    ctx.lap("shards_staged")
    table *= np.float32(ctx.config["init_scale"])
    addresses = [s.address for s in shards]
    emb = RemoteEmbedding(addresses, vocab, dim,
                          timeout_ms=mix("timeout_ms"))
    workers = []
    try:
        zipf = gen.ZipfIds(vocab, mix("zipf_s"), seed, n_shards)
        base = {"root": harness.ROOT, "addresses": addresses, "vocab": vocab,
                "dim": dim, "seed": seed, "n_workers": n_workers,
                "mode": mix("loop"), "ids_per_call": k,
                "zipf_s": mix("zipf_s"), "stripes": n_shards,
                "seconds": ctx.seconds, "warm_s": warm_s,
                "timeout_ms": mix("timeout_ms"),
                "sample_share": mix("sample_share"),
                "sample_max": mix("sample_max"),
                "sample_rows": mix("sample_rows"),
                "rate_per_worker": mix("rate_per_s", 0) / n_workers,
                "think_ms": mix("think_ms", 0),
                "max_calls": int(np.ceil((warm_s + ctx.seconds) * mix(
                    "max_steps_per_s_per_worker", 0))) + 2}
        params = [dict(base, worker=w) for w in range(n_workers)]
        # The workers start (import, plan, connect) while the shards'
        # programs compile: the native compiles are never cached.
        workers = [_spawn(p) for p in params]

        def warm(shard):
            for b in mix("warm_gather"):
                shard._gather_exe(b)
            for b in mix("warm_scatter"):
                shard._scatter_exe(b)
        with ThreadPoolExecutor(n_shards) as pool:
            list(pool.map(warm, shards))
        ctx.lap("programs_compiled")
        # The guarantee, once before the window: an acknowledged apply is
        # read back by the next lookup.
        setup_ids = zipf.draw(gen.rng_for(seed, 5), k)
        setup_grads = ps_worker.grad_block(seed, SETUP_WORKER, k, dim)
        emb.apply_gradients(setup_ids, setup_grads)
        setup_rows = emb.lookup(setup_ids)
        for proc in workers:
            if proc.stdout.readline().strip() != b"ready":
                raise SystemExit("benchmark: a load worker did not start")
        ctx.lap("acked_write_and_workers")
        # -- the window -------------------------------------------------------
        window = harness.TracedWindow(ctx.trace and not ctx.dry)
        # The workers send for warm_s seconds before the window opens: that
        # is set-up, and the profiler starts with the window.
        t_start = time.monotonic() + 0.05 + warm_s
        setup_s = t_start - ctx.t_process
        for proc in workers:
            proc.stdin.write(f"go {t_start!r}\n".encode())
            proc.stdin.flush()
        time.sleep(max(0.0, t_start - time.monotonic()))
        window.start()
        warmed = [(set(s._gather), set(s._scatter)) for s in shards]
        traced_s = min(ctx.seconds, mix("trace_seconds", ctx.seconds))
        if ctx.trace:
            time.sleep(max(0.0, t_start + traced_s - time.monotonic()))
            window.stop()
        logs = [_collect(proc) for proc in workers]
        workers = []
        ctx.lap("window")
        compiled_in_window = sum(
            len(set(s._gather) - g) + len(set(s._scatter) - sc)
            for s, (g, sc) in zip(shards, warmed))
        device = harness.device_report(devices, ctx.cell["chips"])

        # After the window, over the timed path at the timed size: what the
        # table holds once every acknowledged apply is in.
        final_rng = gen.rng_for(seed, 6)
        final_ids = [zipf.draw(final_rng, k) for _ in range(mix("final_lookups"))]
        final_rows = [emb.lookup(i) for i in final_ids]
    finally:
        for proc in workers:
            proc.kill()
            proc.wait()
        emb.close()
        for s in shards:
            s.close()
        dev.close()

    ctx.lap("final_lookups_and_close")
    # -- the reference, once the program's state is freed -----------------------
    plans = [ps_worker.plan(p) for p in params]
    rows_kept = mix("sample_rows")
    tracked = [setup_ids] + final_ids
    lookups = []                     # (t_start, t_end, ids, rows)
    for lg, pl in zip(logs, plans):
        for i, rows in lg["samples"]:
            ids = pl["ids"][i][:rows_kept]
            t = lg["times"][i]
            lookups.append((t[1] if not closed else t[0], t[1] if closed
                            else t[2], ids, rows))
            tracked.append(ids)
    ledger = reference.RowLedger(table, np.concatenate(tracked))
    ledger.apply(setup_ids, setup_grads, lr)
    ctx.check("setup_row_gap",
              _row_gap(ledger, setup_ids, setup_rows, [], ctx.control))
    applies = []                     # (ack, start, worker, step)
    if closed:
        blocks = [ps_worker.grad_block(seed, w, k, dim)
                  for w in range(n_workers)]
        for lg in logs:
            t = lg["times"]
            applies += [(t[i, 3], t[i, 2], lg["worker"], i)
                        for i in range(len(t)) if np.isfinite(t[i, 3])]
        applies.sort()
    lookups.sort(key=lambda l: l[0])

    def contribution(a):
        plan = plans[a[2]]
        return ledger.contribution(plan["ids"][a[3]], blocks[a[2]],
                                   plan["scales"][a[3]], lr)

    # Applies fold into the ledger in the order of their acknowledgements;
    # their contributions read only the ledger's ids, so a pool works them
    # out a chunk ahead. A sampled lookup is compared once every apply
    # acknowledged before it was sent is in.
    window_gap, li = 0.0, 0
    with ThreadPoolExecutor(8) as pool:
        for c0 in range(0, len(applies) + 1, 16):
            chunk = applies[c0:c0 + 16]
            ready = list(pool.map(contribution, chunk))
            for j in range(c0, c0 + len(chunk) + (c0 + 16 > len(applies))):
                ack = applies[j][0] if j < len(applies) else np.inf
                while li < len(lookups) and lookups[li][0] <= ack:
                    t0, t1, ids, rows = lookups[li]
                    maybe = [contribution(a) for a in applies[j:]
                             if a[1] < t1]
                    window_gap = max(window_gap, _row_gap(
                        ledger, ids, rows, [m for m in maybe if m[0].size],
                        ctx.control))
                    li += 1
                if j < len(applies):
                    ledger.fold(ready[j - c0])
    ctx.check("window_row_gap", window_gap)
    ctx.check("final_row_gap", max(
        _row_gap(ledger, i, r, [], ctx.control)
        for i, r in zip(final_ids, final_rows)))
    ctx.check("compiled_in_window", compiled_in_window, 0)

    ctx.lap("reference")
    # -- numbers ---------------------------------------------------------------------
    failed = sum(lg["failed"] for lg in logs)
    if any(lg["exhausted"] for lg in logs):
        raise SystemExit("benchmark: a worker ran out of planned steps; "
                         "raise max_steps_per_s_per_worker in the mix")
    times = np.concatenate([lg["times"] for lg in logs])
    times = times[times[:, 0] >= 0]           # sent or due inside the window
    attempted = len(times)
    series, e2e = {}, {}
    timeout_ms = float(mix("timeout_ms"))
    if closed:
        ok = np.isfinite(times[:, 3])
        acks = np.concatenate([lg["times"][:, 3] for lg in logs])
        acks = acks[np.isfinite(acks) & (acks > 0)]
        e2e["rows_per_s"] = k * int((acks <= ctx.seconds).sum()) / ctx.seconds
        series["lookup_ms"] = (times[ok, 1] - times[ok, 0]) * 1e3
        series["step_client_ms"] = ((times[ok, 1] - times[ok, 0]) +
                                    (times[ok, 3] - times[ok, 2])) * 1e3
        print("benchmark: acknowledged steps by 5 s", np.histogram(
            acks, np.arange(0, ctx.seconds + 5, 5))[0].tolist(),
            "lookup p50 %.0f ms apply p50 %.0f ms" % (
                np.median(series["lookup_ms"]),
                np.median((times[ok, 3] - times[ok, 2]) * 1e3)),
            file=sys.stderr)
    else:
        ok = np.isfinite(times[:, 2])
        lat = np.where(ok, (times[:, 2] - times[:, 0]) * 1e3, timeout_ms)
        e2e["lookup_p95_ms"] = harness.percentile(lat, 95)
        series["lookup_from_due_ms"] = lat
        series["generator_lag_ms"] = (times[ok, 1] - times[ok, 0]) * 1e3
        acks = times[ok, 2]
        lag = series["generator_lag_ms"]
        print("benchmark: %d lookups, from due p50 %.2f p95 %.2f p99 %.2f ms; "
              "generator lag p50 %.2f p99 %.2f max %.2f ms; by 5 s p95 %s" % (
                  len(lat), np.median(lat), e2e["lookup_p95_ms"],
                  harness.percentile(lat, 99), np.median(lag),
                  harness.percentile(lag, 99), lag.max(),
                  [round(harness.percentile(lat[(times[:, 0] >= a) &
                                                (times[:, 0] < a + 5)], 95), 2)
                   for a in range(0, int(ctx.seconds), 5)]), file=sys.stderr)
    for e in itertools.chain.from_iterable(lg["errors"] for lg in logs):
        print(f"benchmark: a call failed: {e}", file=sys.stderr)
    counters = {"calls_in_trace": int((acks <= traced_s).sum()),
                "ids_per_call": k, "dim": dim,
                "compiled_in_window": compiled_in_window}
    return harness.Outcome(
        end_to_end=e2e, attempted=attempted, failed=failed, setup_s=setup_s,
        device=device, counters=dict(counters, series=series),
        trace=window.reduce(ctx.cell["chips"]),
        counts={"calls": attempted, "samples_compared": len(lookups),
                "applies_replayed": len(applies)})
