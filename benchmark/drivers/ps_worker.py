"""One load-generating child of the embedding-shard cells.

Touches neither JAX nor the device client: it holds a ``RemoteEmbedding``
on the shards' loopback addresses, so it shares no interpreter lock with
the servers. The parent writes one JSON line of parameters to its stdin,
reads ``ready``, writes ``go <monotonic start>``, and reads back one
length-prefixed pickle of the worker's log (only bytes this benchmark
wrote are ever unpickled).

The worker sends from ``warm_s`` seconds before the window's start (that
traffic is set-up: it takes the path's first-use costs out of the window,
and the reference folds its applies in like any other); times in the log
are relative to the window's start.

``mode`` is the mix's loop: ``closed`` — each step looks its ids up, makes
gradients and applies them, acknowledged, before the next; ``open`` — each
lookup is sent when its Poisson due time comes, whatever the last one did.
"""

from __future__ import annotations

import json
import os
import pickle
import struct
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import gen  # noqa: E402


def grad_block(seed: int, worker: int, k: int, dim: int) -> np.ndarray:
    """The worker's block of float32 gradient rows, from the seed."""
    return gen.rng_for(seed, 3, worker).standard_normal(
        (k, dim), dtype=np.float32)


def plan(p: dict) -> dict:
    """Everything the worker will send, drawn from the seed before the
    window: ids per call, per-step gradient scales or due times, and which
    calls keep their rows for the comparison."""
    rng = gen.rng_for(p["seed"], 4, p["worker"])
    zipf = gen.ZipfIds(p["vocab"], p["zipf_s"], p["seed"], p["stripes"])
    k = p["ids_per_call"]
    if p["mode"] == "open":
        due = gen.poisson_due_times(rng, p["rate_per_worker"],
                                    p["warm_s"] + p["seconds"]) - p["warm_s"]
        n = due.size
    else:
        due = None
        n = int(p["max_calls"])
    ids = zipf.draw(rng, n * k).reshape(n, k)
    scales = (0.5 + rng.random(n)).astype(np.float32)
    think = (0.5 + rng.random(n)) * p.get("think_ms", 0) * 1e-3
    keep = rng.random(n) < p["sample_share"]
    keep[np.flatnonzero(keep)[p["sample_max"]:]] = False
    return {"due": due, "ids": ids, "scales": scales, "keep": keep,
            "think": think}


def main() -> int:
    p = json.loads(sys.stdin.readline())
    sys.path.insert(0, p["root"])
    from brpc_tpu.ps_remote import RemoteEmbedding

    w = plan(p)
    k, dim = p["ids_per_call"], p["dim"]
    emb = RemoteEmbedding(p["addresses"], p["vocab"], dim,
                          timeout_ms=p["timeout_ms"])
    closed = p["mode"] == "closed"
    if closed:
        block = grad_block(p["seed"], p["worker"], k, dim)
        grads = np.empty_like(block)
    emb.lookup(w["ids"][0])                 # connect; the call's first use
    sys.stdout.write("ready\n")
    sys.stdout.flush()
    t_start = float(sys.stdin.readline().split()[1])
    t_end = t_start + p["seconds"]
    n = w["ids"].shape[0]
    times = np.full((n, 4), np.nan)
    samples, errors, failed, done = [], [], 0, 0
    rows_kept = p["sample_rows"]
    while time.monotonic() < t_start - p["warm_s"]:
        time.sleep(0.0005)
    for i in range(n):
        ids = w["ids"][i]
        try:
            if closed:
                if time.monotonic() >= t_end:
                    break
                t0 = time.monotonic()
                rows = emb.lookup(ids)
                t1 = time.monotonic()
                np.multiply(block, w["scales"][i], out=grads)
                if w["think"][i] > 0:       # the trainer's own dense step
                    time.sleep(w["think"][i])
                t2 = time.monotonic()
                emb.apply_gradients(ids, grads)
                t3 = time.monotonic()
                times[i] = (t0, t1, t2, t3)
            else:
                due = t_start + w["due"][i]
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                t0 = time.monotonic()
                rows = emb.lookup(ids)
                t1 = time.monotonic()
                times[i] = (due, t0, t1, t1)
            if w["keep"][i]:
                samples.append((i, rows[:rows_kept].copy()))
        except Exception as e:          # a failed call is counted, not fatal
            failed += 1
            if len(errors) < 5:
                errors.append(repr(e)[:300])
            times[i, 0] = time.monotonic() if closed else t_start + w["due"][i]
        done += 1
    emb.close()
    out = pickle.dumps({"worker": p["worker"], "times": times[:done] - t_start,
                        "failed": failed, "errors": errors,
                        "samples": samples, "exhausted": closed and done == n})
    sys.stdout.buffer.write(struct.pack("<q", len(out)))
    sys.stdout.buffer.write(out)
    sys.stdout.buffer.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
