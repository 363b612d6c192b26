"""Driver of the collective cell: ``CollectiveChannel.all_reduce`` (one
``psum`` of each chip's own shard per call) over the mesh of the cell's
chips, back to back. The loop, the timer and the bandwidth formula live
here, not in the program."""

from __future__ import annotations

import sys
import time

import numpy as np

import harness
import reference
import work


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = ctx.cell["chips"]
    devices = harness.jax_devices(n, ctx.dry)[:n]
    ctx.lap("jax_devices")
    sys.path.insert(0, harness.ROOT)
    from brpc_tpu.parallel import CollectiveChannel, make_mesh

    mesh = make_mesh({"dp": n}, devices=devices)
    elems = ctx.mix("bytes_per_chip") // 4
    sharded = NamedSharding(mesh, P("dp"))
    # The key is an argument, not a constant of the program: every seed
    # finds the same programs in the cache.
    x = jax.jit(lambda k: jax.random.normal(k, (n, elems), jnp.float32),
                out_shardings=sharded)(reference.seed_key(ctx.seed))
    chan = CollectiveChannel(mesh, "dp")
    all_reduce = jax.jit(chan.all_reduce).lower(x).compile()
    out = all_reduce(x)
    out.block_until_ready()
    ctx.lap("shards_from_seed_and_compile")

    window = harness.TracedWindow(ctx.trace and not ctx.dry)
    window.start()
    setup_s = time.monotonic() - ctx.t_process
    calls, calls_in_trace, elapsed, out = harness.back_to_back(
        lambda: all_reduce(x), ctx.seconds, 1, window,
        min(ctx.seconds, ctx.mix("trace_seconds", ctx.seconds)), ctx.spans)
    ctx.lap("window")
    device = harness.device_report(devices, n)

    # The last call's answer on every chip against the plain float64 sum of
    # the benchmark's own shards, over a sample of column blocks drawn from
    # the seed (the first and the last among them), in units of the bound
    # within which any order of the same n float32 terms agrees.
    block = min(elems, ctx.mix("check_block_elems"))
    starts = {0, elems - block}
    rng = np.random.default_rng(ctx.seed)
    while len(starts) < min(ctx.mix("check_blocks"), elems // block):
        starts.add(int(rng.integers(0, elems // block)) * block)
    worst = 0.0
    for lo in sorted(starts):
        cols = np.asarray(x[:, lo:lo + block])
        want = cols.astype(np.float64).sum(axis=0)
        bound = np.maximum(n * reference.EPS32 * np.abs(cols).sum(axis=0),
                           1e-45)
        for shard in out.addressable_shards:
            got = np.asarray(shard.data[lo:lo + block])
            if ctx.control == "lowprec":
                got = reference.to_bfloat16(want.astype(np.float32))
            worst = max(worst, float(np.max(np.abs(got - want) / bound)))
    ctx.check("sum_gap", worst)
    ctx.lap("reference")
    bus = work.all_reduce(elems * 4, n)["bus_bytes"]
    return harness.Outcome(
        end_to_end={"allreduce_gbps_per_chip": bus * calls / elapsed / 1e9},
        attempted=calls, failed=0, setup_s=setup_s, device=device,
        counters={"calls_in_trace": calls_in_trace, "series": {}},
        trace=window.reduce(n),
        counts={"calls": calls, "bytes_per_chip": elems * 4})
