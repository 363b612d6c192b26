"""Pipeline parallelism — the streaming-RPC activation pipe, compiled.

The reference's streaming RPC is an ordered, flow-controlled byte pipe
between stages (src/brpc/stream.cpp; BASELINE #4 uses it as the activation
pipe for 2-stage PP).  The TPU-native sibling keeps the same shape — stage
i pushes activations to stage i+1 — but compiles the pipe into a
``lax.ppermute`` ring over the 'pp' mesh axis with GPipe-style microbatch
scheduling: at tick t, stage s computes microbatch (t - s) while the
transfer of its previous output overlaps (scaling-book pipelining recipe).
The RPC-tier pipe (cpp/rpc/stream.*) stays the cross-host DCN fallback.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[jax.Array, jax.Array], jax.Array],
    stage_params: jax.Array,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pp",
    microbatches: int | None = None,
) -> jax.Array:
    """Runs ``microbatches`` slices of ``x`` through all pipeline stages.

    stage_params: pytree whose leaves have a leading [n_stages] dim, sharded
    over ``axis`` (each device holds its stage's params).
    stage_fn(params_for_stage, microbatch) -> microbatch (same shape).
    x: [M, ...] microbatched input, M divisible by ``microbatches``;
    returns the fully-processed x.

    Schedule: the classic loop — (M + S - 1) ticks; at each tick every
    stage computes one microbatch then passes it right (the activation
    "StreamWrite"); stage 0 feeds fresh microbatches, stage S-1 banks
    results. Bubble fraction (S-1)/(M+S-1), amortized by M.
    """
    n = mesh.shape[axis]
    mb = microbatches or n

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    def _pipe(params_blk, x_blk):
        # params_blk: stage params with leading dim 1; x_blk: [M/n, ...]
        params = jax.tree_util.tree_map(lambda p: p[0], params_blk)
        stage = lax.axis_index(axis)
        # Gather the full microbatch set on every stage; stage 0 is the
        # feeder (cheap at microbatch granularity; the steady-state traffic
        # is the neighbour ppermute below).
        x_all = lax.all_gather(x_blk, axis, tiled=True)
        m_total = x_all.shape[0]
        per = m_total // mb  # rows per microbatch
        shaped = x_all.reshape(mb, per, *x_all.shape[1:])

        right = [(i, (i + 1) % n) for i in range(n)]
        ticks = mb + n - 1

        def tick(carry, t):
            inflight, done = carry
            # stage 0 injects microbatch t (or zeros past the end)
            fresh = lax.dynamic_index_in_dim(
                shaped, jnp.minimum(t, mb - 1), keepdims=False
            )
            cur = jnp.where(stage == 0, fresh, inflight)
            active = (t - stage >= 0) & (t - stage < mb)
            out = stage_fn(params, cur)
            out = jnp.where(active, out, cur)
            # last stage banks microbatch (t - (n-1)) when it was active
            bank_idx = t - (n - 1)
            done = lax.cond(
                (stage == n - 1) & (bank_idx >= 0) & (bank_idx < mb),
                lambda d: lax.dynamic_update_index_in_dim(
                    d, out, jnp.maximum(bank_idx, 0), 0
                ),
                lambda d: d,
                done,
            )
            # the activation pipe: pass right (stage S-1 → 0 link is idle
            # data, ignored by stage 0 which injects fresh input)
            inflight = lax.ppermute(out, axis, right)
            return (inflight, done), None

        zero_mb = jnp.zeros_like(shaped[0])
        done0 = jnp.zeros_like(shaped)
        (_, done), _ = lax.scan(
            tick, (zero_mb, done0), jnp.arange(ticks)
        )
        full = done.reshape(m_total, *x_all.shape[1:])
        # only stage n-1 banked results; psum of masked copies broadcasts
        # them (ppermute can't fan out one source to many destinations)
        full = lax.psum(
            jnp.where(stage == n - 1, full, jnp.zeros_like(full)), axis
        )
        per_dev = m_total // n
        return lax.dynamic_slice_in_dim(
            full, stage * per_dev, per_dev, axis=0
        )

    return _pipe(stage_params, x)
