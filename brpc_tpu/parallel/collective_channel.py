"""CollectiveChannel — the ParallelChannel contract compiled onto ICI.

The reference fans one call out to N sub-channels with a per-sub
``CallMapper`` (request slicing) and folds replies through a
``ResponseMerger`` (src/brpc/parallel_channel.h:94,127,185).  On TPU the
same contract has a *compiled* fast path: the "sub-channels" are mesh
devices, the mapper is a sharding constraint, and the merger is an XLA
collective riding ICI (psum / all_gather / reduce_scatter / ppermute) —
SURVEY.md §2.7/§5.9.  The RPC tier (cpp/cluster/parallel_channel.*) remains
the partial-failure-tolerant DCN path; this module is the bulk-synchronous
ICI tier, and the BASELINE "ParallelChannel → 8-chip ICI AllReduce" metric
is ``CollectiveChannel.all_reduce``.

Everything here is shard_map-based: callers hand in global arrays with any
sharding; each op pins the input layout, runs the collective per shard, and
returns the merged result.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from brpc_tpu import obs
from jax.sharding import Mesh, PartitionSpec as P


def _record_collective(op: str, x) -> None:  # lint: allow-trace-impure
    """Per-collective call + byte counters (``collective_<op>_calls`` /
    ``collective_<op>_bytes``).  These fire when the python method runs:
    eagerly that is once per collective; under ``jax.jit`` it is once per
    trace — i.e. they count collective *programs* built, the compile-side
    view of ICI traffic (sizes still come from the abstract value, which
    tracers carry).  The pragma declares exactly that intent to the
    ``trace-purity`` check: running once at trace time IS the design."""
    if not obs.enabled():
        return
    obs.counter(f"collective_{op}_calls").add(1)
    obs.counter(f"collective_{op}_bytes").add(
        int(np.prod(np.shape(x))) * np.dtype(x.dtype).itemsize)


class CollectiveChannel:
    """Fan-out/merge primitives over one mesh axis.

    ``axis`` names the "sub-channel" dimension (the ParallelChannel's
    AddChannel list); ``mesh`` supplies the devices. All methods are
    jittable and differentiable.
    """

    def __init__(self, mesh: Mesh, axis: str = "dp"):
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis

    @property
    def num_channels(self) -> int:
        return self.mesh.shape[self.axis]

    # ---- ParallelChannel analogs (fan-out + ResponseMerger) ----

    def all_reduce(self, x: jax.Array, op: str = "sum") -> jax.Array:
        """Every shard contributes, every shard receives the merge.

        The reference shape: ParallelChannel broadcast + additive merger.
        x is sharded over ``axis`` on its leading dim; the result is the
        elementwise reduction, replicated.
        """
        _record_collective("all_reduce", x)
        reducer = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin}[op]

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=P(self.axis),
            out_specs=P(),
            check_vma=False,
        )
        def _ar(shard):
            return reducer(jnp.sum(shard, axis=0), self.axis)

        return _ar(x)

    def all_reduce_inplace(self, x: jax.Array, op: str = "sum") -> jax.Array:
        """AllReduce of replicated-shape tensors (grad sync): x has the SAME
        shape on every shard; result is the cross-shard reduction."""
        _record_collective("all_reduce_inplace", x)
        reducer = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin}[op]

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=P(*[None] * x.ndim),
            out_specs=P(*[None] * x.ndim),
            check_vma=False,
        )
        def _ar(shard):
            return reducer(shard, self.axis)

        return _ar(x)

    def all_gather(self, x: jax.Array, tiled: bool = True) -> jax.Array:
        """Each shard's slice, concatenated everywhere (fan-out + concat
        merger — the reference's default "append responses in channel
        order")."""
        _record_collective("all_gather", x)

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=P(self.axis),
            out_specs=P(),
            check_vma=False,
        )
        def _ag(shard):
            return lax.all_gather(shard, self.axis, tiled=True)

        return _ag(x)

    def reduce_scatter(self, x: jax.Array) -> jax.Array:
        """Sum across shards, then each shard keeps its slice (the sharded
        merger — PartitionChannel's write path)."""
        _record_collective("reduce_scatter", x)

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=P(*[None] * x.ndim),
            out_specs=P(self.axis),
            check_vma=False,
        )
        def _rs(full):
            return lax.psum_scatter(full, self.axis, scatter_dimension=0,
                                    tiled=True)

        return _rs(x)

    def broadcast(self, x: jax.Array, root: int = 0) -> jax.Array:
        """Root shard's value everywhere (SelectiveChannel pick-one +
        replicate)."""
        _record_collective("broadcast", x)

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=P(self.axis),
            out_specs=P(),
            check_vma=False,
        )
        def _bc(shard):
            full = lax.all_gather(shard, self.axis, tiled=True)
            n = self.num_channels
            return lax.dynamic_slice_in_dim(full, root * (full.shape[0] // n),
                                            full.shape[0] // n, axis=0)

        return _bc(x)

    def shift(self, x: jax.Array, offset: int = 1) -> jax.Array:
        """Neighbour exchange over the ring (ppermute) — the streaming-RPC/
        cascade analog; building block of ring attention and PP."""
        _record_collective("shift", x)
        n = self.num_channels
        perm = [(i, (i + offset) % n) for i in range(n)]

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=P(self.axis),
            out_specs=P(self.axis),
            check_vma=False,
        )
        def _sh(shard):
            return lax.ppermute(shard, self.axis, perm)

        return _sh(x)

    def map_reduce(
        self,
        fn: Callable[[jax.Array], jax.Array],
        x: jax.Array,
        op: str = "sum",
    ) -> jax.Array:
        """CallMapper + ResponseMerger in one: apply ``fn`` per shard
        (mapper), reduce results across shards (merger)."""
        _record_collective("map_reduce", x)
        reducer = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin}[op]

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=P(self.axis),
            out_specs=P(),
            check_vma=False,
        )
        def _mr(shard):
            return reducer(fn(shard), self.axis)

        return _mr(x)
