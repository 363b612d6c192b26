"""tpu_ps — parameter-server fabric: sharded embedding serving + grad sync.

The BASELINE north-star app: "bRPC param-server serving Llama-3-8B embedding
shards, allreduce grads over v5e-16".  The reference reaches this shape with
PartitionChannel (shard-addressed calls, src/brpc/partition_channel.h:75)
plus ParallelChannel fan-out for reduction (SURVEY.md §2.7).  TPU-native,
the intra-pod tier compiles to collectives:

- the embedding table lives row-sharded over a 'ps' mesh axis (the
  PartitionChannel "i/N" tag == the mesh coordinate);
- ``lookup`` is the shard-addressed read: every shard gathers its local
  rows, a psum merges (exactly one shard owns each row);
- ``apply_gradients`` is the sharded write: scatter-add lands on the owning
  shard only — no cross-shard traffic beyond the ids broadcast;
- worker gradient sync is CollectiveChannel.all_reduce over 'dp'.

The cross-host / DCN tier (many pods) runs the same contract over the
native RPC PartitionChannel (cpp/cluster/partition_channel.*).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class EmbeddingShards(NamedTuple):
    """A [vocab, dim] table row-sharded over ``axis``.

    Registered as a pytree with (vocab, dim, axis) static so instances pass
    straight through jit/grad.
    """

    table: jax.Array
    vocab: int
    dim: int
    axis: str


jax.tree_util.register_pytree_node(
    EmbeddingShards,
    lambda e: ((e.table,), (e.vocab, e.dim, e.axis)),
    lambda aux, children: EmbeddingShards(children[0], *aux),
)


def create_embedding(
    key: jax.Array,
    vocab: int,
    dim: int,
    mesh: Mesh,
    axis: str = "ps",
    scale: float = 0.02,
    dtype=jnp.float32,
) -> EmbeddingShards:
    if vocab % mesh.shape[axis] != 0:
        raise ValueError(
            f"vocab {vocab} not divisible by {axis}={mesh.shape[axis]}"
        )
    table = jax.random.normal(key, (vocab, dim), dtype) * scale
    table = jax.device_put(table, NamedSharding(mesh, P(axis, None)))
    return EmbeddingShards(table, vocab, dim, axis)


def lookup(emb: EmbeddingShards, ids: jax.Array, mesh: Mesh) -> jax.Array:
    """Shard-addressed read: ids [...] -> rows [..., dim].

    Every shard contributes its owned rows (zeros elsewhere); one psum
    merges — the PartitionChannel broadcast-read with additive merger.
    """
    axis = emb.axis
    n = mesh.shape[axis]
    rows_per = emb.vocab // n

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
        check_vma=False,
    )
    def _lookup(shard, flat_ids):
        base = lax.axis_index(axis) * rows_per
        local = flat_ids - base
        mine = (local >= 0) & (local < rows_per)
        safe = jnp.clip(local, 0, rows_per - 1)
        got = shard[safe]  # [N, dim]
        got = jnp.where(mine[:, None], got, 0)
        return lax.psum(got, axis)

    flat = ids.reshape(-1)
    out = _lookup(emb.table, flat)
    return out.reshape(*ids.shape, emb.dim)


def apply_gradients(
    emb: EmbeddingShards,
    ids: jax.Array,
    grads: jax.Array,
    mesh: Mesh,
    lr: float = 1e-2,
) -> EmbeddingShards:
    """Sharded write: scatter-add -lr*grads onto owning shards only."""
    axis = emb.axis
    n = mesh.shape[axis]
    rows_per = emb.vocab // n

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(), P()),
        out_specs=P(axis, None),
        check_vma=False,
    )
    def _apply(shard, flat_ids, flat_grads):
        base = lax.axis_index(axis) * rows_per
        local = flat_ids - base
        mine = (local >= 0) & (local < rows_per)
        safe = jnp.where(mine, local, 0)
        contrib = jnp.where(mine[:, None], flat_grads, 0)
        return shard.at[safe].add(-lr * contrib)

    flat_ids = ids.reshape(-1)
    flat_grads = grads.reshape(-1, emb.dim)
    new_table = _apply(emb.table, flat_ids, flat_grads)
    return emb._replace(table=new_table)


def make_ps_train_step(emb_axis: str, dp_axis: str, mesh: Mesh, lr: float):
    """The BASELINE #5 loop: embedding lookup → toy loss → grad allreduce
    over dp → sharded embedding update. Returns a jittable step:
    (EmbeddingShards, ids [B,T], targets [B,T,dim]) -> (EmbeddingShards, loss).

    ids/targets are replicated here (each dp worker's slice handled by the
    caller's batch sharding); the demonstrative loss is MSE to targets.
    """

    def step(emb: EmbeddingShards, ids, targets):
        def loss_fn(table):
            e = emb._replace(table=table)
            pred = lookup(e, ids, mesh)
            return jnp.mean((pred - targets) ** 2)

        loss, grad_rows = jax.value_and_grad(
            lambda table: loss_fn(table)
        )(emb.table)
        # grad wrt the full table; turn into per-id dense grads via lookup
        # of the gradient rows — cheaper path: direct sharded SGD on the
        # table gradient (already laid out like the table).
        new_table = emb.table - lr * grad_rows
        return emb._replace(table=new_table), loss

    return step
