"""Exchange data plane: hash repartitioning over the device mesh.

Reference blueprint: PartitionedOutputOperator -> PagePartitioner
(operator/output/PagePartitioner.java:134, the partitionPage hot loop) on the
producer and ExchangeOperator/DirectExchangeClient on the consumer (SURVEY.md
§3.3). Trino moves pages worker-to-worker over pull-based HTTP with ack tokens;
here a REMOTE REPARTITION exchange inside a pod is one fused XLA program:

    partition-id kernel (hash % N)  ->  bucket sort  ->  lax.all_to_all (ICI)

All shapes static: each shard sends exactly ``bucket_cap`` rows to every peer
(padding rides along as inactive rows). After all_to_all each shard holds the
rows whose keys hash to it — the exact post-shuffle layout Trino's
FIXED_HASH_DISTRIBUTION produces (SystemPartitioningHandle.java:49).

These functions run *inside* shard_map: arrays are per-shard blocks.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import kernels as K
from ..ops.repartition import hash_key_columns, partition_ids  # noqa: F401
from ..spi.page import Column, Page

# partition_ids / hash_key_columns moved to ops/repartition.py (the device
# repartition epilogue is their primary consumer now; this module re-exports
# them so the mesh tier and existing imports keep working).


def all_to_all_page(
    page: Page,
    target: jnp.ndarray,
    num_partitions: int,
    axis_name: str,
    bucket_cap: Optional[int] = None,
) -> Tuple[Page, jnp.ndarray]:
    """Repartition a per-shard Page so row i lands on shard ``target[i]``.

    Static-shape strategy: sort rows by destination, slot each destination's
    rows into a fixed-size bucket (capacity ``bucket_cap``), all_to_all the
    bucket axis, then flatten. The default bucket_cap (full shard capacity) is
    safe for any skew; with a smaller cap, overflowing rows CANNOT be silently
    dropped — the second return value is the psum'd global count of rows that
    did not fit, which callers MUST host-check and, if nonzero, re-run with a
    larger cap (ref: Trino degrades to backpressure, never to wrong answers —
    OutputBufferMemoryManager / SkewedPartitionRebalancer.java).
    """
    cap = page.capacity
    if bucket_cap is None:
        bucket_cap = cap  # safe for any skew; tune down when stats allow

    # order rows by (destination, active-last) so each destination's rows are
    # contiguous; compute each row's rank within its destination bucket
    dest_key = jnp.where(page.active, target.astype(jnp.int64), jnp.int64(num_partitions))
    perm = jnp.argsort(dest_key)
    dest_s = dest_key[perm]
    active_s = page.active[perm]
    # rank within destination: position - first-position-of-destination
    idx = jnp.arange(cap)
    is_first = jnp.zeros(cap, dtype=bool).at[0].set(True) | (dest_s != jnp.roll(dest_s, 1))
    anchor = jax.lax.cummax(jnp.where(is_first, idx, 0))
    rank = idx - anchor
    # slot in the (num_partitions, bucket_cap) send matrix; overflow -> dropped
    slot = dest_s * bucket_cap + rank
    in_range = active_s & (rank < bucket_cap) & (dest_s < num_partitions)
    slot = jnp.where(in_range, slot, num_partitions * bucket_cap)

    def scatter_col(data_s: jnp.ndarray) -> jnp.ndarray:
        out = jnp.zeros((num_partitions * bucket_cap + 1,) + data_s.shape[1:], dtype=data_s.dtype)
        out = out.at[slot].set(data_s, mode="drop")
        return out[:-1].reshape((num_partitions, bucket_cap) + data_s.shape[1:])

    sent_active = scatter_col(in_range.astype(jnp.bool_))
    cols = []
    for c in page.columns:
        send_data = scatter_col(c.data[perm])
        send_valid = scatter_col(c.valid[perm] & in_range)
        recv_data = jax.lax.all_to_all(send_data, axis_name, 0, 0, tiled=False)
        recv_valid = jax.lax.all_to_all(send_valid, axis_name, 0, 0, tiled=False)
        cols.append(
            Column(
                c.type,
                recv_data.reshape((num_partitions * bucket_cap,) + c.data.shape[1:]),
                recv_valid.reshape(num_partitions * bucket_cap),
                c.dictionary,
            )
        )
    recv_active = jax.lax.all_to_all(sent_active, axis_name, 0, 0, tiled=False)
    overflow = jnp.sum(
        (active_s & (dest_s < num_partitions) & (rank >= bucket_cap)).astype(jnp.int64)
    )
    overflow = jax.lax.psum(overflow, axis_name)
    return Page(tuple(cols), recv_active.reshape(num_partitions * bucket_cap)), overflow


def bucket_demand(
    page: Page, target: jnp.ndarray, num_partitions: int, axis_name: str
) -> jnp.ndarray:
    """The bucket capacity this exchange needs: the most rows any shard holds
    for one destination (the same on every shard). The capacity an
    overflowing exchange is retried at. In 32 bits: the TPU reduces 64-bit
    integers by sum alone, and a shard's rows fit."""
    per_dest = jnp.zeros(num_partitions, dtype=jnp.int32).at[target].add(
        page.active.astype(jnp.int32), mode="drop"
    )
    return jax.lax.pmax(jnp.max(per_dest), axis_name).astype(jnp.int64)


def hash_targets(
    page: Page, key_indexes: Sequence[int], num_partitions: int
) -> jnp.ndarray:
    """Row -> destination shard by the hash of the key columns
    (FIXED_HASH_DISTRIBUTION); no keys: everything to shard 0."""
    keys = hash_key_columns([page.columns[i] for i in key_indexes])
    if not keys:
        return jnp.zeros(page.capacity, dtype=jnp.int32)
    return partition_ids(keys, num_partitions)


def repartition_by_keys(
    page: Page,
    key_indexes: Sequence[int],
    num_partitions: int,
    axis_name: str,
    bucket_cap: Optional[int] = None,
) -> Tuple[Page, jnp.ndarray]:
    """Hash-repartition a page by key columns (FIXED_HASH_DISTRIBUTION).
    ``bucket_cap`` is the caller's: the mesh tier sizes it from the
    producer's page as traced, so a narrowed producer makes a narrow exchange.

    Returns (page, overflow): see all_to_all_page for the overflow contract."""
    target = hash_targets(page, key_indexes, num_partitions)
    return all_to_all_page(page, target, num_partitions, axis_name, bucket_cap)


def range_targets(
    page: Page,
    key_index: int,
    ascending: bool,
    nulls_first: bool,
    num_partitions: int,
    axis_name: str,
    samples_per_shard: int = 64,
) -> jnp.ndarray:
    """Row -> destination shard by the leading sort key: shard i receives keys
    below shard i+1's — local sort per shard then yields GLOBAL order when shards
    are concatenated in shard-index order. This is the distributed sort's
    shuffle (ref: docs admin/dist-sort.md + MergeOperator.java — Trino merges
    sorted streams instead; on a mesh, sampled range boundaries + all_to_all
    keep everything inside one program with no sequential merge).

    Boundaries come from a per-shard sample of ``samples_per_shard`` local
    quantiles, all_gathered and re-quantiled — the classic sample sort.
    Bucketing is a deterministic function of the key, so equal keys colocate
    (required: secondary sort keys only order rows WITHIN a shard). Skewed
    boundaries can only overflow a bucket, which the caller's overflow retry
    already handles."""
    c = page.columns[key_index]
    # dictionary codes ARE the order keys: dictionaries are sorted, and the
    # mesh tier unifies each column's dictionary across shards before
    # sharding, so code order == value order globally. (value_keys() — the
    # hashing LUT — is a content fingerprint and NOT order-preserving.)
    key = K.encode_sort_column(c.data, c.valid, ascending, nulls_first)
    skey = jnp.sort(jnp.where(page.active, key, jnp.int64(K.INT64_MAX)))
    cnt = jnp.sum(page.active.astype(jnp.int64))
    pos = (jnp.arange(samples_per_shard, dtype=jnp.int64) * cnt) // samples_per_shard
    sample = skey[jnp.clip(pos, 0, page.capacity - 1)]
    allsamp = jax.lax.all_gather(sample, axis_name, axis=0, tiled=True)
    g = jnp.sort(allsamp)
    boundaries = g[jnp.arange(1, num_partitions) * samples_per_shard]
    return jnp.sum(
        (key[:, None] >= boundaries[None, :]).astype(jnp.int32), axis=1
    )


def repartition_by_range(
    page: Page,
    key_index: int,
    ascending: bool,
    nulls_first: bool,
    num_partitions: int,
    axis_name: str,
    bucket_cap: Optional[int] = None,
    samples_per_shard: int = 64,
) -> Tuple[Page, jnp.ndarray]:
    """Range-repartition by the leading sort key (``range_targets``), then
    the all_to_all; ``bucket_cap`` as in ``repartition_by_keys``.

    Returns (page, overflow): see all_to_all_page for the overflow contract."""
    target = range_targets(
        page, key_index, ascending, nulls_first, num_partitions, axis_name,
        samples_per_shard,
    )
    return all_to_all_page(page, target, num_partitions, axis_name, bucket_cap)


