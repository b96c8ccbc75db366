"""Device-side repartition epilogue: hash -> partition id -> stable cosort.

Reference blueprint: operator/output/PagePartitioner.java:134 (partitionPage)
and "Query Processing on Tensor Computation Runtimes" — shuffle preparation
should stay in the tensor runtime. The old exchange edge round-tripped every
page through a fully host-side path: whole-page D2H, numpy row hashing, then
ONE boolean-selection pass per output partition (n passes over the data) and a
fresh Page object per partition. This module appends a compiled epilogue to
the producing fragment's program instead:

    splitmix64-style key hash  ->  partition id  ->  stable cosort by id
                               ->  per-partition offsets/counts

so ONE device-to-host transfer yields a partition-CONTIGUOUS page: partition
p's rows are ``[offsets[p], offsets[p] + counts[p])`` of the sorted buffers,
in their original relative order (the cosort is stable), with inactive rows
sorted past the end. Serde then slices frames straight out of the contiguous
buffers (runtime/serde.serialize_page_slices) — no per-partition host
selection passes, no per-partition Page materialization.

The partition id is THE engine-wide repartition rule: the same 64-bit mix as
the mesh tier (parallel/exchange.py re-exports from here) and the host mirror
(spi/host_pages.hash_partition_host), with the same NULL sentinel, float
order-key unfold, and dictionary value-key translation — producers on any
tier route the same key to the same consumer.

Static-shape discipline: the epilogue jit-caches on (n_parts, key indexes,
page layout). Upstream operators already emit canonical 4x-spaced capacity
classes (runtime/ooc._shape_class), so the epilogue adds a handful of
compiles per fragment, never one per bucket.
"""

from __future__ import annotations

from functools import partial

from ..runtime import kernelcost
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import knobs
from ..spi.page import Column, Page
from . import kernels as K

DEVICE_REPARTITION_ENV = "TRINO_TPU_DEVICE_REPARTITION"


def device_repartition_enabled() -> bool:
    """Env kill-switch (default ON): the bit-identity tests
    flip this to force the legacy host path."""
    return knobs.env_flag(DEVICE_REPARTITION_ENV, True)


def partition_ids(
    key_cols: Sequence[Tuple[jnp.ndarray, jnp.ndarray]], num_partitions: int
) -> jnp.ndarray:
    """Row -> destination partition (the PagePartitioner hash).

    ``key_cols`` are (data, valid) pairs: NULL keys normalize to a sentinel
    before hashing so the whole NULL group lands on one consumer partition
    (hashing the undefined payload under a NULL would split it — duplicate
    NULL-key rows after FINAL aggregation). Floats hash via the order_key bit
    unfold. Host mirror: spi/host_pages.hash_partition_host — keep in sync.

    Uses the same 64-bit mix as the join/group hash so bucketed joins stay
    aligned across exchanges.
    """
    acc = jnp.uint64(0x9E3779B97F4A7C15)
    for d, v in key_cols:
        k = jnp.where(v, K.order_key(d), jnp.int64(K.INT64_MAX))
        x = k.astype(jnp.uint64)
        x = (x ^ (x >> 33)) * jnp.uint64(0xFF51AFD7ED558CCD)
        x = (x ^ (x >> 33)) * jnp.uint64(0xC4CEB9FE1A85EC53)
        x = x ^ (x >> 33)
        acc = (acc ^ x) * jnp.uint64(0x100000001B3)
    return (acc % jnp.uint64(num_partitions)).astype(jnp.int32)


def hash_key_columns(cols: Sequence[Column]):
    """Columns -> (data, valid) pairs for partition hashing. Dictionary-coded
    columns map through their content-stable value keys (a static LUT) —
    codes are dictionary-LOCAL, and two producers of the same exchange can
    carry different vocabularies, so hashing raw codes would route the same
    string to different shards (silent lost join matches). Mirrors the host
    tier's Dictionary.value_keys() hashing in spi/host_pages.py."""
    out = []
    for c in cols:
        d = c.data
        if c.dictionary is not None:
            lut = jnp.asarray(c.dictionary.value_keys())
            d = lut[jnp.clip(c.data, 0, lut.shape[0] - 1)]
        out.append((d, c.valid))
    return out


def supports_device_repartition(page: Page) -> bool:
    """Scalar and multi-lane columns ride the epilogue; nested layouts
    (array/map/row: children/lengths) fall back to the host path — the wire
    serde has no frame encoding for them either."""
    return all(
        not c.children and c.lengths is None and c.elem_valid is None
        for c in page.columns
    )


def _partition_dest(n_parts: int, key_idx: Tuple[int, ...], page: Page):
    """Traced: per-row destination — partition id for active rows,
    ``n_parts`` (the discard tail) for inactive ones. Pure elementwise work:
    it fuses into the producing fragment's program on any backend."""
    cap = page.capacity
    keys = hash_key_columns([page.columns[i] for i in key_idx])
    if not keys:
        # no keys: every row to partition of hash(0) — the host rule
        keys = [(jnp.zeros(cap, dtype=jnp.int64), jnp.ones(cap, dtype=jnp.bool_))]
    target = partition_ids(keys, n_parts)
    return jnp.where(page.active, target, jnp.int32(n_parts))


def _repartition_epilogue(n_parts: int, key_idx: Tuple[int, ...], page: Page):
    """The fully in-program epilogue (TPU tier). Returns (sorted_page,
    offsets, counts): partition p's rows occupy ``sorted_page[offsets[p] :
    offsets[p] + counts[p]]`` in original relative order; inactive rows sort
    to the tail (destination ``n_parts``). Dictionaries ride the jit cache as
    static aux (page layout), so the value-key LUTs fold into the program as
    constants. The rows follow the stable sort of their destinations in one
    gather (``K.cosort``: a three-operand sort and ``K.gather_rows``)."""
    dest = _partition_dest(n_parts, key_idx, page)
    counts = jnp.bincount(dest, length=n_parts + 1)[:n_parts].astype(jnp.int64)
    offsets = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int64), jnp.cumsum(counts)[:-1]]
    )
    if any(c.data.ndim > 1 for c in page.columns):
        # multi-lane payloads (int128 limbs, digests) can't ride lax.sort
        # operands of mismatched trailing shape — permutation-gather instead
        perm = jnp.argsort(dest, stable=True)
        cols = tuple(
            Column(c.type, c.data[perm], c.valid[perm], c.dictionary)
            for c in page.columns
        )
        return Page(cols, page.active[perm]), offsets, counts
    payloads: List[jnp.ndarray] = []
    for c in page.columns:
        payloads.append(c.data)
        payloads.append(c.valid)
    payloads.append(page.active)
    _, sorted_payloads = K.cosort([dest.astype(jnp.int64)], payloads)
    cols = tuple(
        Column(c.type, sorted_payloads[2 * i], sorted_payloads[2 * i + 1], c.dictionary)
        for i, c in enumerate(page.columns)
    )
    return Page(cols, sorted_payloads[-1]), offsets, counts


_jit_repartition_epilogue = partial(kernelcost.jit, static_argnums=(0, 1))(
    _repartition_epilogue
)

_jit_partition_dest = kernelcost.jit(_partition_dest, static_argnums=(0, 1))


def repartition_frames(
    page: Page,
    key_idx: Sequence[int],
    n_parts: int,
    pool=None,
    compress: bool = True,
):
    """THE production repartition edge: page -> one serialized v2 frame per
    partition + row counts, ``(frames, counts)``.

    - TPU: the full in-program epilogue + ONE D2H of the contiguous page,
      then frames slice out of it (serde.serialize_page_slices).
    - host-backed backends: the compiled hash yields per-row destinations,
      then gather+encode run FUSED per partition on ``pool``
      (serde.serialize_page_partitions) — partitions are independent, so
      the grouping pass, the buffer gathers, and LZ4 parallelize across
      cores instead of running as three serialized single-threaded phases.

    Frame bytes are identical across both formulations (and to the
    building-block path repartition_to_host -> serialize_page_slices).
    """
    from ..runtime.observability import RECORDER
    from ..runtime.serde import serialize_page_partitions, serialize_page_slices

    key_idx = tuple(key_idx)
    if jax.default_backend() == "tpu":
        cols, offsets, counts = repartition_to_host(page, key_idx, n_parts)
        frames = serialize_page_slices(
            cols, offsets, counts, compress=compress, pool=pool
        )
        return frames, counts
    with RECORDER.span(
        "repartition_kernel", "exchange", parts=n_parts, capacity=page.capacity
    ):
        dest = np.asarray(_jit_partition_dest(n_parts, key_idx, page))
        host_cols = [
            (c.type, np.asarray(c.data), np.asarray(c.valid), c.dictionary)
            for c in page.columns
        ]
    return serialize_page_partitions(
        host_cols, dest, n_parts, compress=compress, pool=pool
    )


def repartition_to_host(page: Page, key_idx: Sequence[int], n_parts: int):
    """Run the repartition epilogue and return a partition-CONTIGUOUS host
    chunk in one transfer: ``(cols, offsets, counts)`` where ``cols`` is
    ``[(type, data, valid, dictionary), ...]`` whose rows ``[offsets[p],
    offsets[p] + counts[p])`` are partition p's, in original relative order
    (offsets/counts are int64 numpy arrays of length ``n_parts``; rows past
    ``sum(counts)`` don't exist — inactive padding never reaches the wire).

    Two formulations, same bit-identical contract:

    - TPU: the whole epilogue (hash -> stable cosort -> offsets/counts) runs
      in-program and ONE D2H fetches the contiguous page — host touches
      nothing per-partition.
    - host-backed backends (CPU/GPU test tiers): only the compiled
      elementwise hash runs in-program; contiguity is a numpy grouping pass
      (per-partition flatnonzero + one take per buffer, O(n_parts * n) with
      branch-free constants). Measured on XLA CPU, its sort/scatter
      lowerings lose ~10x to this (lax.sort 0.6 s, scatter 0.26 s per 1M
      rows vs ~40 ms total here) — the compiled cosort would throw away the
      win the epilogue exists to deliver.

    Emits a ``repartition_kernel`` flight-recorder span covering dispatch +
    the fetch, so the observability plane can attribute the win.
    """
    from ..runtime.observability import RECORDER

    key_idx = tuple(key_idx)
    with RECORDER.span(
        "repartition_kernel", "exchange", parts=n_parts, capacity=page.capacity
    ):
        if jax.default_backend() == "tpu":
            sorted_page, offsets, counts = _jit_repartition_epilogue(
                n_parts, key_idx, page
            )
            # one D2H of the whole pytree (vs n boolean-selection passes)
            host = jax.device_get(
                ([(c.data, c.valid) for c in sorted_page.columns], offsets, counts)
            )
            host_cols, off, cnt = host
            cols = [
                (c.type, np.asarray(d), np.asarray(v), c.dictionary)
                for c, (d, v) in zip(sorted_page.columns, host_cols)
            ]
            return cols, np.asarray(off), np.asarray(cnt)
        dest = np.asarray(_jit_partition_dest(n_parts, key_idx, page))
        order = np.concatenate(
            [np.flatnonzero(dest == p) for p in range(n_parts)]
        )
        counts = np.bincount(dest, minlength=n_parts + 1)[:n_parts].astype(np.int64)
        offsets = np.concatenate(
            [np.zeros(1, dtype=np.int64), np.cumsum(counts)[:-1]]
        )
        cols = [
            (
                c.type,
                np.asarray(c.data).take(order, axis=0),
                np.asarray(c.valid).take(order),
                c.dictionary,
            )
            for c in page.columns
        ]
    return cols, offsets, counts
