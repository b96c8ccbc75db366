"""Memory connector: writable in-memory tables (device-resident pages).

Reference blueprint: plugin/trino-memory (MemoryConnector/MemoryMetadata/
MemoryPagesStore — SURVEY.md §2.9 "Benchmark/test connectors"). Tables live as
lists of device Pages; CREATE TABLE AS / INSERT append, scans concatenate.
"""

from __future__ import annotations

import threading
import uuid
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..spi.connector import (
    ColumnStatistics,
    ColumnMetadata,
    Connector,
    ConnectorMetadata,
    ConnectorPageSourceProvider,
    ConnectorSplitManager,
    SchemaTableName,
    Split,
    TableHandle,
    TableMetadata,
    TableStatistics,
)
from ..spi.page import Column, Page
from ..spi.types import DateType, IntegralType


@dataclass
class _StoredTable:
    columns: Tuple[ColumnMetadata, ...]
    pages: List[Page] = field(default_factory=list)
    # bucketed layout (ref: plugin/trino-memory has none; this mirrors
    # hive-style bucketed tables so the engine's co-located join path has a
    # first-class fixture): rows are hash-split on write, split i == bucket i
    bucketed_by: Tuple[str, ...] = ()
    bucket_count: int = 0
    # live rows of ``pages``, kept by whoever writes ``pages`` (insert,
    # replace_pages: under the connector's lock, counted on the device when
    # the rows change), so that statistics read no page
    rows: int = 0
    # {column: (least, most)} over the live, non-null values of the integer
    # columns (bigint, integer, date), kept as ``rows`` is and read with it
    # in the same one read: a key's range bounds its distinct values, which
    # is what join ordering needs to tell a key from a nation code
    spans: Dict[str, Tuple[int, int]] = field(default_factory=dict)
    # {column: strings in its dictionary} of the dictionary-coded columns, the
    # largest over the pages written: such a column holds no more distinct
    # values than its dictionary has strings (a brand is one of 25, a
    # container one of 40), which is what tells a filter on them from a
    # guess. On the host already: a page carries its dictionaries
    codes: Dict[str, int] = field(default_factory=dict)
    # the integer columns whose values do not fall from one stored row to the
    # next (``lineitem`` by ``l_orderkey``, as its generator wrote it): seen
    # in the same one read, page by page (a page's rows a prefix of it, none
    # null, none under the one before; its least value not under the most of
    # the pages before it), never declared, gone with the first page that
    # breaks it. A scan states the first of them as ``sorted_by``, and a
    # grouping on it takes ``_jit_presorted_group``, which checks again
    ordered: Dict[str, bool] = field(default_factory=dict)

    def row_count(self) -> int:
        return self.rows

    def note(self, pages: "Sequence[Page]", reset: bool = False) -> int:
        """Fold the pages written into ``rows`` and ``spans`` (``_page_facts``:
        one device read for all of them) and ``codes``; returns the rows they
        hold."""
        if reset:
            self.rows, self.spans, self.codes, self.ordered = 0, {}, {}, {}
        for page in pages:
            for meta, col in zip(self.columns, page.columns):
                if col.dictionary is not None:
                    self.codes[meta.name] = max(self.codes.get(meta.name, 0), len(col.dictionary))
        facts = _page_facts(self.columns, pages)
        names = [c.name for c in self.columns if _ranged(c)]
        width = 1 + 3 * len(names)
        added = 0
        for at in range(0, len(facts), width):
            rows = int(facts[at])
            if not rows:
                continue
            for j, name in enumerate(names):
                low, high, rising = (int(v) for v in facts[at + 1 + 3 * j:at + 4 + 3 * j])
                had = self.spans.get(name)
                first = self.rows + added == 0
                self.ordered[name] = bool(rising) and (first or (self.ordered.get(name, False) and low >= had[1]))
                if low > high:
                    continue  # every value null
                self.spans[name] = (low, high) if had is None else (min(had[0], low), max(had[1], high))
            added += rows
        self.rows += added
        return added


def _ranged(column: ColumnMetadata) -> bool:
    return isinstance(column.type, (IntegralType, DateType))


def _page_facts(columns: Sequence[ColumnMetadata], pages: Sequence[Page]) -> "List[int]":
    """For each page: its live rows, then (least, most, rising) of every
    ranged column over its live, non-null values, ``rising`` 1 where the live
    rows are a prefix of the page, none of them null, and none under the row
    before it; all pages in ONE device read."""
    scalars = []
    for page in pages:
        rows = page.num_rows().astype(jnp.int64)
        scalars.append(rows)
        prefix = jnp.all(page.active == (jnp.arange(page.capacity) < rows))
        for meta, col in zip(columns, page.columns):
            if not _ranged(meta):
                continue
            if col.data.ndim != 1 or col.dictionary is not None:
                scalars += [jnp.int64(1), jnp.int64(0), jnp.int64(0)]  # no range: least > most
                continue
            live = page.active & col.valid
            info = jnp.iinfo(col.data.dtype)
            scalars.append(jnp.min(jnp.where(live, col.data, info.max)).astype(jnp.int64))
            scalars.append(jnp.max(jnp.where(live, col.data, info.min)).astype(jnp.int64))
            falls = page.active[1:] & (col.data[1:] < col.data[:-1])
            rising = prefix & ~jnp.any(page.active & ~col.valid) & ~jnp.any(falls)
            scalars.append(rising.astype(jnp.int64))
    return np.asarray(jnp.stack(scalars)).tolist() if scalars else []


class MemoryConnector(Connector):
    name = "memory"

    def __init__(self):
        self._tables: Dict[SchemaTableName, _StoredTable] = {}
        # warm-path cache plane: per-table mutation versions drawn from one
        # monotone counter (drop+recreate never repeats a version). The
        # nonce is per CONNECTOR INSTANCE: two memory connectors in one
        # process (or a restarted process reading a persisted cache) hold
        # different data at the same count — their tokens must never match
        self._versions: Dict[SchemaTableName, int] = {}
        self._version_seq = 0
        self._cache_nonce = uuid.uuid4().hex[:8]
        # reentrant: DML holds mutation_guard() across a read-compute-swap
        # that itself calls the locked replace_pages
        self._lock = threading.RLock()
        self._meta = _MemoryMetadata(self)
        self._splits = _MemorySplitManager(self)
        self._pages = _MemoryPageSourceProvider(self)

    def metadata(self):
        return self._meta

    def split_manager(self):
        return self._splits

    def page_source_provider(self):
        return self._pages

    # ------------------------------------------------------------------- DML

    def create_table(
        self,
        name: SchemaTableName,
        columns: Sequence[ColumnMetadata],
        bucketed_by: Sequence[str] = (),
        bucket_count: int = 0,
    ) -> None:
        with self._lock:
            if name in self._tables:
                raise ValueError(f"table already exists: {name}")
            if bucketed_by:
                known = {c.name for c in columns}
                missing = [c for c in bucketed_by if c not in known]
                if missing or bucket_count < 1:
                    raise ValueError(
                        f"bad bucketing spec: columns={missing or bucketed_by} "
                        f"count={bucket_count}"
                    )
            self._tables[name] = _StoredTable(
                tuple(columns), bucketed_by=tuple(bucketed_by),
                bucket_count=bucket_count if bucketed_by else 0,
            )
            self._bump(name)

    def drop_table(self, name: SchemaTableName, if_exists: bool = False) -> None:
        with self._lock:
            if name not in self._tables:
                if if_exists:
                    return
                raise ValueError(f"table not found: {name}")
            del self._tables[name]
            self._bump(name)

    def _bump(self, name: SchemaTableName) -> None:
        """Advance the table's mutation version (called under _lock)."""
        self._version_seq += 1
        self._versions[name] = self._version_seq

    def cache_table_version(self, schema: str, table: str):
        """Warm-path cache plane hook (runtime/cachestore.py): the mutation
        counter versions in-memory tables exactly — every create/drop/
        insert/replace advances it, so stale warm entries can never match.
        The instance nonce keeps tokens unique across connector INSTANCES
        and processes: a different memory connector (or a restarted
        process reading a persisted cache) holding different data at the
        same count must never alias."""
        with self._lock:
            n = self._versions.get(SchemaTableName(schema, table), 0)
        return f"mem{self._cache_nonce}-{n}"

    def insert(self, name: SchemaTableName, page: Page) -> int:
        """Append a page (the ConnectorPageSink.appendPage analogue).
        Bucketed tables hash-split the rows on write so split i holds
        exactly bucket i (hive bucketed-write analogue)."""
        with self._lock:
            table = self._tables.get(name)
            if table is None:
                raise ValueError(f"table not found: {name}")
            if page.num_columns != len(table.columns):
                raise ValueError(
                    f"column count mismatch: {page.num_columns} vs {len(table.columns)}"
                )
            self._bump(name)
            # rows and the integer columns' ranges, counted on the device: one read
            rows = table.note([page])
            if not table.bucketed_by:
                table.pages.append(page)
                return rows
            from ..spi.host_pages import (
                host_partition_targets,
                page_to_host as _page_to_host,
                pages_from_host_rows as _pages_from_host_rows,
            )

            cols = _page_to_host(page)
            key_idx = [
                next(i for i, c in enumerate(table.columns) if c.name == k)
                for k in table.bucketed_by
            ]
            targets = host_partition_targets(cols, key_idx, table.bucket_count)
            while len(table.pages) < table.bucket_count:
                table.pages.append(None)
            for b in range(table.bucket_count):
                sel = targets == b
                if not sel.any():
                    continue
                newp = _pages_from_host_rows(cols, sel)
                old = table.pages[b]
                if old is None:
                    table.pages[b] = newp
                else:
                    from ..runtime.executor import _concat_pages

                    table.pages[b] = _concat_pages([old, newp])
            return rows

    def table(self, name: SchemaTableName) -> Optional[_StoredTable]:
        with self._lock:
            return self._tables.get(name)

    def mutation_guard(self):
        """Hold the table lock across a read-compute-swap so a concurrent
        INSERT can't land between reading ``pages`` and ``replace_pages``
        (rows it appended would be silently discarded)."""
        return self._lock

    def replace_pages(self, name: SchemaTableName, pages: List[Page]) -> None:
        """Swap a table's pages atomically (row-level DELETE/UPDATE/MERGE —
        the ConnectorMergeSink.storeMergedRows analogue for an in-memory
        store). Bucketed tables re-bucket the replacement rows so the
        split i == bucket i invariant survives DML."""
        with self._lock:
            table = self._tables.get(name)
            if table is None:
                raise ValueError(f"table not found: {name}")
            self._bump(name)
            if not table.bucketed_by:
                table.pages = list(pages)
                # one read for all the pages
                live = [p for p in table.pages if p is not None]
                table.note(live, reset=True)
                return
            table.pages = []
            table.note([], reset=True)  # each insert below adds what it writes
            for p in pages:
                if p is not None:
                    self.insert(name, p)


class _MemoryMetadata(ConnectorMetadata):
    def __init__(self, connector: MemoryConnector):
        self.connector = connector

    def list_schemas(self):
        return sorted({n.schema for n in self.connector._tables} | {"default"})

    def list_tables(self, schema: Optional[str] = None):
        return sorted(
            (n for n in self.connector._tables if schema is None or n.schema == schema),
            key=str,
        )

    def get_table_metadata(self, name: SchemaTableName) -> Optional[TableMetadata]:
        t = self.connector.table(name)
        if t is None:
            return None
        # the first column seen to rise from row to row, as the generator
        # connectors state theirs
        rising = tuple(c.name for c in t.columns if t.ordered.get(c.name))[:1]
        return TableMetadata(name, t.columns, sorted_by=() if t.bucketed_by else rising)

    def table_partitioning(self, handle: TableHandle):
        from ..spi.connector import TablePartitioning

        t = self.connector.table(handle.schema_table)
        if t is None or not t.bucketed_by:
            return None
        return TablePartitioning(
            columns=t.bucketed_by, bucket_count=t.bucket_count
        )

    def get_table_statistics(self, handle: TableHandle) -> TableStatistics:
        t = self.connector.table(handle.schema_table)
        if t is None:
            return TableStatistics(row_count=0.0)
        rows = float(t.row_count())
        # an integer column holds no more distinct values than its range has,
        # a dictionary-coded one no more than its dictionary has strings
        columns = {
            name: ColumnStatistics(ndv=min(rows, float(high - low + 1)))
            for name, (low, high) in t.spans.items()
        }
        for name, strings in t.codes.items():
            columns.setdefault(name, ColumnStatistics(ndv=min(rows, float(strings))))
        return TableStatistics(row_count=rows, columns=columns)


class _MemorySplitManager(ConnectorSplitManager):
    def __init__(self, connector: MemoryConnector):
        self.connector = connector

    def get_splits(self, handle: TableHandle, desired_splits: int = 1) -> List[Split]:
        t = self.connector.table(handle.schema_table)
        if t is None:
            return []
        if t.bucketed_by:
            # split i IS bucket i; empty buckets still get a split so the
            # co-located join's bucket alignment holds on both sides
            return [
                Split(handle, i, t.bucket_count) for i in range(t.bucket_count)
            ]
        if not t.pages:
            return []
        return [Split(handle, i, len(t.pages)) for i in range(len(t.pages))]


class _MemoryPageSourceProvider(ConnectorPageSourceProvider):
    def __init__(self, connector: MemoryConnector):
        self.connector = connector

    def create_page_source(self, split: Split, column_indexes: Sequence[int]) -> Page:
        t = self.connector.table(split.table.schema_table)
        page = (
            t.pages[split.split_id] if split.split_id < len(t.pages) else None
        )
        if page is None:  # empty bucket of a bucketed table
            from ..spi.host_pages import empty_page_for

            names = [t.columns[i].name for i in column_indexes]
            types = {t.columns[i].name: t.columns[i].type for i in column_indexes}
            return empty_page_for(names, types)
        cols = tuple(page.columns[i] for i in column_indexes)
        return Page(cols, page.active)


class BlackHoleConnector(Connector):
    """plugin/trino-blackhole analogue: accepts writes, reads return nothing."""

    name = "blackhole"

    def __init__(self):
        self._schemas: Dict[SchemaTableName, Tuple[ColumnMetadata, ...]] = {}
        self._meta = _BlackHoleMetadata(self)

    def metadata(self):
        return self._meta

    def split_manager(self):
        class _NoSplits(ConnectorSplitManager):
            def get_splits(self, handle, desired_splits=1):
                return []

        return _NoSplits()

    def page_source_provider(self):
        class _NoPages(ConnectorPageSourceProvider):
            def create_page_source(self, split, column_indexes):
                raise RuntimeError("blackhole has no data")

        return _NoPages()

    def create_table(self, name, columns):
        self._schemas[name] = tuple(columns)

    def drop_table(self, name, if_exists=False):
        if name not in self._schemas and not if_exists:
            raise ValueError(f"table not found: {name}")
        self._schemas.pop(name, None)

    def insert(self, name, page) -> int:
        return int(np.asarray(page.active).sum())  # swallowed


class _BlackHoleMetadata(ConnectorMetadata):
    def __init__(self, connector: BlackHoleConnector):
        self.connector = connector

    def list_schemas(self):
        return ["default"]

    def list_tables(self, schema=None):
        return sorted(self.connector._schemas, key=str)

    def get_table_metadata(self, name):
        cols = self.connector._schemas.get(name)
        return TableMetadata(name, cols) if cols else None
