"""Iceberg-lite: snapshot-versioned tables over the lakehouse storage stack.

Reference blueprint: plugin/trino-iceberg (IcebergMetadata.java — snapshot
log, manifest-driven scans, optimistic metadata commits) shrunk to the
mechanism that matters on this storage stack:

- every INSERT/CTAS commit appends ONE snapshot JSON
  (`<table>/_iceberg/snap-%012d.json`) listing the table's COMPLETE data
  file set (manifest inlined — "lite": no manifest-list indirection),
- commits are optimistic: the snapshot object is created with the
  filesystem's atomic create-EXCLUSIVE put (`fs.write_if_absent`; the
  S3 If-None-Match / GCS precondition primitive). Two writers racing on
  the same parent snapshot produce ONE winner; the loser raises
  CommitConflict and its freshly written (uuid-named) data objects stay
  unreferenced — invisible to every reader, exactly iceberg's failed-
  commit garbage,
- reads resolve the CURRENT snapshot (or `FOR VERSION AS OF n`) and scan
  exactly its manifest — concurrent writers never tear a read.

Builds on the lake connector's partitioned-Parquet writer/metastore; the
schema evolution/delete-file/compaction surface of real iceberg is out of
scope.
"""

from __future__ import annotations

import json
from typing import List, Optional, Sequence

from ..fs import Location
from ..spi.connector import Split, TableHandle
from .lake import LakeConnector, _LakeMetadata, _LakeSplitManager

_SNAP_DIR = "_iceberg"


class CommitConflict(RuntimeError):
    """Another writer committed the same parent snapshot first."""


def _snap_name(snapshot_id: int) -> str:
    return f"snap-{snapshot_id:012d}.json"


class IcebergLiteConnector(LakeConnector):
    name = "iceberg_lite"

    def metadata(self):
        if not isinstance(self._meta, _IcebergMetadata):
            self._meta = _IcebergMetadata(self)
        return self._meta

    def split_manager(self):
        if not isinstance(self._splits, _IcebergSplitManager):
            self._splits = _IcebergSplitManager(self)
        return self._splits

    # ------------------------------------------------------------ snapshots

    def _table_loc(self, schema: str, table: str) -> Optional[Location]:
        t = self.metastore.get_table(schema, table)
        return Location.parse(t.location) if t is not None else None

    def snapshots(self, schema: str, table: str) -> List[int]:
        loc = self._table_loc(schema, table)
        if loc is None:
            return []
        fs = self._fs(loc)
        ids = []
        for entry in fs.list_files(loc.child(_SNAP_DIR)):
            base = entry.location.path.rsplit("/", 1)[-1]
            if base.startswith("snap-") and base.endswith(".json"):
                ids.append(int(base[len("snap-"):-len(".json")]))
        return sorted(ids)

    def current_snapshot_id(self, schema: str, table: str) -> int:
        ids = self.snapshots(schema, table)
        return ids[-1] if ids else 0

    def cache_table_version(self, schema: str, table: str):
        """Warm-path cache plane hook (runtime/cachestore.py): the current
        snapshot id, QUALIFIED by the table's storage location — snapshot
        ids are sequential per table (parent+1), so two warehouses holding
        a same-named table at the same snapshot count must never alias.
        Every DML commit appends a snapshot, so a bump invalidates exactly
        and only the entries it should; the location is stable across
        processes, so persisted entries stay valid after a restart."""
        loc = self._table_loc(schema, table)
        if loc is None:
            return None  # unknown table: TTL-or-bypass, never a guess
        return f"{loc.uri()}@{self.current_snapshot_id(schema, table)}"

    def read_snapshot(self, schema: str, table: str, snapshot_id: int) -> dict:
        loc = self._table_loc(schema, table)
        path = loc.child(_SNAP_DIR, _snap_name(snapshot_id))
        return json.loads(self._fs(loc).read(path))

    def _commit_snapshot(
        self, schema: str, table: str, parent: int, files: List[dict], op: str
    ) -> int:
        """Optimistic append of snapshot parent+1; raises CommitConflict on
        a concurrent commit (the caller's data objects stay unreferenced)."""
        loc = self._table_loc(schema, table)
        snap = {
            "snapshot_id": parent + 1,
            "parent": parent or None,
            "operation": op,
            "files": files,
        }
        target = loc.child(_SNAP_DIR, _snap_name(parent + 1))
        if not self._fs(loc).write_if_absent(
            target, json.dumps(snap, indent=1).encode()
        ):
            raise CommitConflict(
                f"snapshot {parent + 1} of {schema}.{table} was committed "
                "by a concurrent writer"
            )
        return parent + 1

    # ---------------------------------------------------------------- write

    def insert(self, name, page) -> int:
        n, written = self._insert_pages(name, page)
        if n == 0:
            return 0
        parent = self.current_snapshot_id(name.schema, name.table)
        base = (
            self.read_snapshot(name.schema, name.table, parent)["files"]
            if parent
            else []
        )
        self._commit_snapshot(
            name.schema, name.table, parent, base + written, "append"
        )
        return n


class _IcebergMetadata(_LakeMetadata):
    def apply_filter(self, handle, domain):
        # connector_handle is reserved for the snapshot pin; partition
        # pruning under time travel is future work ("lite")
        return None

    def apply_version(self, handle: TableHandle, version: int) -> Optional[TableHandle]:
        name = handle.schema_table
        if version not in self.connector.snapshots(name.schema, name.table):
            raise ValueError(
                f"snapshot {version} of {name} does not exist"
            )
        return TableHandle(
            catalog=handle.catalog,
            schema_table=name,
            connector_handle={"snapshot_id": version},
        )


class _IcebergSplitManager(_LakeSplitManager):
    def get_splits(self, handle: TableHandle) -> List[Split]:
        name = handle.schema_table
        ch = getattr(handle, "connector_handle", None)
        if isinstance(ch, dict) and "snapshot_id" in ch:
            sid = int(ch["snapshot_id"])
        else:
            sid = self.connector.current_snapshot_id(name.schema, name.table)
        if sid == 0:
            return []  # no committed snapshot: an empty (or new) table
        files = self.connector.read_snapshot(name.schema, name.table, sid)["files"]
        return [
            Split(table=handle, split_id=i, total_splits=len(files), info=f)
            for i, f in enumerate(files)
        ]
