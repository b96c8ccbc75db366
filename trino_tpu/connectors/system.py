"""``system`` catalog: SQL-queryable live engine state + procedures.

Reference blueprint: core/trino-main/src/main/java/io/trino/connector/system/
(SystemConnector, GlobalSystemConnector — ``system.runtime.queries`` /
``tasks`` / ``nodes`` backed by QueryManager/TaskManager/NodeManager
snapshots, ``system.metrics`` over JMX beans, and the
``system.runtime.kill_query`` procedure; SURVEY.md §5.5). The engine
dogfoods its own query language over its own runtime: every table is a
zero-copy-ish snapshot assembled at scan time, flowing through the same
compiled pipeline as any data scan.

Consistency caveats (documented in ARCHITECTURE.md "System catalog"):
snapshots are eventually consistent — a scan sees each source's state at
the moment its rows are built, with no cross-source barrier; the tasks
read is lock-free against running workers (one registry lock per manager,
never blocking task execution).

Wiring: the connector reads a :class:`SystemContext` owned by the Metadata
facade. ``QueryManager`` self-registers into the runner's context at
construction; ``CoordinatorServer`` adds its node manager and optional
persistent history store; worker ``TaskManager`` instances register into a
process-wide set (``server.worker.all_task_managers``).
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from ..spi.connector import (
    ColumnMetadata,
    Connector,
    ConnectorMetadata,
    ConnectorPageSourceProvider,
    ConnectorSplitManager,
    SchemaTableName,
    Split,
    TableHandle,
    TableMetadata,
)
from ..spi.page import Page
from ..spi.types import BIGINT, BOOLEAN, DOUBLE, VarcharType
from .synthetic import synthetic_page

VARCHAR = VarcharType()

CATALOG_NAME = "system"


@dataclass
class SystemContext:
    """Late-bound engine references the system tables snapshot.

    Every field is optional: an embedded LocalQueryRunner without a
    QueryManager still serves ``nodes``/``metrics``/``flight_events``;
    query-backed tables are empty until a manager attaches (QueryManager
    auto-wires itself when built over a runner's ``execute``).
    """

    query_manager: Optional[object] = None
    node_manager: Optional[object] = None
    history_store: Optional[object] = None
    # memory arbitration plane (runtime/memory.py): the QueryManager
    # registers its pool + ClusterMemoryManager here at construction
    memory_pool: Optional[object] = None
    cluster_memory: Optional[object] = None
    # cluster observability plane (runtime/clusterobs.py): the coordinator
    # attaches its federated-metrics fold here; None = empty cluster tables
    cluster_metrics: Optional[object] = None
    # extra task snapshot providers beyond the process-wide worker registry
    task_sources: List[object] = field(default_factory=list)


# table name -> ordered column metadata, per schema (a slice of the
# reference's SystemTable registry)
TABLES: Dict[str, Dict[str, Tuple[ColumnMetadata, ...]]] = {
    "runtime": {
        "queries": (
            ColumnMetadata("query_id", VARCHAR),
            ColumnMetadata("state", VARCHAR),
            ColumnMetadata("user", VARCHAR),
            ColumnMetadata("source", VARCHAR),
            ColumnMetadata("query", VARCHAR),
            ColumnMetadata("resource_group", VARCHAR),
            ColumnMetadata("error_type", VARCHAR),
            ColumnMetadata("created", DOUBLE),       # epoch seconds
            ColumnMetadata("ended", DOUBLE),         # NULL while running
            ColumnMetadata("elapsed_ms", BIGINT),
            ColumnMetadata("cpu_ms", BIGINT),
            ColumnMetadata("rows", BIGINT),
            ColumnMetadata("device_busy_ms", BIGINT),
            ColumnMetadata("host_wait_ms", BIGINT),
            ColumnMetadata("compile_ms", BIGINT),
        ),
        "query_history": (
            ColumnMetadata("query_id", VARCHAR),
            ColumnMetadata("state", VARCHAR),
            ColumnMetadata("user", VARCHAR),
            ColumnMetadata("query", VARCHAR),
            ColumnMetadata("created", DOUBLE),
            ColumnMetadata("ended", DOUBLE),
            ColumnMetadata("elapsed_ms", BIGINT),
            ColumnMetadata("cpu_ms", BIGINT),
            ColumnMetadata("rows", BIGINT),
            ColumnMetadata("error_type", VARCHAR),
        ),
        "tasks": (
            ColumnMetadata("node_id", VARCHAR),
            ColumnMetadata("task_id", VARCHAR),
            ColumnMetadata("query_id", VARCHAR),
            ColumnMetadata("state", VARCHAR),
            ColumnMetadata("error", VARCHAR),
            ColumnMetadata("queued_ms", BIGINT),
            ColumnMetadata("run_ms", BIGINT),
            ColumnMetadata("buffered_pages", BIGINT),
        ),
        "nodes": (
            ColumnMetadata("node_id", VARCHAR),
            ColumnMetadata("http_uri", VARCHAR),
            ColumnMetadata("node_version", VARCHAR),
            ColumnMetadata("coordinator", BOOLEAN),
            ColumnMetadata("state", VARCHAR),
            ColumnMetadata("device", VARCHAR),
            ColumnMetadata("last_seen_age_ms", BIGINT),
        ),
        "task_attempts": (
            ColumnMetadata("query_id", VARCHAR),
            ColumnMetadata("fragment_id", BIGINT),
            ColumnMetadata("partition_id", BIGINT),
            ColumnMetadata("attempt", BIGINT),
            ColumnMetadata("worker", VARCHAR),
            ColumnMetadata("outcome", VARCHAR),   # ok|failed|timeout|stale
            ColumnMetadata("error_category", VARCHAR),
            ColumnMetadata("speculative", BOOLEAN),
            ColumnMetadata("elapsed_ms", BIGINT),
        ),
        "flight_events": (
            ColumnMetadata("kind", VARCHAR),
            ColumnMetadata("cat", VARCHAR),
            ColumnMetadata("phase", VARCHAR),
            ColumnMetadata("ts", BIGINT),   # microseconds (monotonic clock)
            ColumnMetadata("dur", BIGINT),  # microseconds; 0 for non-X events
            ColumnMetadata("tid", BIGINT),
            ColumnMetadata("args", VARCHAR),
        ),
        "resource_groups": (
            ColumnMetadata("id", VARCHAR),
            ColumnMetadata("parent", VARCHAR),
            ColumnMetadata("hard_concurrency_limit", BIGINT),
            ColumnMetadata("max_queued", BIGINT),
            ColumnMetadata("scheduling_weight", BIGINT),
            ColumnMetadata("soft_memory_limit_bytes", BIGINT),  # NULL = none
            ColumnMetadata("memory_usage_bytes", BIGINT),
            ColumnMetadata("running", BIGINT),
            ColumnMetadata("queued", BIGINT),
        ),
        "memory_pool": (
            ColumnMetadata("node_id", VARCHAR),
            ColumnMetadata("pool", VARCHAR),
            ColumnMetadata("max_bytes", BIGINT),        # 0 = unbounded
            ColumnMetadata("reserved_bytes", BIGINT),
            ColumnMetadata("revocable_bytes", BIGINT),
            ColumnMetadata("peak_bytes", BIGINT),
            ColumnMetadata("blocked_queries", BIGINT),
            ColumnMetadata("low_memory_kills", BIGINT),  # NULL on workers
        ),
        # warm-path cache plane snapshot (runtime/cachestore.py): one row
        # per tier (plan / result / fragment)
        "caches": (
            ColumnMetadata("tier", VARCHAR),
            ColumnMetadata("entries", BIGINT),
            ColumnMetadata("bytes", BIGINT),
            ColumnMetadata("hits", BIGINT),
            ColumnMetadata("misses", BIGINT),
            ColumnMetadata("evictions", BIGINT),
            ColumnMetadata("invalidations", BIGINT),
        ),
        # persisted query-profile bundles (cluster observability plane;
        # $TRINO_TPU_QUERY_PROFILE_DIR — empty when unset)
        "query_profiles": (
            ColumnMetadata("query_id", VARCHAR),
            ColumnMetadata("state", VARCHAR),
            ColumnMetadata("user", VARCHAR),
            ColumnMetadata("query", VARCHAR),
            ColumnMetadata("wall_ms", BIGINT),
            ColumnMetadata("stages", BIGINT),
            ColumnMetadata("diagnosis", VARCHAR),
            ColumnMetadata("created", DOUBLE),
            ColumnMetadata("path", VARCHAR),
        ),
        # ANN serving tier: measured recall@k of centroid-pruned vector
        # top-k against the periodic exact oracle (ops/tensor.py ring;
        # empty until ann_recall_sample_rate draws a sample)
        "ann_recall": (
            ColumnMetadata("table_name", VARCHAR),
            ColumnMetadata("k", BIGINT),
            ColumnMetadata("nprobe", BIGINT),
            ColumnMetadata("recall", DOUBLE),
            ColumnMetadata("probed_splits", BIGINT),
            ColumnMetadata("total_splits", BIGINT),
        ),
        # per-plan-node cardinality actuals of recent queries (the
        # statistics feedback plane's bounded ring; runtime/statstore.py)
        # kernel cost plane (runtime/kernelcost.py): per-program XLA
        # cost-model attribution of recent kernel_cost-enabled queries;
        # the node column is "" for local rows and the announcing worker's
        # id for rows folded from the federated plane
        "kernel_costs": (
            ColumnMetadata("node", VARCHAR),
            ColumnMetadata("query_id", VARCHAR),
            ColumnMetadata("plan_node", VARCHAR),
            ColumnMetadata("label", VARCHAR),
            ColumnMetadata("program_key", VARCHAR),
            ColumnMetadata("platform", VARCHAR),
            ColumnMetadata("flops", DOUBLE),            # NULL = unavailable
            ColumnMetadata("bytes_accessed", DOUBLE),   # NULL = unavailable
            ColumnMetadata("peak_hbm_bytes", BIGINT),
            ColumnMetadata("arithmetic_intensity", DOUBLE),
            ColumnMetadata("classification", VARCHAR),  # memory-/compute-bound
            ColumnMetadata("status", VARCHAR),  # ok | cost_unavailable
            ColumnMetadata("ts", DOUBLE),       # epoch seconds
        ),
        # host-path observability plane (runtime/hostprof.py): collapsed
        # wall-clock sampling-profiler stacks per named engine thread,
        # heaviest-first; empty until the sampler has run (host_profile
        # session property or $TRINO_TPU_HOSTPROF)
        "host_profile": (
            ColumnMetadata("thread", VARCHAR),
            ColumnMetadata("stack", VARCHAR),     # root;...;leaf collapsed
            ColumnMetadata("samples", BIGINT),
            ColumnMetadata("share", DOUBLE),      # fraction of all samples
        ),
        "operator_stats": (
            ColumnMetadata("query_id", VARCHAR),
            ColumnMetadata("fragment", BIGINT),       # NULL on local runs
            ColumnMetadata("node_id", BIGINT),        # preorder position
            ColumnMetadata("plan_node", VARCHAR),
            ColumnMetadata("estimated_rows", DOUBLE),  # NULL = no estimate
            ColumnMetadata("actual_rows", BIGINT),
            ColumnMetadata("input_rows", BIGINT),
            ColumnMetadata("output_bytes", BIGINT),
            ColumnMetadata("null_fraction", DOUBLE),
            ColumnMetadata("build_rows", BIGINT),      # joins only
            ColumnMetadata("dynamic_filter_selectivity", DOUBLE),
            ColumnMetadata("q_error", DOUBLE),
            ColumnMetadata("ts", DOUBLE),              # epoch seconds
        ),
    },
    "optimizer": {
        # the history-based stats store: estimate-vs-actual per recorded
        # plan-shape key (structural subtree fingerprint or canonical leaf)
        "stats_history": (
            ColumnMetadata("key", VARCHAR),
            ColumnMetadata("plan_fingerprint", VARCHAR),
            ColumnMetadata("plan_node", VARCHAR),
            ColumnMetadata("table_name", VARCHAR),     # scans only
            ColumnMetadata("estimated_rows", DOUBLE),
            ColumnMetadata("actual_rows", DOUBLE),
            ColumnMetadata("q_error", DOUBLE),
            ColumnMetadata("runs", BIGINT),
            ColumnMetadata("updated_at", DOUBLE),
        ),
    },
    "metrics": {
        "counters": (
            ColumnMetadata("name", VARCHAR),
            ColumnMetadata("labels", VARCHAR),
            ColumnMetadata("kind", VARCHAR),  # counter | gauge
            ColumnMetadata("value", DOUBLE),
            ColumnMetadata("help", VARCHAR),
        ),
        "histograms": (
            ColumnMetadata("name", VARCHAR),
            ColumnMetadata("labels", VARCHAR),
            ColumnMetadata("le", DOUBLE),  # +Inf bucket -> inf
            ColumnMetadata("cumulative_count", BIGINT),
            ColumnMetadata("sum", DOUBLE),
            ColumnMetadata("count", BIGINT),
            # estimated quantiles by exponential-bucket interpolation
            # (metrics.histogram_quantile); NULL while the series is empty
            ColumnMetadata("p50", DOUBLE),
            ColumnMetadata("p95", DOUBLE),
            ColumnMetadata("p99", DOUBLE),
            ColumnMetadata("help", VARCHAR),
        ),
        # federated per-node series folded from announcement snapshots
        # (cluster observability plane; empty without a coordinator fold)
        "cluster_counters": (
            ColumnMetadata("name", VARCHAR),
            ColumnMetadata("labels", VARCHAR),
            ColumnMetadata("node", VARCHAR),
            ColumnMetadata("kind", VARCHAR),  # counter | gauge
            ColumnMetadata("value", DOUBLE),
            ColumnMetadata("help", VARCHAR),
        ),
        "cluster_histograms": (
            ColumnMetadata("name", VARCHAR),
            ColumnMetadata("labels", VARCHAR),
            ColumnMetadata("node", VARCHAR),
            ColumnMetadata("le", DOUBLE),  # +Inf bucket -> inf
            ColumnMetadata("cumulative_count", BIGINT),
            ColumnMetadata("sum", DOUBLE),
            ColumnMetadata("count", BIGINT),
            ColumnMetadata("help", VARCHAR),
        ),
    },
}


def device_kind() -> str:
    """``device_kind`` of the default device, as JAX reports it (e.g. "TPU v5
    lite", "cpu") — what system.runtime.nodes and node announcements show."""
    import jax

    return jax.devices()[0].device_kind


def _ms(secs: Optional[float]) -> Optional[int]:
    return None if secs is None else int(secs * 1000)


class SystemConnector(Connector):
    """One per Metadata facade; every table reads live engine state."""

    name = CATALOG_NAME
    # warm-path cache plane: live engine snapshots must NEVER serve stale
    # (a monitoring dashboard polling system.runtime.* wants NOW, not a
    # TTL-old replay) — runtime/cachestore.py bypasses on this attr
    cache_bypass = True

    def __init__(self, context: Optional[SystemContext] = None):
        self.context = context or SystemContext()
        self._meta = _SystemMetadata()
        self._splits = _SystemSplits()
        self._pages = _SystemPageSource(self)

    def metadata(self):
        return self._meta

    def split_manager(self):
        return self._splits

    def page_source_provider(self):
        return self._pages

    # ------------------------------------------------------------- snapshots

    def _rows(self, schema: str, table: str) -> List[tuple]:
        fn = getattr(self, f"_rows_{schema}_{table}", None)
        if fn is None:
            raise ValueError(f"unknown system table: {schema}.{table}")
        return fn()

    def _rows_runtime_queries(self) -> List[tuple]:
        mgr = self.context.query_manager
        if mgr is None:
            return []
        rows = []
        for q in mgr.list_queries():
            times = (q.query_stats or {}).get("times", {})
            rows.append((
                q.query_id,
                q.state.value,
                q.user,
                q.source or None,
                q.sql,
                q.resource_group or None,
                q.error_type,
                q.stats.create_time,
                q.stats.end_time,
                _ms(q.stats.elapsed),
                _ms(q.stats.cpu_time),
                q.stats.rows,
                _ms(times.get("device_busy_secs", 0.0)),
                _ms(times.get("host_wait_secs", 0.0)),
                _ms(times.get("compile_secs", 0.0)),
            ))
        rows.sort(key=lambda r: (r[7], r[0]))
        return rows

    def _rows_runtime_query_history(self) -> List[tuple]:
        store = self.context.history_store
        if store is None:
            return []
        rows = []
        for ev in store.records():
            rows.append((
                ev.get("queryId"),
                ev.get("state"),
                ev.get("user"),
                ev.get("query"),
                ev.get("createTime"),
                ev.get("endTime"),
                _ms(ev.get("elapsedSeconds")),
                _ms(ev.get("cpuSeconds")),
                ev.get("outputRows"),
                ev.get("errorType"),
            ))
        return rows

    def _rows_runtime_tasks(self) -> List[tuple]:
        from ..server.worker import all_task_managers

        sources = list(all_task_managers()) + list(self.context.task_sources)
        rows = []
        for tm in sources:
            try:
                snaps = tm.snapshot()
            except Exception:  # noqa: BLE001 — one bad source can't kill the scan
                continue
            for s in snaps:
                rows.append((
                    s.get("nodeId"),
                    s.get("taskId"),
                    s.get("queryId"),
                    s.get("state"),
                    s.get("error"),
                    _ms(s.get("queuedSecs")),
                    _ms(s.get("runSecs")),
                    s.get("bufferedPages"),
                ))
        rows.sort(key=lambda r: (r[0] or "", r[1] or ""))
        return rows

    def _rows_runtime_nodes(self) -> List[tuple]:
        mgr = self.context.node_manager
        now = time.time()
        if mgr is None:
            # embedded single-process runner: this process IS the cluster
            from .. import __version__

            return [(
                "local", None, __version__, True, "ACTIVE", device_kind(), 0,
            )]
        return [
            (
                n.node_id,
                n.uri or None,
                n.version or None,
                bool(n.coordinator),
                n.state.value,
                n.device or None,
                max(int((now - n.last_heartbeat) * 1000), 0),
            )
            for n in mgr.all_nodes()
        ]

    def _rows_runtime_task_attempts(self) -> List[tuple]:
        """FTE scheduler attempt history (bounded process-wide ring — the
        task-attempt analogue of query_history; ref: the scheduler's task
        lifecycle events surfaced through EXPLAIN/ system.runtime)."""
        from ..runtime.fte_scheduler import attempt_log

        return [
            (
                r.get("query_id"),
                r.get("fragment"),
                r.get("partition"),
                r.get("attempt"),
                r.get("worker"),
                r.get("outcome"),
                r.get("category") or None,
                bool(r.get("speculative")),
                r.get("elapsed_ms"),
            )
            for r in attempt_log()
        ]

    def _rows_runtime_resource_groups(self) -> List[tuple]:
        """Live admission state per materialized group (ref: the reference's
        ResourceGroupInfo rows behind /v1/resourceGroupState)."""
        mgr = self.context.query_manager
        groups = getattr(mgr, "resource_groups", None) if mgr else None
        flat = getattr(groups, "flat_info", None)
        if flat is None:
            return []
        return [
            (
                row.get("id"),
                row.get("parent"),
                row.get("hardConcurrencyLimit"),
                row.get("maxQueued"),
                row.get("schedulingWeight"),
                row.get("softMemoryLimitBytes"),
                row.get("memoryUsageBytes", 0),
                row.get("running", 0),
                row.get("queued", 0),
            )
            for row in flat()
        ]

    def _rows_runtime_memory_pool(self) -> List[tuple]:
        """Pool standing per node: the local (coordinator) pool first, then
        every announced worker's heartbeat-reported memory."""
        rows: List[tuple] = []
        pool = self.context.memory_pool
        if pool is None:
            mgr = self.context.query_manager
            pool = getattr(mgr, "memory_pool", None) if mgr else None
        cluster = self.context.cluster_memory
        if pool is not None:
            s = pool.snapshot()
            rows.append((
                "local",
                s.get("pool"),
                s.get("maxBytes", 0),
                s.get("reservedBytes", 0),
                s.get("revocableBytes", 0),
                s.get("peakBytes", 0),
                s.get("blockedQueries", 0),
                getattr(cluster, "kills_total", 0) if cluster else 0,
            ))
        nmgr = self.context.node_manager
        if nmgr is not None:
            for n in nmgr.all_nodes():
                if getattr(n, "coordinator", False):
                    continue  # the coordinator's pool is the "local" row
                rows.append((
                    n.node_id,
                    "general",
                    getattr(n, "pool_max_bytes", 0),
                    getattr(n, "reserved_bytes", 0),
                    getattr(n, "revocable_bytes", 0),
                    getattr(n, "peak_bytes", 0),
                    getattr(n, "blocked_queries", 0),
                    None,
                ))
        return rows

    def _rows_runtime_caches(self) -> List[tuple]:
        from ..runtime.cachestore import CACHES

        return CACHES.stats_rows()

    def _rows_runtime_ann_recall(self) -> List[tuple]:
        from ..ops import tensor as T

        return list(T.ann_recall_rows())

    def _rows_runtime_flight_events(self) -> List[tuple]:
        from ..runtime.observability import RECORDER

        rows = []
        for ev in RECORDER.events():
            args = ev.get("args")
            rows.append((
                ev.get("name"),
                ev.get("cat"),
                ev.get("ph"),
                ev.get("ts"),
                int(ev.get("dur", 0)),
                ev.get("tid"),
                json.dumps(args) if args else None,
            ))
        return rows

    def _rows_metrics_counters(self) -> List[tuple]:
        from ..runtime.metrics import REGISTRY

        rows = []
        for entry in REGISTRY.collect():
            if entry["type"] == "histogram":
                continue
            rows.append((
                entry["name"],
                json.dumps(entry["labels"]) if entry["labels"] else None,
                entry["type"],
                float(entry["value"]),
                entry["help"] or None,
            ))
        return rows

    def _rows_metrics_histograms(self) -> List[tuple]:
        from ..runtime.metrics import REGISTRY, histogram_quantile

        rows = []
        for entry in REGISTRY.collect():
            if entry["type"] != "histogram":
                continue
            labels = json.dumps(entry["labels"]) if entry["labels"] else None
            qs = [
                histogram_quantile(entry["buckets"], entry["count"], q)
                for q in (0.50, 0.95, 0.99)
            ]
            for bound, cum in entry["buckets"]:
                rows.append((
                    entry["name"], labels, bound, cum,
                    entry["sum"], entry["count"],
                    qs[0], qs[1], qs[2],
                    entry["help"] or None,
                ))
        return rows

    def _rows_runtime_query_profiles(self) -> List[tuple]:
        """Persisted query-profile bundles (cluster observability plane);
        empty rows until $TRINO_TPU_QUERY_PROFILE_DIR is configured."""
        from ..runtime.clusterobs import profile_store

        store = profile_store()
        if store is None:
            return []
        rows = []
        for p in store.list():
            rows.append((
                p.get("queryId"),
                p.get("state"),
                p.get("user") or None,
                p.get("query"),
                _ms(p.get("wallSecs")),
                len(p.get("stages") or {}),
                p.get("diagnosis"),
                p.get("createdAt"),
                p.get("_path"),
            ))
        rows.sort(key=lambda r: (r[7] or 0.0, r[0] or ""))
        return rows

    def _rows_metrics_cluster_counters(self) -> List[tuple]:
        cm = self.context.cluster_metrics
        if cm is None:
            return []
        from ..runtime.metrics import REGISTRY

        return cm.counters_rows(local_registry=REGISTRY)

    def _rows_metrics_cluster_histograms(self) -> List[tuple]:
        cm = self.context.cluster_metrics
        if cm is None:
            return []
        from ..runtime.metrics import REGISTRY

        return cm.histograms_rows(local_registry=REGISTRY)

    def _rows_runtime_kernel_costs(self) -> List[tuple]:
        """XLA cost-model attributions: this process's ledger plus rows
        folded from worker announcements (federated plane, TTL-pruned)."""
        from ..runtime import kernelcost

        def to_row(node: str, r: dict) -> tuple:
            peak = r.get("peak_hbm_bytes")
            return (
                node,
                r.get("query_id") or None,
                r.get("plan_node") or None,
                r.get("label"),
                r.get("key"),
                r.get("platform"),
                r.get("flops"),
                r.get("bytes_accessed"),
                int(peak) if peak is not None else None,
                r.get("arithmetic_intensity"),
                r.get("classification"),
                r.get("status"),
                r.get("ts"),
            )

        rows = [to_row("", r) for r in kernelcost.ledger_rows()]
        rows.extend(to_row(nid, r) for nid, r in kernelcost.federated_rows())
        rows.sort(key=lambda r: (r[12] or 0.0, r[0] or "", r[4] or ""))
        return rows

    def _rows_runtime_host_profile(self) -> List[tuple]:
        """Host-path sampling-profiler snapshot: collapsed stacks per named
        engine thread from the bounded sample ring (runtime/hostprof.py)."""
        from ..runtime.hostprof import PROFILER

        return list(PROFILER.profile_rows())

    def _rows_runtime_operator_stats(self) -> List[tuple]:
        """Recent per-plan-node cardinality actuals (the statistics feedback
        plane's bounded process ring; runtime/statstore.py)."""
        from ..runtime.statstore import operator_stats_log

        return [
            (
                r.get("query_id") or None,
                r.get("fragment"),
                r.get("node_id"),
                r.get("kind"),
                r.get("estimate"),
                r.get("actual"),
                r.get("input_rows"),
                r.get("bytes"),
                r.get("null_frac"),
                r.get("build_rows"),
                r.get("dyn_filter_sel"),
                r.get("qerror"),
                r.get("ts"),
            )
            for r in operator_stats_log()
        ]

    def _rows_optimizer_stats_history(self) -> List[tuple]:
        """The history-based stats store, live (file- or memory-backed)."""
        from ..runtime.statstore import load_history

        rows = []
        for key, ent in sorted(load_history().items()):
            rows.append((
                key,
                ent.get("plan") or None,
                ent.get("kind"),
                ent.get("table"),
                ent.get("estimate"),
                ent.get("actual"),
                ent.get("qerror"),
                int(ent.get("runs", 1)),
                ent.get("updated_at"),
            ))
        return rows


class _SystemMetadata(ConnectorMetadata):
    def list_schemas(self) -> List[str]:
        return sorted(TABLES)

    def list_tables(self, schema: Optional[str] = None) -> List[SchemaTableName]:
        schemas = [schema] if schema else sorted(TABLES)
        return [
            SchemaTableName(s, t)
            for s in schemas
            if s in TABLES
            for t in sorted(TABLES[s])
        ]

    def get_table_metadata(self, name: SchemaTableName) -> Optional[TableMetadata]:
        cols = TABLES.get(name.schema, {}).get(name.table)
        if cols is None:
            return None
        return TableMetadata(name, tuple(cols))


class _SystemSplits(ConnectorSplitManager):
    def get_splits(self, handle: TableHandle, desired_splits: int = 1) -> List[Split]:
        st = handle.schema_table
        return [
            Split(
                table=handle, split_id=0, total_splits=1,
                info=(st.schema, st.table),
            )
        ]


class _SystemPageSource(ConnectorPageSourceProvider):
    def __init__(self, conn: SystemConnector):
        self.conn = conn

    def create_page_source(self, split: Split, column_indexes: Sequence[int]) -> Page:
        schema, table = split.info
        all_cols = TABLES[schema][table]
        rows = self.conn._rows(schema, table)
        return synthetic_page(all_cols, rows, column_indexes)


# --------------------------------------------------------------------------- #
# procedures (ref: io.trino.connector.system.KillQueryProcedure)
# --------------------------------------------------------------------------- #


def call_procedure(runner, parts: Tuple[str, ...], args: List[object]):
    """Dispatch CALL catalog.schema.proc(args) -> (column_names, rows).

    The only registry today is the system catalog's; connector-defined
    procedures would hook in here (spi Procedure analogue).
    """
    if len(parts) != 3 or parts[0] != CATALOG_NAME:
        raise ValueError(
            f"procedure not found: {'.'.join(parts)} "
            f"(procedures live in the system catalog, e.g. "
            f"system.runtime.kill_query)"
        )
    key = (parts[1], parts[2])
    if key == ("runtime", "kill_query"):
        if not 1 <= len(args) <= 2:
            raise ValueError("kill_query(query_id, message) takes 1-2 arguments")
        message = str(args[1]) if len(args) == 2 and args[1] is not None else ""
        return _kill_query(runner, str(args[0]), message)
    raise ValueError(f"procedure not found: {'.'.join(parts)}")


def _kill_query(runner, query_id: str, message: str):
    from ..runtime.query_manager import CancelResult, QueryNotFound

    ctx = runner.metadata.system_context
    mgr = ctx.query_manager
    if mgr is None:
        raise ValueError(
            "kill_query requires a query manager (submit through a "
            "QueryManager or the coordinator)"
        )
    target = mgr.get(query_id)
    if target is None:
        raise QueryNotFound(query_id)
    # authorization (ref: KillQueryProcedure -> checkCanKillQueryOwnedBy):
    # killing your own query is always allowed; killing another user's
    # query consults the access-control hook when the installed
    # implementation provides one
    user = runner._current_user()
    if target.user != user:
        hook = getattr(
            runner.access_control, "check_can_kill_query_owned_by", None
        )
        if hook is not None:
            hook(user, target.user)
    result = mgr.kill(query_id, message)  # raises QueryNotFound when unknown
    if result is CancelResult.TERMINAL:
        raise ValueError(f"query is not running: {query_id}")
    return ["result"], [(True,)]
