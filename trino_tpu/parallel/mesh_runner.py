"""MeshQueryRunner: whole fragment trees lowered into ONE shard_map program.

Reference blueprint: SURVEY.md §3.3 — every REMOTE exchange in Trino is a real
data plane (AddExchanges.java:145 -> PartitionedOutputOperator -> exchange
consumer chain). The TPU-native replacement executes the ENTIRE multi-stage
plan as one XLA program over a jax.sharding.Mesh:

    SOURCE fragments      -> per-shard blocks of the sharded scan pages
    REPARTITION exchange  -> all_to_all collective (parallel/exchange.py)
    BROADCAST / GATHER    -> all_gather collective (replicated consumers)
    SINGLE fragments      -> replicated SPMD compute over gathered inputs

No host round-trip between stages: stage outputs never leave HBM, the exchange
rides ICI, and XLA overlaps the collectives with compute — the role Trino's
pull/ack HTTP streams play between JVM workers (DirectExchangeClient.java:270).

Where a table's shards live: the runner keeps them over the mesh between
statements (`_ShardStore`, one per `MeshQueryRunner`): each column a statement
scans, and the table's `active`, padded to the shards' capacity and placed
along the mesh axis once per VERSION of its table (`cachestore.table_version`,
read before and after the load), so the next statement assembles its scan
pages from arrays that are there already and moves nothing. What drops them:
a version token that has changed (any write: checked at every statement,
before anything is put), the byte budget (least recently used tables beyond
`_STORE_SHARE` of a device's memory) and a put that ends in
RESOURCE_EXHAUSTED. A connector that gives no token, or answers BYPASS, is
resharded on every statement. Data is kept where the program reads it; no
answer is kept. The program donates none of its inputs.

Static-shape discipline: every page between two stages is sized by what it
holds. Each fragment runs on runtime/adaptive.py's narrowing executor: per
shard, a filter's, scan's or aggregation's output is compacted to its hint, a
join allocates its hinted capacity, a grouped aggregation computes into a
group capacity, and an exchange's bucket is twice the even share of the
producer's page as traced. The program returns, in one small vector, the
summed OVERFLOW scalar and every point's overflow and true count (the most
over the shards). The runner reads it once per attempt: on overflow the
points grow to what was measured and the program is rebuilt; a program that
ran over-provisioned is rebuilt once at the measured sizes; the capacities a
statement settles at are kept (runtime/capstore), so its next execution runs
one cached program — degrade to recompile, never to wrong answers.
"""

from __future__ import annotations

import hashlib
import math
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..metadata import CatalogManager, Metadata, Session
from ..planner.fragmenter import (
    ExchangeType,
    Partitioning,
    RemoteSourceNode,
    SubPlan,
    plan_fragments,
)
from ..planner.plan import LogicalPlan, OutputNode, PlanNode, TableScanNode, visit_plan
from ..planner.stats import StatsEstimator
from ..runtime import cachestore, capstore, kernelcost
from ..runtime.adaptive import (
    _AdaptiveTracedExecutor,
    candidate_nodes,
    plan_capacities,
    settled_capacity,
    tight_capacity,
)
from ..runtime.executor import (
    Relation,
    _concat_scan_pages,
    _load_splits,
    _round_capacity,
)
from ..runtime.local import QueryResult
from ..runtime.memory import page_bytes
from ..runtime.metrics import REGISTRY
from ..runtime.traced import is_traceable
from ..runtime.tracing import SYNC_PREFIX, TRACER
from ..spi.page import Column, Page
from . import exchange
from .mesh import make_mesh


class MeshLoweringError(Exception):
    """Plan cannot lower to a single shard_map program (host syncs needed)."""


def _pad_column(c: Column, pad: int) -> Column:
    if not pad:
        return c
    return Column(
        c.type,
        jnp.pad(c.data, [(0, pad)] + [(0, 0)] * (c.data.ndim - 1)),
        jnp.pad(c.valid, (0, pad)),
        c.dictionary,
    )


@dataclass
class _ScanSpec:
    """One table scan's sharded input page + its fragment/scan identity."""

    fragment_id: int
    page: Page  # global page, device_put with P(axis) sharding
    symbols: Tuple[str, ...]


# a fragment of these partitionings runs whole, the same on every shard
_REPLICATED = (Partitioning.SINGLE, Partitioning.COORDINATOR_ONLY)
# attempts a statement's first execution may take before the tier gives it up
_MAX_ATTEMPTS = 6
# the estimator's margin (plan_capacities' own), over a shard's even share
_SEED_MARGIN = 2.0
# the share of a device's memory (its allocator's `bytes_limit`) that the
# tables' shards kept between statements may take; a backend that reports no
# limit (the CPU's) is reckoned as one v5e chip's 16 GiB
_STORE_SHARE = 0.25
_UNREPORTED_BYTES_LIMIT = 16 << 30

ATTEMPTS_COUNTER = "trino_tpu_mesh_program_attempts_total"
ATTEMPTS_HELP = (
    "runs of a mesh tier program: one a statement whose capacities held, "
    "and one more for each overflow retry and for the re-run at measured sizes"
)
RETRIES_COUNTER = "trino_tpu_mesh_overflow_retries_total"
RETRIES_HELP = (
    "mesh tier programs rebuilt because a narrowing point or an exchange "
    "bucket overflowed"
)
COLUMNS_COUNTER = "trino_tpu_mesh_shard_columns_total"
COLUMNS_HELP = (
    "columns of the mesh tier's scans by where their shards came from: hit "
    "(on the mesh since an earlier statement), miss (padded and put by this "
    "one), stale (dropped because their table's version token had changed)"
)


def _count_columns(result: str, columns: int) -> None:
    if columns:
        REGISTRY.counter(
            COLUMNS_COUNTER, {"result": result}, help=COLUMNS_HELP
        ).inc(columns)


def _table_token(metadata, handle) -> Optional[str]:
    """The version token of the table a scan reads, as the warm-path caches
    read it (`cachestore.table_version`: equal tokens imply equal data), or
    None where nothing may be kept by it: a connector that gives none, or one
    that answers BYPASS."""
    pin = handle.connector_handle
    pinned = str(pin["snapshot_id"]) if isinstance(pin, dict) and "snapshot_id" in pin else None
    name = handle.schema_table
    token = cachestore.table_version(metadata, handle.catalog, name.schema, name.table, pinned)
    return None if token == cachestore.BYPASS else token


def _column_bytes(column: Column) -> int:
    """`page_bytes`' count of one column."""
    return page_bytes(Page((column,), np.empty(0, bool)))


@dataclass
class _TableShards:
    """One version of one table as it lies on the mesh: its `active` and
    every column a statement has scanned so far, each padded to
    ``per_shard * n`` rows and placed with ``P(axis)``."""

    connector: object  # held, so that its id stays its own
    handle: object  # the scan's handle as resolved: the version is read by it
    token: str
    rows: int  # the capacity of the scan's page as loaded, before padding
    active: jax.Array
    columns: Dict[int, Column] = field(default_factory=dict)  # by the table's column index
    loaded_bytes: Dict[int, int] = field(default_factory=dict)  # a column as loaded, unpadded
    device_bytes: int = 0  # what one device holds of `active` and `columns`


class _ShardStore:
    """The tables' shards that stay on the mesh between statements, so that a
    column is resharded once per version of its table and not once per
    statement. Keyed by what decides the bytes: the connector instance and
    the scan's handle as resolved (the owner's mesh and the per-shard
    capacity follow from them), each entry held to the version token it was
    loaded under. Least recently used tables go once a device's share
    (``budget``) is passed. One lock: the statement that misses a column puts
    it while the others wait, and then find it."""

    def __init__(self, mesh):
        self.lock = threading.Lock()
        self.tables: "OrderedDict[tuple, _TableShards]" = OrderedDict()  # oldest use first
        limits = [(d.memory_stats() or {}).get("bytes_limit") for d in mesh.devices.flat]
        self.budget = int(_STORE_SHARE * min(b or _UNREPORTED_BYTES_LIMIT for b in limits))
        self._n = mesh.devices.size

    def device_bytes(self) -> int:
        return sum(t.device_bytes for t in self.tables.values())

    def drop_stale(self, metadata) -> None:
        """Drop every table whose version token is no longer the one its
        shards were loaded under (INSERT, DELETE / UPDATE / MERGE, DROP,
        CREATE and CTAS all advance it)."""
        for key, table in list(self.tables.items()):
            if _table_token(metadata, table.handle) != table.token:
                self._drop_stale(key)

    def _drop_stale(self, key) -> None:
        _count_columns("stale", len(self.tables.pop(key).columns))

    def find(self, key, token) -> Optional[_TableShards]:
        """The table kept under ``key``, now the most recently used one, if
        ``token`` is still the one it was loaded under; else it is dropped."""
        table = self.tables.get(key)
        if table is None:
            return None
        if table.token != token:
            self._drop_stale(key)
            return None
        self.tables.move_to_end(key)
        return table

    def keep(self, key, table: _TableShards, columns: Dict[int, Column], loaded_bytes) -> None:
        """Add ``columns`` to ``table`` under ``key``, then let the least
        recently used other tables go while a device holds more than the
        budget; a table that passes it alone is not kept."""
        table.columns.update(columns)
        table.loaded_bytes.update(loaded_bytes)
        leaves = jax.tree_util.tree_leaves((table.active, tuple(table.columns.values())))
        table.device_bytes = sum(x.nbytes for x in leaves) // self._n
        self.tables[key] = table
        self.tables.move_to_end(key)
        while self.device_bytes() > self.budget:
            self.tables.popitem(last=False)

    def clear(self) -> None:
        self.tables.clear()


class _MeshFragmentExecutor(_AdaptiveTracedExecutor):
    """Executes one fragment per-shard inside shard_map, on the narrowing
    executor's capacities and records. Scans read this shard's block of the
    sharded page; RemoteSources turn into collectives, and a repartitioning
    one is a narrowing point of its own: its bucket capacity, the overflow of
    its all_to_all and the bucket it would have needed."""

    def __init__(
        self,
        plan,
        metadata,
        session,
        staged: Dict[int, Tuple[Page, Partitioning]],
        scan_pages: List[Page],
        num_partitions: int,
        axis_name: str,
        capacities: Dict[int, int],
        records: list,
        join_capacity_factor: float,
    ):
        super().__init__(
            plan, metadata, session, dict(enumerate(scan_pages)),
            capacities, records, join_capacity_factor,
        )
        self._staged = staged
        self._n = num_partitions
        self._axis = axis_name

    def _exchange(self, node: RemoteSourceNode, page: Page, target) -> Page:
        """all_to_all of ``page`` by ``target``. The bucket is the hint a
        retry measured, else twice the even share of the producer's page AS
        TRACED: a narrowed producer makes a narrow exchange."""
        unhinted = _round_capacity(max(2 * page.capacity // self._n, 8), base=8)
        bucket_cap = self.capacities.get(id(node)) or unhinted
        out, overflow = exchange.all_to_all_page(
            page, target, self._n, self._axis, bucket_cap=bucket_cap
        )
        demand = exchange.bucket_demand(page, target, self._n, self._axis)
        self._record(id(node), overflow, demand, bucket_cap, unhinted)
        return out

    def _exec_RemoteSourceNode(self, node: RemoteSourceNode) -> Relation:
        page, producer_part = self._staged[node.fragment_id]
        single_producer = producer_part in _REPLICATED
        me = jax.lax.axis_index(self._axis).astype(jnp.int32)
        if node.exchange_type == ExchangeType.REPARTITION_RANGE:
            o = node.orderings[0]
            key_idx = node.symbols.index(o.symbol)
            if single_producer:
                # replicated producer: each shard keeps its key range — same
                # sample-sort boundaries, no collective needed
                c = page.columns[key_idx]
                from ..ops import kernels as K

                # sorted dictionary codes are order keys (see
                # exchange.range_targets)
                key = K.encode_sort_column(c.data, c.valid, o.ascending, o.nulls_first)
                skey = jnp.sort(jnp.where(page.active, key, jnp.int64(K.INT64_MAX)))
                cnt = jnp.sum(page.active.astype(jnp.int64))
                pos = (jnp.arange(1, self._n, dtype=jnp.int64) * cnt) // self._n
                bounds = skey[jnp.clip(pos, 0, page.capacity - 1)]
                target = jnp.sum(
                    (key[:, None] >= bounds[None, :]).astype(jnp.int32), axis=1
                )
                out = Page(page.columns, page.active & (target == me))
            else:
                target = exchange.range_targets(
                    page, key_idx, o.ascending, o.nulls_first, self._n, self._axis
                )
                out = self._exchange(node, page, target)
            return Relation(out, node.symbols)
        if node.exchange_type == ExchangeType.REPARTITION:
            key_idx = [node.symbols.index(k) for k in node.partition_keys]
            target = exchange.hash_targets(page, key_idx, self._n)
            if single_producer:
                # replicated producer: repartitioning needs NO collective —
                # each shard keeps exactly the rows that hash to it
                out = Page(page.columns, page.active & (target == me))
            else:
                out = self._exchange(node, page, target)
            return Relation(out, node.symbols)
        # GATHER / BROADCAST: consumers need the complete producer output.
        # A replicated producer already satisfies that without a collective.
        if single_producer:
            return Relation(page, node.symbols)
        gathered = _all_gather_page(page, self._axis)
        return Relation(gathered, node.symbols)


@dataclass
class _MeshProgram:
    """One compiled shard_map program and what its trace found: the points
    that reported (their ordinals in ``MeshQueryRunner._points`` order), the
    static capacity each ran at, the one it would run at unhinted, and which
    of them no hint can narrow."""

    fn: object
    ordinals: List[int] = field(default_factory=list)
    ran: List[int] = field(default_factory=list)
    unhinted: List[int] = field(default_factory=list)
    fixed: List[bool] = field(default_factory=list)


def _first_block(x: jax.Array) -> jax.Array:
    """The block of ``x`` that starts at row 0, as the device that holds it
    holds it: a view of that buffer, no slice. In the one process that drives
    the mesh every shard is addressable."""
    first = [s.data for s in x.addressable_shards if not s.index[0].start]
    assert first, "shard 0's block is not addressable from this process"
    return first[0]


def _all_gather_page(page: Page, axis_name: str) -> Page:
    """Every shard's rows on every shard: each leaf of the page, a nested
    column's `lengths`, `elem_valid` and `children` too, gathered along its
    row axis."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.all_gather(x, axis_name, axis=0, tiled=True), page
    )


class MeshQueryRunner:
    """SQL -> fragments -> ONE shard_map program over the device mesh.

    The planner-connected ICI execution path: the same SubPlan the DCN-tier
    DistributedQueryRunner schedules stage-by-stage compiles here into a single
    collective program (the intra-pod tier of SURVEY.md §5.8's two-level
    design). Plans with host-sync operators raise MeshLoweringError — callers
    (DistributedQueryRunner) fall back to the staged path.
    """

    def __init__(
        self,
        session: Optional[Session] = None,
        mesh=None,
        n_devices: Optional[int] = None,
        axis_name: str = "workers",
        catalogs: Optional[CatalogManager] = None,
        metadata: Optional[Metadata] = None,
    ):
        self.catalogs = catalogs or CatalogManager()
        self.metadata = metadata or Metadata(self.catalogs)
        self.session = session or Session()
        self.mesh = mesh if mesh is not None else make_mesh(
            n_devices or len(jax.devices())
        )
        self.axis = axis_name
        self.n = self.mesh.shape[axis_name]
        # compiled shard_map programs keyed by (plan structure, capacities) —
        # repeated queries reuse the XLA executable (the PageFunctionCompiler
        # cache discipline applied to whole multi-fragment programs)
        self._program_cache: Dict[tuple, object] = {}
        # the scanned tables' shards, kept over the mesh between statements
        self._shards = _ShardStore(self.mesh)
        self._sharding = NamedSharding(self.mesh, P(self.axis))

    @staticmethod
    def tpch(scale: float = 0.01, n_devices: Optional[int] = None, **kw):
        from ..connectors.tpch import TpchConnector

        runner = MeshQueryRunner(
            Session(catalog="tpch", schema="sf" + f"{scale:g}".replace(".", "_")),
            n_devices=n_devices,
        )
        runner.catalogs.register("tpch", TpchConnector(scale=scale, **kw))
        return runner

    # ----------------------------------------------------------------- planning

    def plan_distributed(self, sql: str) -> SubPlan:
        return plan_fragments(sql, self.metadata, self.session)

    # ---------------------------------------------------------------- execution

    def execute(self, sql: str) -> QueryResult:
        with TRACER.statement(sql):
            subplan = self.plan_distributed(sql)
            names, page = self.execute_subplan(subplan)
            return QueryResult(names, self.gather(page))

    def gather(self, out_page: Page) -> list:
        """The answer's rows on the host (span `mesh:gather`), from the root
        page as `execute_subplan` hands it back: shard 0's block of every
        leaf as it lies on device 0, all of them fetched in one copy, and
        the row encoding. No device program runs."""
        with TRACER.span("mesh:gather") as span:
            # out_specs P(axis) stacks each shard's (replicated) root block;
            # the root fragment is SINGLE so shard 0's block is the complete
            # answer. Mapping over the leaves keeps a nested column's
            # `lengths`, `elem_valid` and `children`; one `device_get`
            # starts every copy before it waits for any
            leaves, tree = jax.tree_util.tree_flatten(out_page)
            blocks = jax.device_get([_first_block(x) for x in leaves])
            rows = jax.tree_util.tree_unflatten(tree, blocks).to_pylist()
            span.attributes.update(
                rows=len(rows), arrays=len(blocks), bytes=sum(b.nbytes for b in blocks)
            )
        return rows

    def execute_subplan(self, subplan: SubPlan) -> Tuple[List[str], Page]:
        """Spans `mesh:lower` (the plan checked, keyed and fingerprinted,
        the capacities read or seeded, the program in hand; the scans'
        `mesh:load_scan` and `mesh:shard` inside it; once more for a rebuilt
        program) and `mesh:program` (per attempt, its read of what it
        measured a `sync:mesh_measured` inside it) under the caller's
        statement root; `gather` adds `mesh:gather`. Returns the column names
        and the root page as the program left it, every shard's block of it.

        A statement's first execution settles its capacities: seeded from the
        estimator (a shard's share, with its margin), grown where a point
        overflowed to what it measured, and rebuilt once where a point did
        not run at the capacity its count asks for (`settled_capacity`). What
        it settles at is kept by the plan's fingerprint (runtime/capstore),
        so the next execution is one attempt of a cached program."""
        from ..runtime import observability as obs

        collector = obs.current_collector()
        join_factor = float(self.session.get("mesh_join_capacity_factor") or 1.0)

        # from the plan to the program in hand; the scans' resharding
        # (`mesh:load_scan`, `mesh:shard`) lies inside, the shapes it gives
        # are part of the program's key
        with TRACER.span("mesh:lower") as lowering:
            self._check_lowerable(subplan)
            scan_specs, scan_counts = self._shard_scans(subplan)
            root = subplan.root_fragment.root
            assert isinstance(root, OutputNode)
            flat_pages = [s.page for s in scan_specs]
            plan_key = repr(
                [(f.fragment_id, f.partitioning, f.root) for f in subplan.fragments]
            )
            shapes = (self.n, tuple(p.capacity for p in flat_pages), join_factor)
            points = self._points(subplan)
            fingerprint = hashlib.sha256(repr((plan_key, shapes)).encode()).hexdigest()
            kept = capstore.load(fingerprint)
            settled = kept is not None and len(kept) == len(points)
            caps = list(kept) if settled else self._seed_capacities(subplan, points)

            def program_for(caps, lowering):
                """(the program at these capacities, whether it was kept); the
                span `mesh:lower` it ends says which, and of how many points."""
                cache_key = (plan_key, shapes, tuple(caps))
                program = self._program_cache.get(cache_key)
                cached = program is not None
                if program is None:
                    program = self._build_program(subplan, scan_counts, caps, join_factor)
                    self._program_cache[cache_key] = program
                elif collector is not None:
                    collector.add_count("compile_cache_hits")
                lowering.attributes.update(
                    cached=cached, points=len(points), settled=settled
                )
                return program, cached

            program, cached = program_for(caps, lowering)
        resized = False
        for attempt in range(_MAX_ATTEMPTS):
            if attempt:  # rebuilt at other capacities: a lowering of its own
                with TRACER.span("mesh:lower", attempt=attempt) as lowering:
                    program, cached = program_for(caps, lowering)
            # the one shard_map program and the one read of what it measured
            # (the flight recorder keeps the span under the category `mesh`);
            # the read is the span's child, so its own time is the dispatch
            with TRACER.span(
                "mesh:program", cat="mesh", attempt=attempt, cached=cached,
            ) as ran, obs.compile_window() as cw:
                out_page, measured = program.fn(*flat_pages)
                with TRACER.span(SYNC_PREFIX + "mesh_measured"):
                    measured = np.asarray(measured)
                k = len(program.ordinals)
                overflow, actual = measured[1 : 1 + k], measured[1 + k :]
                held = int(measured[0]) == 0
                ran.attributes.update(
                    narrow_points=k,
                    narrow_rows=int(actual.sum()),
                    narrow_capacity=sum(program.ran),
                    overflowed=int((overflow > 0).sum()),
                )
            REGISTRY.counter(ATTEMPTS_COUNTER, help=ATTEMPTS_HELP).inc()
            if collector is not None:
                collector.add_time(
                    "device_busy_secs",
                    max(ran.duration_secs - cw.seconds, 0.0),
                )
            if held:
                if settled or resized:
                    break
                # once, the program this statement keeps is rebuilt at the
                # measured sizes: where a point ran wider than its count
                # needs, or was narrowed where that does not halve it
                keep = [
                    None if fixed else settled_capacity(a, unhinted)
                    for a, unhinted, fixed in zip(
                        actual, program.unhinted, program.fixed
                    )
                ]
                if all(
                    (hint or unhinted) == cap
                    for hint, unhinted, cap in zip(keep, program.unhinted, program.ran)
                ):
                    break
                for o, hint in zip(program.ordinals, keep):
                    caps[o] = hint
                resized = True
                continue
            # degrade to recompile, never to wrong answers: the points that
            # overflowed grow to what they measured (the first one's count is
            # exact; one downstream of it may need another attempt)
            REGISTRY.counter(RETRIES_COUNTER, help=RETRIES_HELP).inc()
            if collector is not None:
                collector.add_count("overflow_retries")
            obs.RECORDER.instant(
                "mesh_overflow_retry", "mesh", attempt=attempt
            )
            for o, a, over in zip(program.ordinals, actual, overflow):
                if over > 0:
                    caps[o] = tight_capacity(a)
            settled = False
        else:
            raise MeshLoweringError("capacity retry limit exceeded")
        if caps != kept:
            capstore.save(fingerprint, caps)

        return list(root.column_names), out_page

    # ----------------------------------------------------------------- internals

    def _check_lowerable(self, subplan: SubPlan) -> None:
        """Reject plans whose SPMD execution would be wrong, not just slow.

        - cross / non-equi joins get NO exchange from the planner, so both
          sides land in one fragment: each shard would join only its own
          blocks, silently dropping cross-shard pairs.
        - a fragment whose partitioning is not SOURCE but which contains a
          table scan (e.g. scan UNION Values -> SINGLE) would be consumed as
          replicated while its scan rows are actually sharded.
        The staged (DCN-tier) runner handles these shapes correctly.
        """
        from ..planner.plan import JoinNode

        for frag in subplan.fragments:
            if not is_traceable(
                LogicalPlan(frag.root, subplan.types),
                allow_joins=True,
                extra_types=(RemoteSourceNode,),
            ):
                raise MeshLoweringError(
                    f"fragment {frag.fragment_id} contains host-sync operators"
                )
            scans = 0
            bad = []

            def check(n: PlanNode):
                nonlocal scans
                if isinstance(n, TableScanNode):
                    scans += 1
                if isinstance(n, JoinNode) and not n.criteria:
                    bad.append("cross or non-equi join (no exchange inserted)")

            visit_plan(frag.root, check)
            if bad:
                raise MeshLoweringError(bad[0])
            if scans > 1:
                raise MeshLoweringError(
                    "multiple scans in one fragment (no co-location exchange)"
                )
            if scans and frag.partitioning != Partitioning.SOURCE:
                raise MeshLoweringError(
                    f"scan in a {frag.partitioning.value} fragment would be "
                    "consumed as replicated"
                )

    def _shard_scans(self, subplan: SubPlan):
        """Every fragment's scans as mesh-sharded global pages (splits ->
        shards), with per-column dictionaries unified BEFORE sharding so the
        static dictionary aux is identical on every shard. The shards of a
        table that gives a version token stay on the mesh (`_ShardStore`):
        what a token no longer vouches for is dropped before anything is put."""
        scan_specs: List[_ScanSpec] = []
        scan_counts: Dict[int, int] = {}
        with self._shards.lock:
            self._shards.drop_stale(self.metadata)
        for frag in subplan.fragments:
            scans: List[TableScanNode] = []

            def collect(n: PlanNode):
                if isinstance(n, TableScanNode):
                    scans.append(n)

            visit_plan(frag.root, collect)
            scan_counts[frag.fragment_id] = len(scans)
            for node in scans:
                symbols = tuple(s for s, _ in node.assignments)
                scan_specs.append(_ScanSpec(frag.fragment_id, self._shard_scan(node), symbols))
        return scan_specs, scan_counts

    def _shard_scan(self, node: TableScanNode) -> Page:
        """One scan's page over the mesh (spans `mesh:load_scan`, then
        `mesh:shard`), assembled from the columns the store holds and the
        ones this statement loads on device 0, pads and puts, one column at a
        time. What it puts is kept where the table's version token reads the
        same before and after the load (the mixed-snapshot guard of
        `cachestore.resolve_versions`); a statement whose tokens differ, or
        whose connector gives none, uses what it loaded and keeps nothing."""
        connector, handle, col_indexes = self._resolve_scan(node)
        wanted = list(dict.fromkeys(col_indexes))
        token = _table_token(self.metadata, handle)
        key = None
        if token is not None:
            try:
                hash(handle)
                key = (id(connector), handle)
            except TypeError:  # a handle that cannot key a dictionary
                pass
        with self._shards.lock:
            kept = self._shards.find(key, token) if key is not None else None
            with TRACER.span(
                "mesh:load_scan", table=str(node.table.schema_table)
            ) as loaded:
                missing = [i for i in wanted if kept is None or i not in kept.columns]
                page = None
                if missing or kept is None:
                    # the splits' pages concatenated on device 0
                    page = self._load_columns(connector, handle, missing)
                    consistent = kept is None or page.capacity == kept.rows
                    if key is not None and (
                        not consistent or _table_token(self.metadata, handle) != token
                    ):
                        # written under the load, or packed another way than
                        # the columns kept: nothing kept is used, nothing is kept
                        key = None
                        if kept is not None:
                            kept, missing = None, wanted
                            page = self._load_columns(connector, handle, wanted)
                fresh = dict(zip(missing, page.columns)) if page is not None else {}
                fresh_bytes = {i: _column_bytes(c) for i, c in fresh.items()}
                rows = page.capacity if kept is None else kept.rows
                cached = len(wanted) - len(fresh)
                loaded.attributes.update(
                    rows=rows, cached=cached,
                    bytes=rows + sum(
                        fresh_bytes[i] if i in fresh else kept.loaded_bytes[i] for i in wanted
                    ),
                )
            per_shard = _round_capacity(max(math.ceil(rows / self.n), 1), base=8)
            # awaited here: the program then starts on every device at
            # once, with no collective left waiting for a shard still on
            # its way, and the span covers the transfer it names
            with TRACER.span("mesh:shard") as sharding_span:
                pad = per_shard * self.n - rows
                put = {i: self._put(_pad_column(c, pad)) for i, c in fresh.items()}
                moved = sum(_column_bytes(c) for c in put.values())
                new = kept is None
                if new:
                    active = self._put(jnp.pad(page.active, (0, pad)))
                    kept = _TableShards(connector, handle, token, rows, active)
                    moved += active.size
                columns = {**kept.columns, **put}
                if key is not None and (put or new):
                    self._shards.keep(key, kept, put, fresh_bytes)
                sharding_span.attributes.update(h2d_bytes=moved, cached=cached, put=len(put))
            _count_columns("hit", cached)
            _count_columns("miss", len(put))
        return Page(tuple(columns[i] for i in col_indexes), kept.active)

    def _put(self, tree):
        """``tree`` over the mesh axis, waited for; a put that ends in
        RESOURCE_EXHAUSTED empties the store and is tried once more."""
        try:
            return jax.block_until_ready(jax.device_put(tree, self._sharding))
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            self._shards.clear()
            return jax.block_until_ready(jax.device_put(tree, self._sharding))

    def _resolve_scan(self, node: TableScanNode):
        """(the connector, the handle with the scan's constraint applied, the
        table's index of each column the scan assigns)."""
        connector = self.metadata.connector_for(node.table)
        handle = node.table
        if node.constraint.domains:
            absorbed = self.metadata.apply_filter(handle, node.constraint)
            if absorbed is not None:
                handle = absorbed
        meta = self.metadata.get_table_metadata(node.table)
        return connector, handle, [meta.column_index(c) for _, c in node.assignments]

    def _load_columns(self, connector, handle, col_indexes) -> Page:
        splits = connector.split_manager().get_splits(handle)
        provider = connector.page_source_provider()
        pages = _load_splits(provider, splits, col_indexes, self.session)
        if not pages:
            # fully pruned scan: the staged (DCN) path handles it; keep the
            # mesh program's scan layout uniform instead of special-casing
            raise MeshLoweringError("empty scan (fully pruned) on mesh path")
        return _concat_scan_pages(pages)

    def _load_scan(self, node: TableScanNode) -> Page:
        connector, handle, col_indexes = self._resolve_scan(node)
        return self._load_columns(connector, handle, col_indexes)

    @staticmethod
    def _points(subplan: SubPlan) -> List[PlanNode]:
        """Every node a capacity may be chosen for, fragment by fragment in
        canonical preorder: the narrowing executor's candidates and the
        remote sources (a repartitioning one sizes its bucket). A capacity
        vector is a list over these, None where nothing is hinted."""
        return [
            node
            for frag in subplan.fragments
            for node in candidate_nodes(
                LogicalPlan(frag.root, subplan.types), extra=(RemoteSourceNode,)
            )
        ]

    def _seed_capacities(self, subplan: SubPlan, points) -> List[Optional[int]]:
        """The estimator's capacities, per shard: a fragment that runs on its
        own part of the rows gets an ``n``-th of each estimate (with the
        margin), a replicated one the whole. A remote source stands for its
        producer's root; an exchange's bucket is not seeded (its default
        follows the producer's page)."""
        est = StatsEstimator(self.metadata, subplan.types)
        frag_by_id = {f.fragment_id: f for f in subplan.fragments}
        seeded: Dict[int, int] = {}
        for frag in subplan.fragments:  # producers first

            def assume(node: PlanNode):
                if isinstance(node, RemoteSourceNode):
                    try:
                        est.assume(node, est.stats(frag_by_id[node.fragment_id].root))
                    except Exception:  # estimator gaps must never kill execution
                        pass

            visit_plan(frag.root, assume)
            shards = 1 if frag.partitioning in _REPLICATED else self.n
            seeded.update(
                plan_capacities(
                    LogicalPlan(frag.root, subplan.types), self.metadata,
                    margin=_SEED_MARGIN / shards, estimator=est,
                )
            )
        return [seeded.get(id(node)) for node in points]

    def _build_program(self, subplan, scan_counts, caps, join_factor) -> _MeshProgram:
        root_id = subplan.root_fragment.fragment_id
        n, axis = self.n, self.axis
        ordinal = {id(node): i for i, node in enumerate(self._points(subplan))}
        hints = {key: caps[i] for key, i in ordinal.items() if caps[i] is not None}
        program = _MeshProgram(None)

        def body(*flat_scan_pages: Page):
            staged: Dict[int, Tuple[Page, Partitioning]] = {}
            records: list = []
            ran: Dict[int, Tuple[int, int]] = {}
            fixed: set = set()
            unkeyed: List[jnp.ndarray] = []
            it = iter(flat_scan_pages)
            for frag in subplan.fragments:
                frag_scans = [next(it) for _ in range(scan_counts[frag.fragment_id])]
                executor = _MeshFragmentExecutor(
                    LogicalPlan(frag.root, subplan.types),
                    self.metadata,
                    self.session,
                    staged,
                    frag_scans,
                    n,
                    axis,
                    hints,
                    records,
                    join_factor,
                )
                if isinstance(frag.root, OutputNode):
                    rel = executor.eval(frag.root.source)
                    page = Page(
                        tuple(rel.column_for(s) for s in frag.root.symbols),
                        rel.page.active,
                    )
                else:
                    rel = executor.eval(frag.root)
                    page = Page(
                        tuple(
                            rel.column_for(s) for s in frag.root.output_symbols
                        ),
                        rel.page.active,
                    )
                staged[frag.fragment_id] = (page, frag.partitioning)
                ran.update(executor.ran)
                fixed.update(executor._read_masked)
                unkeyed.extend(executor.overflows)
            root_page = staged[root_id][0]
            keys = [key for key, _, _ in records]
            overflows = [over.astype(jnp.int64) for _, over, _ in records]
            counts = [count.astype(jnp.int64) for _, _, count in records]
            total = jnp.int64(0)
            for o in overflows + unkeyed:
                total = total + o.astype(jnp.int64)
            # psum makes the indicator globally visible (values already psum'd
            # just scale by n — the host only tests > 0); a point's overflow
            # and count are the most over the shards, which share one shape
            # (the TPU reduces 64-bit integers by sum alone: the most is taken
            # in 32 bits, which hold any shard's row count)
            measured = [jax.lax.psum(total, axis)[None]]
            if records:
                narrow = jnp.clip(
                    jnp.stack(overflows + counts), 0, jnp.iinfo(jnp.int32).max
                ).astype(jnp.int32)
                measured.append(jax.lax.pmax(narrow, axis).astype(jnp.int64))
            program.ordinals = [ordinal[key] for key in keys]
            program.ran = [ran[key][0] for key in keys]
            program.unhinted = [ran[key][1] for key in keys]
            program.fixed = [key in fixed for key in keys]
            return root_page, jnp.concatenate(measured)

        program.fn = kernelcost.jit(
            jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=tuple(P(axis) for _ in range(sum(scan_counts.values()))),
                out_specs=(P(axis), P()),
            )
        )
        return program
