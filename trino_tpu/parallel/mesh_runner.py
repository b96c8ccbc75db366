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

Static-shape discipline: joins get a fixed output capacity and the program
returns a summed OVERFLOW scalar (join emits beyond capacity + all_to_all
bucket overflow). The runner host-checks it and retries with doubled
capacities — degrade to recompile, never to wrong answers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..metadata import CatalogManager, Metadata, Session
from ..planner import LogicalPlanner, optimize
from ..planner.fragmenter import (
    ExchangeType,
    Partitioning,
    PlanFragment,
    RemoteSourceNode,
    SubPlan,
    add_exchanges,
    create_fragments,
)
from ..planner.plan import LogicalPlan, OutputNode, PlanNode, TableScanNode, visit_plan
from ..runtime import kernelcost
from ..runtime.executor import Relation, _concat_pages, _round_capacity
from ..runtime.local import QueryResult
from ..runtime.memory import page_bytes
from ..runtime.traced import _TracedExecutor, is_traceable
from ..runtime.tracing import TRACER
from ..spi.page import Column, Page
from ..sql import parse_statement
from . import exchange
from .mesh import make_mesh


class MeshLoweringError(Exception):
    """Plan cannot lower to a single shard_map program (host syncs needed)."""


def _pad_page(page: Page, capacity: int) -> Page:
    if page.capacity == capacity:
        return page
    pad = capacity - page.capacity
    cols = tuple(
        Column(
            c.type,
            jnp.pad(c.data, [(0, pad)] + [(0, 0)] * (c.data.ndim - 1)),
            jnp.pad(c.valid, (0, pad)),
            c.dictionary,
        )
        for c in page.columns
    )
    return Page(cols, jnp.pad(page.active, (0, pad)))


@dataclass
class _ScanSpec:
    """One table scan's sharded input page + its fragment/scan identity."""

    fragment_id: int
    page: Page  # global page, device_put with P(axis) sharding
    symbols: Tuple[str, ...]


class _MeshFragmentExecutor(_TracedExecutor):
    """Executes one fragment per-shard inside shard_map. Scans read this
    shard's block of the sharded page; RemoteSources turn into collectives."""

    def __init__(
        self,
        plan,
        metadata,
        session,
        staged: Dict[int, Tuple[Page, Partitioning]],
        scan_pages: List[Page],
        frag_by_id: Dict[int, PlanFragment],
        num_partitions: int,
        axis_name: str,
        bucket_caps: Dict[int, int],
        join_capacity_factor: float,
    ):
        super().__init__(
            plan, metadata, session, dict(enumerate(scan_pages)),
            join_capacity_factor=join_capacity_factor,
        )
        self._staged = staged
        self._frag_by_id = frag_by_id
        self._n = num_partitions
        self._axis = axis_name
        self._bucket_caps = bucket_caps

    def _exec_RemoteSourceNode(self, node: RemoteSourceNode) -> Relation:
        page, producer_part = self._staged[node.fragment_id]
        single_producer = producer_part in (
            Partitioning.SINGLE,
            Partitioning.COORDINATOR_ONLY,
        )
        if node.exchange_type == ExchangeType.REPARTITION_RANGE:
            o = node.orderings[0]
            key_idx = node.symbols.index(o.symbol)
            if single_producer:
                # replicated producer: each shard keeps its key range — same
                # sample-sort boundaries, no collective needed
                me = jax.lax.axis_index(self._axis).astype(jnp.int32)
                c = page.columns[key_idx]
                from ..ops import kernels as K

                # sorted dictionary codes are order keys (see
                # exchange.repartition_by_range)
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
                bucket_cap = self._bucket_caps[node.fragment_id]
                out, overflow = exchange.repartition_by_range(
                    page, key_idx, o.ascending, o.nulls_first,
                    self._n, self._axis, bucket_cap=bucket_cap,
                )
                self.overflows.append(overflow)
            return Relation(out, node.symbols)
        if node.exchange_type == ExchangeType.REPARTITION:
            if single_producer:
                # replicated producer: repartitioning needs NO collective —
                # each shard keeps exactly the rows that hash to it
                keys = exchange.hash_key_columns(
                    [page.columns[node.symbols.index(k)] for k in node.partition_keys]
                )
                if keys:
                    target = exchange.partition_ids(keys, self._n)
                else:
                    target = jnp.zeros(page.capacity, dtype=jnp.int32)
                me = jax.lax.axis_index(self._axis).astype(jnp.int32)
                out = Page(page.columns, page.active & (target == me))
            else:
                key_idx = [node.symbols.index(k) for k in node.partition_keys]
                bucket_cap = self._bucket_caps[node.fragment_id]
                out, overflow = exchange.repartition_by_keys(
                    page, key_idx, self._n, self._axis, bucket_cap=bucket_cap
                )
                self.overflows.append(overflow)
            return Relation(out, node.symbols)
        # GATHER / BROADCAST: consumers need the complete producer output.
        # A replicated producer already satisfies that without a collective.
        if single_producer:
            return Relation(page, node.symbols)
        gathered = _all_gather_page(page, self._axis)
        return Relation(gathered, node.symbols)


def _all_gather_page(page: Page, axis_name: str) -> Page:
    cols = tuple(
        Column(
            c.type,
            jax.lax.all_gather(c.data, axis_name, axis=0, tiled=True),
            jax.lax.all_gather(c.valid, axis_name, axis=0, tiled=True),
            c.dictionary,
        )
        for c in page.columns
    )
    active = jax.lax.all_gather(page.active, axis_name, axis=0, tiled=True)
    return Page(cols, active)


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
        stmt = parse_statement(sql)
        planner = LogicalPlanner(self.metadata, self.session)
        plan = planner.plan(stmt)
        plan = optimize(plan, self.metadata, self.session)
        plan = add_exchanges(plan, self.metadata, self.session)
        return create_fragments(plan)

    # ---------------------------------------------------------------- execution

    def execute(self, sql: str) -> QueryResult:
        with TRACER.statement(sql):
            subplan = self.plan_distributed(sql)
            names, page = self.execute_subplan(subplan)
            return QueryResult(names, self.gather(page))

    @staticmethod
    def gather(page: Page) -> list:
        """The answer's rows on the host (span `mesh:gather`): the wait for
        the mesh program, the copy from device 0 and the row encoding."""
        with TRACER.span("mesh:gather") as span:
            rows = page.to_pylist()
            span.attributes["rows"] = len(rows)
        return rows

    def execute_subplan(self, subplan: SubPlan) -> Tuple[List[str], Page]:
        """Spans `mesh:load_scan`, `mesh:shard` (per scan) and `mesh:program`
        (per attempt) under the caller's statement root; `gather` adds
        `mesh:gather`."""
        self._check_lowerable(subplan)
        scan_specs, scan_counts = self._shard_scans(subplan)
        root = subplan.root_fragment.root
        assert isinstance(root, OutputNode)

        join_factor = float(self.session.get("mesh_join_capacity_factor") or 1.0)
        bucket_caps = self._initial_bucket_caps(subplan, scan_specs)
        flat_pages = [s.page for s in scan_specs]

        from ..runtime import observability as obs

        collector = obs.current_collector()
        plan_key = repr(
            [(f.fragment_id, f.partitioning, f.root) for f in subplan.fragments]
        )
        for attempt in range(4):
            cache_key = (
                plan_key,
                tuple(p.capacity for p in flat_pages),
                tuple(sorted(bucket_caps.items())),
                join_factor,
            )
            program = self._program_cache.get(cache_key)
            cached = program is not None
            if program is None:
                program = self._build_program(
                    subplan, scan_counts, bucket_caps, join_factor
                )
                self._program_cache[cache_key] = program
            elif collector is not None:
                collector.add_count("compile_cache_hits")
            # the one shard_map program and the read of its overflow flag
            # (the flight recorder keeps the span under the category `mesh`)
            with TRACER.span(
                "mesh:program", cat="mesh", attempt=attempt,
                join_factor=join_factor, cached=cached,
            ) as ran, obs.compile_window() as cw:
                out_page, overflow = program(*flat_pages)
                done = int(overflow) == 0
            if collector is not None:
                collector.add_time(
                    "device_busy_secs",
                    max(ran.duration_secs - cw.seconds, 0.0),
                )
            if done:
                break
            # degrade to recompile, never to wrong answers
            if collector is not None:
                collector.add_count("overflow_retries")
            obs.RECORDER.instant(
                "mesh_overflow_retry", "mesh", attempt=attempt
            )
            join_factor *= 2.0
            bucket_caps = {k: v * 2 for k, v in bucket_caps.items()}
        else:
            raise MeshLoweringError("capacity retry limit exceeded")

        # out_specs P(axis) stacks each shard's (replicated) root block; the
        # root fragment is SINGLE so shard 0's block is the complete answer
        cap = out_page.capacity // self.n
        cols = tuple(
            Column(c.type, c.data[:cap], c.valid[:cap], c.dictionary)
            for c in out_page.columns
        )
        page = Page(cols, out_page.active[:cap])
        return list(root.column_names), page

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
        """Load every fragment's scans as mesh-sharded global pages (splits ->
        shards), with per-column dictionaries unified BEFORE sharding so the
        static dictionary aux is identical on every shard."""
        scan_specs: List[_ScanSpec] = []
        scan_counts: Dict[int, int] = {}
        sharding = NamedSharding(self.mesh, P(self.axis))
        for frag in subplan.fragments:
            scans: List[TableScanNode] = []

            def collect(n: PlanNode):
                if isinstance(n, TableScanNode):
                    scans.append(n)

            visit_plan(frag.root, collect)
            scan_counts[frag.fragment_id] = len(scans)
            for node in scans:
                with TRACER.span(
                    "mesh:load_scan", table=str(node.table.schema_table)
                ) as loaded:
                    page = self._load_scan(node)
                    # the splits' pages concatenated on device 0
                    loaded.attributes["rows"] = page.capacity
                    loaded.attributes["bytes"] = page_bytes(page)
                per_shard = _round_capacity(
                    max(math.ceil(page.capacity / self.n), 1), base=8
                )
                with TRACER.span("mesh:shard") as sharding_span:
                    padded = _pad_page(page, per_shard * self.n)
                    sharded = jax.device_put(padded, sharding)
                    sharding_span.attributes["h2d_bytes"] = page_bytes(padded)
                symbols = tuple(s for s, _ in node.assignments)
                scan_specs.append(_ScanSpec(frag.fragment_id, sharded, symbols))
        return scan_specs, scan_counts

    def _load_scan(self, node: TableScanNode) -> Page:
        connector = self.metadata.connector_for(node.table)
        handle = node.table
        if node.constraint.domains:
            absorbed = self.metadata.apply_filter(handle, node.constraint)
            if absorbed is not None:
                handle = absorbed
        splits = connector.split_manager().get_splits(handle)
        meta = self.metadata.get_table_metadata(node.table)
        col_indexes = [meta.column_index(c) for _, c in node.assignments]
        provider = connector.page_source_provider()
        from ..runtime.executor import _load_splits

        pages = _load_splits(provider, splits, col_indexes, self.session)
        if not pages:
            # fully pruned scan: the staged (DCN) path handles it; keep the
            # mesh program's scan layout uniform instead of special-casing
            raise MeshLoweringError("empty scan (fully pruned) on mesh path")
        return _concat_pages(pages)

    def _initial_bucket_caps(self, subplan, scan_specs) -> Dict[int, int]:
        """bucket_cap per REPARTITION producer fragment: 2x the even share of
        the producer's (estimated) per-shard capacity, pow2-rounded. Overflow
        is detected and retried, so this is a bandwidth/memory tradeoff, not a
        correctness knob."""
        caps: Dict[int, int] = {}
        frag_caps: Dict[int, int] = {}
        for s in scan_specs:
            frag_caps[s.fragment_id] = max(
                frag_caps.get(s.fragment_id, 0), s.page.capacity // self.n
            )
        for frag in subplan.fragments:
            base = frag_caps.get(frag.fragment_id, 0)
            for fid in frag.input_fragments:
                base = max(base, frag_caps.get(fid, 0))
            frag_caps[frag.fragment_id] = max(base, 8)
            caps[frag.fragment_id] = _round_capacity(
                max(2 * frag_caps[frag.fragment_id] // self.n, 8), base=8
            )
        return caps

    def _build_program(self, subplan, scan_counts, bucket_caps, join_factor):
        frag_by_id = {f.fragment_id: f for f in subplan.fragments}
        root_id = subplan.root_fragment.fragment_id
        n, axis = self.n, self.axis

        def body(*flat_scan_pages: Page):
            staged: Dict[int, Tuple[Page, Partitioning]] = {}
            overflows: List[jnp.ndarray] = []
            it = iter(flat_scan_pages)
            for frag in subplan.fragments:
                frag_scans = [next(it) for _ in range(scan_counts[frag.fragment_id])]
                executor = _MeshFragmentExecutor(
                    LogicalPlan(frag.root, subplan.types),
                    self.metadata,
                    self.session,
                    staged,
                    frag_scans,
                    frag_by_id,
                    n,
                    axis,
                    bucket_caps,
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
                overflows.extend(executor.overflows)
            root_page = staged[root_id][0]
            total = jnp.int64(0)
            for o in overflows:
                total = total + o.astype(jnp.int64)
            # psum makes the indicator globally visible (values already psum'd
            # just scale by n — the host only tests > 0)
            total = jax.lax.psum(total, axis)
            return root_page, total

        return kernelcost.jit(
            jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=tuple(P(axis) for _ in range(sum(scan_counts.values()))),
                out_specs=(P(axis), P()),
            )
        )
