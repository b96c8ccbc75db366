"""DistributedQueryRunner: multi-worker stage-by-stage fragment execution.

Reference blueprint: the coordinator scheduling loop of SURVEY.md §3.1 —
PlanFragmenter output scheduled stage by stage (PipelinedQueryScheduler.java:163,
SqlStage/StageScheduler), splits assigned to workers (SOURCE_DISTRIBUTION,
SourcePartitionedScheduler), stage outputs repartitioned/gathered/broadcast
between stages (§3.3 exchange data plane).

Round-1 execution model: N logical workers; each fragment runs once per
partition with that partition's inputs; page movement between stages is
host-mediated (the DCN tier). The single-program ICI all_to_all path for
partial-agg pipelines lives in parallel/distributed.py; fusing fragment chains
into shard_map programs is the round-2 unification.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..metadata import CatalogManager, Metadata, Session
from .. import knobs
from ..planner.fragmenter import (
    ExchangeType,
    Partitioning,
    PlanFragment,
    RemoteSourceNode,
    SubPlan,
    plan_fragments,
)
from ..planner.plan import LogicalPlan, OutputNode, PlanNode, TableScanNode, visit_plan
from ..runtime.device_scheduler import current_priority as _current_priority
from ..runtime.executor import PlanExecutor, Relation, _concat_pages
from ..runtime.local import QueryResult
from ..runtime.tracing import TRACER
from ..spi.host_pages import (
    empty_page_for,
    host_order_key as _host_order_key,
    host_partition_targets,
    page_from_host_chunks as _page_from_host_chunks,
    page_to_host as _page_to_host,
    pages_from_host_rows as _pages_from_host_rows,
)
from ..spi.page import Column, Dictionary, Page
from ..spi.types import is_string
from ..sql import tree as t


_INT64_MIN = np.int64(np.iinfo(np.int64).min)
_INT64_MAX = np.int64(np.iinfo(np.int64).max)


def host_range_targets(
    chunk_cols: List[List], rs: "RemoteSourceNode", n: int
) -> List[np.ndarray]:
    """Row -> consumer partition by SORT-ORDER range, per producer chunk
    (the DCN-tier distributed sort shuffle; ref: the reference's
    MergePartitioning + benchto distributed_sort suite, redesigned as
    boundary cuts over the encoded sort-key space).

    Boundaries are quantile cuts of the encoded first sort key across ALL
    producers, and rows with EQUAL keys always share a target (searchsorted
    over value cuts) — required because the parent GATHER concatenates
    locally-sorted parts in part order, so a key split across two parts
    would interleave its secondary sort order."""
    o = rs.orderings[0]
    ki = list(rs.symbols).index(o.symbol)
    dicts = [c[ki][3] for c in chunk_cols]
    real = [d for d in dicts if d is not None]
    remap = None
    if real and len({d.fingerprint() for d in real}) > 1:
        # codes are dictionary-local; re-encode into one merged SORTED vocab
        # so code order == value order across producers
        merged_values = sorted(set().union(*[list(d.values) for d in real]))
        code_of = {s: c for c, s in enumerate(merged_values)}
        remap = {
            id(d): np.array([code_of[s] for s in d.values], dtype=np.int64)
            for d in real
        }
    keys: List[np.ndarray] = []
    for cols in chunk_cols:
        _, data, valid, dictionary = cols[ki]
        if dictionary is not None and remap is not None:
            lut = remap[id(dictionary)]
            data = lut[np.clip(data, 0, len(lut) - 1)]
        k = _host_order_key(np.asarray(data))
        if not o.ascending:
            k = ~k
        k = np.where(
            np.asarray(valid), k, _INT64_MIN if o.nulls_first else _INT64_MAX
        )
        keys.append(k)
    all_keys = np.concatenate(keys) if keys else np.zeros(0, dtype=np.int64)
    if len(all_keys) == 0:
        return [np.zeros(len(k), dtype=np.int64) for k in keys]
    sk = np.sort(all_keys)
    cuts = sk[[(len(sk) * (i + 1)) // n for i in range(n - 1)]]
    return [np.searchsorted(cuts, k, side="right") for k in keys]


def _worker_alive(url: str, secret) -> bool:
    import urllib.error
    import urllib.request

    from ..server.worker import SIGNATURE_HEADER, sign

    rel = "/v1/task/__probe__"
    req = urllib.request.Request(f"{url.rstrip('/')}{rel}", method="GET")
    req.add_header(SIGNATURE_HEADER, sign(secret, "GET", rel))
    try:
        with urllib.request.urlopen(req, timeout=5) as resp:
            resp.read()
        return True
    except urllib.error.HTTPError:
        return True  # 404 for an unknown task — the server answered
    except OSError:
        return False


def scan_sources(metadata, node: TableScanNode):
    """THE scan-setup rule (constraint absorption -> split enumeration ->
    column projection), shared by every tier that reads a TableScanNode so
    pruning/projection semantics cannot diverge between them. Returns
    (splits, col_indexes, page_source_provider)."""
    connector = metadata.connector_for(node.table)
    handle = node.table
    if node.constraint.domains:
        absorbed = metadata.apply_filter(handle, node.constraint)
        if absorbed is not None:
            handle = absorbed
    splits = connector.split_manager().get_splits(handle)
    meta = metadata.get_table_metadata(node.table)
    col_indexes = [meta.column_index(c) for _, c in node.assignments]
    return splits, col_indexes, connector.page_source_provider()


def run_fragment_partition(executor: "_FragmentExecutor", root: PlanNode) -> Page:
    """One fragment x one partition -> output Page (shared by the in-process
    scheduler and the worker task API)."""
    from ..runtime.failure import InjectedFailure, chaos_category, chaos_fire

    # chaos site "task_crash_mid_execute": the SHARED entry of both the
    # in-process scheduler and the worker task API — a crash here models a
    # task dying with its output uncommitted, on either execution path
    act = chaos_fire("task_crash_mid_execute", text=type(root).__name__)
    if act is not None:
        raise InjectedFailure(
            "injected crash mid-execute", category=chaos_category(act)
        )
    if isinstance(root, OutputNode):
        _, page = executor.execute()
        return page
    rel = executor.eval(root)
    return Page(
        tuple(rel.column_for(s) for s in root.output_symbols), rel.page.active
    )


class _FragmentExecutor(PlanExecutor):
    """Executes one fragment for one partition: RemoteSources read staged pages;
    table scans take only this partition's splits (SOURCE distribution)."""

    def __init__(
        self,
        plan: LogicalPlan,
        metadata: Metadata,
        session: Session,
        staged: Dict[int, List[Page]],
        partition: int,
        n_workers: int,
    ):
        super().__init__(plan, metadata, session)
        self.staged = staged
        self.partition = partition
        self.n_workers = n_workers

    def _exec_RemoteSourceNode(self, node: RemoteSourceNode) -> Relation:
        pages = self.staged[node.fragment_id]
        page = pages[self.partition] if self.partition < len(pages) else pages[0]
        return Relation(page, node.symbols)

    def _exec_TableScanNode(self, node: TableScanNode) -> Relation:
        splits, col_indexes, provider = scan_sources(self.metadata, node)
        # SOURCE distribution: round-robin split assignment
        # (ref: UniformNodeSelector / SourcePartitionedScheduler)
        splits = [s for i, s in enumerate(splits) if i % self.n_workers == self.partition]
        symbols = tuple(s for s, _ in node.assignments)
        if not splits:
            # empty_page_for keeps multi-lane storage (vectors, long
            # decimals) and the sentinel string dictionaries: downstream
            # programs compile against the layout even when this partition
            # drew zero splits (SOURCE round-robin at small scales, or an
            # ANN probe pruning below the worker count)
            page = empty_page_for(symbols, {s: self.types[s] for s in symbols})
            return Relation(page, symbols)
        pages = [provider.create_page_source(sp, col_indexes) for sp in splits]
        return Relation(_concat_pages(pages), symbols)


class DistributedQueryRunner:
    """Multi-worker engine (the DistributedQueryRunner.java:108 analogue —
    a full multi-stage cluster in one process)."""

    def __init__(
        self,
        session: Optional[Session] = None,
        n_workers: int = 4,
        worker_urls: Optional[List[str]] = None,
        secret: Optional[str] = None,
        worker_locations: Optional[Dict[str, str]] = None,
        coordinator_location: str = "",
        node_registry=None,
    ):
        """``worker_urls``: if set, tasks dispatch to remote WorkerServers over
        the /v1/task HTTP API (HttpRemoteTask analogue) instead of executing
        in-process; workers must mount identically-configured catalogs.
        ``secret``: shared HMAC secret for internal requests (defaults to
        $TRINO_TPU_INTERNAL_SECRET; required for non-localhost workers).
        ``worker_locations``: url -> network-location path ("region/rack/
        host"); with ``coordinator_location`` set, the PIPELINED tier runs
        counter-based nearest-first placement with per-worker capacity
        (session max_tasks_per_worker) and tier spill-over
        (TopologyAwareNodeSelector.java:51). ``node_registry``: a
        runtime.nodes.NodeRegistry whose ANNOUNCED worker locations overlay
        the constructor config — announcements win, so live re-announcement
        moves placement. The FTE tier's attempt-rotation ignores topology by
        design: survival beats locality there."""
        import os

        self.catalogs = CatalogManager()
        self.metadata = Metadata(self.catalogs)
        self.session = session or Session()
        self.n_workers = n_workers
        self.worker_urls = worker_urls
        self.worker_locations = worker_locations or {}
        self.coordinator_location = coordinator_location
        self.node_registry = node_registry
        self.secret = (
            secret
            if secret is not None
            else knobs.env_str("TRINO_TPU_INTERNAL_SECRET")
        )
        # which execution tier handled the last query and, for fallbacks,
        # why the single-program ICI tier rejected it
        self.last_tier: Optional[str] = None
        self.last_tier_reason: Optional[str] = None
        # serving fabric plane (runtime/ha.py): the leader lease fencing
        # journal appends when this runner serves behind an HA coordinator;
        # last_fte_adopted counts committed attempts re-adopted on resume
        self.ha_lease = None
        self.last_fte_adopted = 0

    @staticmethod
    def tpch(scale: float = 0.01, n_workers: int = 4, split_target_rows: int = 4096):
        from ..connectors.tpch import TpchConnector

        runner = DistributedQueryRunner(
            Session(catalog="tpch", schema="sf" + f"{scale:g}".replace(".", "_")), n_workers
        )
        runner.catalogs.register(
            "tpch", TpchConnector(scale=scale, split_target_rows=split_target_rows)
        )
        return runner

    def plan_distributed(self, sql: str) -> SubPlan:
        return plan_fragments(sql, self.metadata, self.session, self.n_workers)

    def execute(self, sql: str) -> QueryResult:
        from ..runtime.failure import execute_with_retry

        # the statement's root (the QueryManager's where there is one): the
        # mesh tier's spans and whatever a fragment executor opens hang here
        with TRACER.statement(sql):
            return execute_with_retry(
                self._execute_once, sql,
                retry_policy=str(self.session.get("retry_policy")),
            )

    def _feedback_enabled(self) -> bool:
        try:
            return bool(self.session.get("statistics_feedback"))
        except KeyError:
            return True

    def _observe_fragments(self, subplan: SubPlan, collector, node_actuals,
                           skip_fragments=()) -> None:
        """Fold the query-level per-node actuals (already aggregated across
        partitions and FTE attempts) into the collector + statistics
        feedback plane, one observe per fragment. ``skip_fragments``:
        fragments whose actuals are INCOMPLETE (some winning attempts ran
        remotely and left no local stash) — observing them would record
        undercounted rows as truth and poison the history overlay."""
        from ..runtime import statstore

        query_id = statstore.current_query_id() or ""
        # inline whoever calls: the staged paths read the collector's
        # planNodes as soon as this returns
        with statstore.feedback_span(query_id) as span:
            span.attributes["nodes"] = len(node_actuals)
            for frag in subplan.fragments:
                if frag.fragment_id in skip_fragments:
                    continue
                statstore.observe_query(
                    LogicalPlan(frag.root, subplan.types), self.metadata,
                    self.session, collector, node_actuals, query_id=query_id,
                    fragment=frag.fragment_id,
                )

    def _cluster_obs_enabled(self) -> bool:
        try:
            return bool(self.session.get("cluster_obs"))
        except KeyError:
            return False

    def _execute_once(self, sql: str) -> QueryResult:
        if self._cluster_obs_enabled():
            # the profile's planning phase opens here; _execute_fte closes
            # it where its own first phase begins, so that the phases are
            # contiguous (the sums-to-wall contract)
            self._obs_planning_t0 = time.monotonic()
        subplan = self.plan_distributed(sql)
        # per-query observability (stale entries from a previous query must
        # not leak into this one's fragment-width report)
        self.last_partition_counts = {}
        if str(self.session.get("retry_policy")) == "TASK":
            # fault-tolerant execution: stage-by-stage over durable exchange,
            # failed tasks re-attempted individually (no whole-query restart).
            # With remote workers, each task attempt dispatches over HTTP with
            # durable inputs shipped inline — a worker dying mid-task costs
            # ONE task retry on a surviving worker, never the query (ref:
            # EventDrivenFaultTolerantQueryScheduler.java:209).
            self.last_tier, self.last_tier_reason = "fte", None
            return self._execute_fte(subplan, sql=sql)
        if self.worker_urls:
            # remote workers: pipelined all-at-once scheduling — every stage's
            # tasks dispatch immediately and pull their inputs from producer
            # workers' output buffers (no coordinator stage barrier)
            self.last_tier, self.last_tier_reason = "remote", None
            return self._execute_remote_streaming(subplan)
        # tier 1 (SURVEY.md §5.8): lower the whole fragment tree into one
        # shard_map program — exchanges ride ICI collectives, no host hops.
        # Falls back to the staged (DCN-tier) path for plans that need host
        # syncs, remote workers, or when the mesh is unavailable.
        self.last_tier = "staged"
        self.last_tier_reason = "ici tier disabled or mesh unavailable"
        if (
            self.worker_urls is None
            and self.session.get("use_ici_exchange")
            and len(jax.devices()) >= self.n_workers
        ):
            from .mesh_runner import MeshLoweringError, MeshQueryRunner

            try:
                if getattr(self, "_mesh_runner", None) is None:
                    self._mesh_runner = MeshQueryRunner(
                        session=self.session,
                        n_devices=self.n_workers,
                        catalogs=self.catalogs,
                        metadata=self.metadata,
                    )
                names, page = self._mesh_runner.execute_subplan(subplan)
                self.last_tier = "ici"
                self.last_tier_reason = None
                return QueryResult(
                    names, self._mesh_runner.gather(page),
                    [c.type for c in page.columns],
                )
            except MeshLoweringError as e:
                # observability for the tier decision (VERDICT r2: nothing
                # tracked which queries lower vs fall back): EXPLAIN-level
                # consumers and tests read last_tier/last_tier_reason
                self.last_tier = "staged"
                self.last_tier_reason = str(e)
        from ..runtime.memory import query_memory_context
        from ..runtime.spiller import Spiller

        # parked stage outputs become REVOCABLE pool memory when a memory
        # scope is active (QueryManager execution over a configured pool):
        # pool pressure reclaims them by spilling to host even below the
        # session trigger, instead of blocking peers (runtime/memory.py)
        spiller = Spiller(
            int(self.session.get("exchange_spill_trigger_bytes") or 0),
            memory=query_memory_context(tag="exchange"),
        )
        self.last_spiller = spiller
        staged: Dict[int, List[object]] = {}
        # statistics feedback plane: per-node actuals summed across fragment
        # partitions, observed once at query end (runtime/statstore.py)
        from ..runtime import observability as obs

        feedback = self._feedback_enabled()
        collector = obs.QueryStatsCollector()
        node_actuals: Dict[int, dict] = {}
        # fragments are listed children-first, so inputs are always staged;
        # parked stage outputs spill to host beyond the device budget (the root
        # fragment's output is consumed immediately — never parked/spilled)
        root_id = subplan.root_fragment.fragment_id
        try:
            for frag in subplan.fragments:
                pages = self._execute_fragment(
                    subplan, frag, staged,
                    actuals_sink=node_actuals if feedback else None,
                )
                staged[frag.fragment_id] = (
                    pages if frag.fragment_id == root_id
                    else spiller.maybe_spill(pages)
                )
            final_pages = staged[root_id]
            assert len(final_pages) == 1
            root = subplan.root_fragment.root
            assert isinstance(root, OutputNode)
            result = QueryResult(
                list(root.column_names),
                final_pages[0].to_pylist(),
                [c.type for c in final_pages[0].columns],
            )
            if feedback and node_actuals:
                try:
                    self._observe_fragments(subplan, collector, node_actuals)
                    result.query_stats = collector.snapshot()
                except Exception:  # lint: disable=bare-except-swallow -- stats feedback is advisory; a fold failure must not fail a finished query
                    pass
            return result
        finally:
            spiller.detach()

    # ------------------------------------------------------------------ internals

    def _parts_for(self, frag: PlanFragment) -> int:
        """Fragment width: SINGLE runs one part; everything else takes the
        stats-derived hint (DeterminePartitionCount.java:88) capped by the
        worker count."""
        if frag.partitioning == Partitioning.SINGLE:
            return 1
        if frag.partition_count is not None:
            return max(1, min(self.n_workers, frag.partition_count))
        return self.n_workers

    def _execute_fragment(
        self, subplan: SubPlan, frag: PlanFragment, staged,
        actuals_sink: Optional[Dict[int, dict]] = None,
    ) -> List[Page]:
        n_parts = self._parts_for(frag)
        # observability: how wide each fragment actually ran (tests + EXPLAIN)
        self.last_partition_counts[frag.fragment_id] = n_parts

        # locate this fragment's remote sources to pre-stage their exchanges
        remotes = self._remote_sources(frag.root)
        exchanged: Dict[int, List[Page]] = {}
        from ..runtime.spiller import Spiller

        for rs in remotes:
            producer = [Spiller.load(e) for e in staged[rs.fragment_id]]
            pages = self._run_exchange(rs, producer, n_parts, subplan)
            if self.session.get("exchange_compression"):
                # cross the wire: serialize -> LZ4 (C++) -> deserialize, exactly
                # what the DCN page stream does (runtime/serde.py)
                from ..runtime.serde import deserialize_page, serialize_page

                pages = [deserialize_page(serialize_page(p)) for p in pages]
            exchanged[rs.fragment_id] = pages

        plan = LogicalPlan(frag.root, subplan.types)
        out_pages: List[Page] = []
        for p in range(n_parts):
            executor = _FragmentExecutor(
                plan, self.metadata, self.session, exchanged, p, n_parts
            )
            self._attach_fragment_cache(executor, p, n_parts)
            self._attach_device_batching(executor, p, n_parts)
            executor.collect_actuals = actuals_sink is not None
            out_pages.append(run_fragment_partition(executor, frag.root))
            if actuals_sink is not None:
                from ..runtime.statstore import merge_actuals

                # dynamic-filter pre/post rows pair up INSIDE finalize (per
                # executor) before partitions sum — no synthetic-node ids
                # escape the executor's lifetime
                merge_actuals(actuals_sink, executor.finalize_actuals())
        return out_pages

    def _remote_sources(self, root: PlanNode) -> List[RemoteSourceNode]:
        from ..planner.fragmenter import remote_sources

        return remote_sources(root)

    def _attach_fragment_cache(
        self, executor, p: int, n_parts: int, blocking: bool = True,
    ) -> None:
        """Warm-path cache plane: staged and FTE fragment executors share
        scan->filter->(partial-)agg prefixes across queries too. The scope
        carries the partition coordinates — partition p of n scans
        DIFFERENT splits than p' of n', so their materializations must
        never alias (fragment ids stay OUT of the scope: the subtree
        fingerprint already identifies the work, and keeping ids out lets
        identical prefixes match across differently-shaped outer plans).
        ``blocking=False`` (FTE attempts) disables the single-flight wait:
        a speculative sibling spawned to race a stalled attempt must never
        queue behind that attempt's own flight."""
        from ..runtime.cachestore import (
            CACHES,
            SINGLE_FLIGHT_WAIT_SECS,
            FragmentBinding,
        )
        from ..runtime.statstore import current_query_id

        if not CACHES.fragment_enabled(self.session):
            return
        executor.fragment_cache = FragmentBinding(
            CACHES.fragment, self.metadata, self.session,
            scope=f"part{p}/{n_parts}",
            query_id=current_query_id() or "",
            wait_secs=SINGLE_FLIGHT_WAIT_SECS if blocking else 0.0,
            registry=getattr(self.catalogs, "cache_nonce", ""),
        )

    def _attach_device_batching(self, executor, p: int, n_parts: int) -> None:
        """Device batching plane for fragment executors: same partition
        scoping rule as the fragment cache — partition p of n scans
        DIFFERENT splits than p' of n', so lanes and shared scans carry
        the partition coordinates and never alias across them."""
        from ..runtime.device_scheduler import attach as _attach_batching

        _attach_batching(
            executor, self.metadata, self.session, catalogs=self.catalogs,
            scope=f"part{p}/{n_parts}",
        )

    def _ha_enabled(self) -> bool:
        try:
            return bool(self.session.get("ha_plane"))
        except KeyError:
            return False

    def _execute_fte(self, subplan: SubPlan, sql: str = "",
                     resume=None) -> QueryResult:
        """Task-level fault tolerance (retry_policy=TASK): every task
        attempt's COMPLETE output commits atomically to the durable exchange;
        a failed task re-runs from its producers' stored outputs while
        finished tasks are never re-executed; the first committed attempt per
        partition is the one consumers read (output deduplication).

        Round-5 data plane: tasks read inputs from and commit outputs to the
        durable exchange store DIRECTLY (a shared-filesystem location, the
        FileSystemExchangeManager contract) — producers write output
        pre-partitioned for the consumer stage, and the coordinator ships
        only descriptors and reads only attempt metadata (row counts for
        adaptive replanning). The single exception is REPARTITION_RANGE
        (distributed sort), whose global quantile cuts still materialize
        through the coordinator; `fte_coordinator_payload_bytes` counts
        exactly those bytes and is 0 for hash/gather/broadcast plans.

        Round-8 control plane: the per-stage dispatch loop is the
        EVENT-DRIVEN scheduler (runtime/fte_scheduler.py) — all of a
        stage's tasks run concurrently, failures classify (USER fails the
        query instantly; INTERNAL/EXTERNAL retry with backoff away from a
        per-query node blacklist), attempts carry deadlines, stragglers
        speculate, and corrupt committed exchange attempts are quarantined
        and re-produced.

        Round-16 serving fabric (runtime/ha.py, gated on ``ha_plane``): the
        coordinator journals dispatch progress (begin / stage_start /
        winner / stage_done / finished) NEXT TO the durable exchange, so a
        standby taking over the leader lease can replay the journal,
        re-adopt committed exchange attempts (``resume``), and finish the
        query instead of failing it. The ``coordinator_crash`` chaos site
        aborts exactly the way a dead process would: journal + committed
        attempts stay on the substrate, nothing is cleaned up.

        ref: EventDrivenFaultTolerantQueryScheduler.java:209 (stage-by-stage
        scheduling from TaskDescriptorStorage), spi/exchange/ExchangeManager,
        plugin/trino-exchange-filesystem FileSystemExchangeSink; SURVEY §3.4.
        """
        import threading
        import uuid

        from ..runtime.exchange_spi import ExchangeManager, decode_guard
        from ..runtime.fte_scheduler import EventDrivenFteScheduler, TaskSpec
        from ..runtime.serde import deserialize_page, serialize_page

        query_id = (
            resume.query_id if resume is not None else uuid.uuid4().hex[:12]
        )
        base = self.session.get("fte_exchange_dir") or None
        mgr = getattr(self, "_fte_manager", None)
        if mgr is None or (base and mgr.base_dir != base):
            mgr = ExchangeManager(base)
            self._fte_manager = mgr
        ha_on = self._ha_enabled()
        journal = None
        self.last_fte_adopted = 0
        if ha_on:
            from ..runtime.ha import DispatchJournal

            journal = DispatchJournal(
                DispatchJournal.path_for(mgr.base_dir, query_id),
                lease=self.ha_lease,
            )
            if resume is None:
                try:
                    journal.begin(
                        query_id, sql, self.session, self.n_workers,
                        exchange_dir=mgr.base_dir,
                    )
                except Exception as e:
                    from ..runtime.ha import FencedWriteError

                    if isinstance(e, FencedWriteError):
                        # fenced before any record landed: the new leader
                        # re-runs from scratch (no journal to replay)
                        e.query_id = query_id
                        e.journal_path = None
                    raise
        # cluster observability plane: per-stage wall + component breakdown
        # measured contiguously around the stage loop (profiles' sums-to-
        # wall contract); None when cluster_obs is off — the off path runs
        # byte-identical to the ungated engine
        obs_stages = None
        if self._cluster_obs_enabled():
            from ..runtime.clusterobs import StageBreakdown

            obs_stages = StageBreakdown()
            obs_enter = time.monotonic()
            planning_t0 = getattr(self, "_obs_planning_t0", None)
            if planning_t0 is not None:
                obs_stages.add_phase("planning", obs_enter - planning_t0)
                self._obs_planning_t0 = None
        self.last_stage_breakdown = obs_stages
        self.last_task_attempts: Dict[tuple, int] = {}
        # exchange payload routed through this coordinator (range edges only)
        self.fte_coordinator_payload_bytes = 0
        # adaptive replanning decisions made this query (AdaptivePlanner.java:87
        # analogue: stage-boundary re-optimization from ACTUAL sizes)
        self.last_adaptive: List[dict] = []

        scheduler = EventDrivenFteScheduler(
            workers=list(self.worker_urls or []),
            session=self.session,
            query_id=query_id,
            probe=lambda url: _worker_alive(url, self.secret),
            node_manager=self.node_registry,
        )
        self.last_fte_scheduler = scheduler  # observability (tests/EXPLAIN)
        self.last_fte_root_fid = subplan.root_fragment.fragment_id
        if obs_stages is not None and journal is not None:
            # epoch-stitched cluster traces: task_attempt spans carry the
            # leader epoch they dispatched under, so a merged post-failover
            # timeline can show both epochs side by side
            scheduler.epoch = journal.epoch
        if journal is not None:
            # every winning commit lands in the dispatch journal keyed like
            # the attempt ring; a fenced append (superseded lease epoch) is
            # fatal — the old leader must stop scheduling
            scheduler.on_winner = (
                lambda key, att: journal.winner(key[0], key[1], att)
            )
        # statistics feedback plane: each LOCAL attempt stashes its own
        # per-node actuals under (fid, partition, attempt); after a stage
        # completes, ONLY the scheduler-confirmed winning attempt of each
        # task folds into the query rollup — losing/abandoned speculative
        # siblings and failed retries must not double-count operator rows
        feedback = self._feedback_enabled()
        pending_actuals: Dict[tuple, Dict[int, dict]] = {}
        node_actuals: Dict[int, dict] = {}
        incomplete_frags: set = set()

        def _fold_stage(fid: int, n_parts: int) -> None:
            from ..runtime.statstore import merge_actuals

            for p in range(n_parts):
                winner = scheduler.winners.get((fid, p))
                won = (
                    pending_actuals.pop((fid, p, winner), None)
                    if winner is not None else None
                )
                if won is not None:
                    merge_actuals(node_actuals, won)
                else:
                    # the winning attempt ran remotely (or left no stash):
                    # this fragment's rollup is missing that partition's
                    # rows — observing it would record UNDERCOUNTED actuals
                    # as truth and poison the history overlay
                    incomplete_frags.add(fid)
            # losers/stale attempts of this fragment free their stashes.
            # snapshot the keys: an abandoned sibling's thread can still be
            # running and stashing concurrently (dict writes are atomic;
            # iterating the live dict is not)
            for key in list(pending_actuals):
                if key[0] == fid:
                    pending_actuals.pop(key, None)

        # consumer topology: every fragment feeds exactly ONE RemoteSourceNode
        # (each REMOTE exchange cuts its own fragment), so a producer knows at
        # dispatch time how its consumer is partitioned and writes its output
        # pre-split into that many parts
        consumer_edge: Dict[int, RemoteSourceNode] = {}
        consumer_fid: Dict[int, int] = {}
        for frag in subplan.fragments:
            for rs in self._remote_sources(frag.root):
                consumer_edge[rs.fragment_id] = rs
                consumer_fid[rs.fragment_id] = frag.fragment_id
        parts_of = {f.fragment_id: self._parts_for(f) for f in subplan.fragments}
        produced_parts: Dict[int, int] = {}

        root_id = subplan.root_fragment.fragment_id
        exchanges = {}
        preserve = False
        # contiguous stage-wall marks: elapsed between marks is credited to
        # the stage that just ran, so stage walls + phases sum to the
        # function's wall time (the profile's 5% contract)
        obs_prev_fid: Optional[int] = None
        obs_mark = 0.0
        # the result's attached phases once root_read has closed: what
        # follows it (the journal's copy, the exchange directory's removal)
        # is the phase "cleanup", closed in the finally below
        obs_phases = None
        try:
            if obs_stages is not None:
                obs_mark = time.monotonic()
                obs_stages.add_phase("setup", obs_mark - obs_enter)
            for frag in subplan.fragments:
                if obs_stages is not None:
                    now = time.monotonic()
                    if obs_prev_fid is not None:
                        obs_stages.add(obs_prev_fid, wall_secs=now - obs_mark)
                    obs_mark = now
                    obs_prev_fid = frag.fragment_id
                fid = frag.fragment_id
                n_parts = parts_of[fid]
                self.last_partition_counts[fid] = n_parts
                ex = mgr.create_exchange(query_id, fid)
                exchanges[fid] = ex

                edge = consumer_edge.get(fid)
                if edge is not None and edge.exchange_type == ExchangeType.REPARTITION:
                    out_n = parts_of[consumer_fid[fid]]
                    out_keys = list(edge.partition_keys)
                else:  # root / GATHER / BROADCAST / RANGE: one gathered part
                    out_n, out_keys = 1, []
                produced_parts[fid] = out_n

                if resume is not None and fid in resume.stages_done:
                    # dispatch handoff: this stage completed under the dead
                    # coordinator — its committed durable attempts ARE the
                    # stage output. Adopt them wholesale; consumers read
                    # them off the substrate exactly as they would have.
                    scheduler.register_exchange(ex.root, fid)
                    continue
                if ha_on:
                    from ..runtime.failure import chaos_fire as _chaos_fire
                    from ..runtime.ha import CoordinatorCrashError

                    if _chaos_fire(
                        "coordinator_crash", text=f"{query_id}_f{fid}_pre"
                    ) is not None:
                        raise CoordinatorCrashError(query_id, journal.path)
                if journal is not None:
                    journal.stage_start(fid, n_parts)

                remotes = self._remote_sources(frag.root)
                modes = self._adaptive_join_modes_durable(
                    frag.root, exchanges, parts_of
                )
                # REPARTITION_RANGE needs global quantile cuts over all
                # producers — the one exchange kind the coordinator still
                # materializes (counted in fte_coordinator_payload_bytes)
                range_parts: Dict[int, List[Page]] = {}
                for rs in remotes:
                    if rs.exchange_type != ExchangeType.REPARTITION_RANGE:
                        continue
                    pex = exchanges[rs.fragment_id]
                    n_pp = parts_of[rs.fragment_id]

                    def _read_range(pex=pex, n_pp=n_pp):
                        pages, nbytes = [], 0
                        for pp in range(n_pp):
                            attempt = pex.committed_parts_attempt(pp)
                            for blob in pex.source_part(pp, 0, attempt):
                                nbytes += len(blob)
                                with decode_guard(pex.root, pp, attempt):
                                    pages.append(deserialize_page(blob))
                        return pages, nbytes

                    pages, nbytes = self._fte_read_recovering(
                        scheduler, _read_range
                    )
                    self.fte_coordinator_payload_bytes += nbytes
                    range_parts[rs.fragment_id] = self._run_exchange(
                        rs, pages, n_parts, subplan
                    )

                out_symbols = list(frag.root.output_symbols)
                plan = LogicalPlan(frag.root, subplan.types)
                scheduler.register_exchange(ex.root, fid)
                # partition-independent inputs (gather/broadcast/flipped
                # build) staged ONCE per fragment in local mode — lazily
                # under a lock, so concurrent partitions share the staging
                # and a corruption-recovery re-run after the stage restages
                # the producer's FRESH attempt from disk
                local_shared: Dict[int, object] = {}
                shared_lock = threading.Lock()
                specs: List[TaskSpec] = []
                for p in range(n_parts):
                    input_specs: Dict[int, dict] = {}
                    for rs in remotes:
                        pfid = rs.fragment_id
                        if pfid in range_parts:
                            pages = range_parts[pfid]
                            page = pages[p] if p < len(pages) else pages[0]
                            blob = serialize_page(page)
                            self.fte_coordinator_payload_bytes += len(blob)
                            # page kept for the local path (no serde round
                            # trip); remote dispatch ships only the blob
                            input_specs[pfid] = {"inline_blob": blob, "page": page}
                            continue
                        if (
                            rs.exchange_type == ExchangeType.REPARTITION
                            and modes.get(pfid) != "broadcast"
                        ):
                            mode, part = "part", p
                        else:  # gather, broadcast, adaptive-flipped build
                            mode, part = "all", 0
                        input_specs[pfid] = {
                            "durable": {
                                "dir": exchanges[pfid].root,
                                "producer_parts": parts_of[pfid],
                                "n_parts": produced_parts[pfid],
                                "mode": mode,
                                "part": part,
                                "symbols": list(rs.symbols),
                            }
                        }
                    out_spec_base = {
                        "kind": "durable",
                        "dir": ex.root,
                        "partition": p,
                        "n": out_n,
                        "keys": out_keys,
                        "symbols": out_symbols,
                    }
                    specs.append(TaskSpec(
                        fid, p,
                        self._make_fte_task(
                            frag, subplan, plan, input_specs, out_spec_base,
                            p, n_parts, query_id, local_shared, shared_lock,
                            pending_actuals if feedback else None,
                            obs_stages=obs_stages,
                        ),
                    ))
                if resume is not None:
                    # re-adopt committed attempts of the in-flight stage:
                    # the durable exchange is first-commit-wins, so a task
                    # whose attempt already committed under the old leader
                    # is DONE — re-running it would only burn device time
                    keep = []
                    for s in specs:
                        if ex.committed_parts_attempt(s.partition) is not None:
                            self.last_fte_adopted += 1
                        else:
                            keep.append(s)
                    specs = keep
                # event-driven concurrent dispatch of the whole stage
                scheduler.run_stage(specs)
                if feedback:
                    try:
                        _fold_stage(fid, n_parts)
                    except Exception:  # noqa: BLE001 — observability only
                        incomplete_frags.add(fid)
                if journal is not None:
                    journal.stage_done(fid)
                if ha_on:
                    from ..runtime.failure import chaos_fire as _chaos_fire
                    from ..runtime.ha import CoordinatorCrashError

                    if _chaos_fire(
                        "coordinator_crash", text=f"{query_id}_f{fid}_post"
                    ) is not None:
                        raise CoordinatorCrashError(query_id, journal.path)

            if obs_stages is not None:
                now = time.monotonic()
                if obs_prev_fid is not None:
                    obs_stages.add(obs_prev_fid, wall_secs=now - obs_mark)
                obs_mark = now

            # the root fragment's gathered output is read HERE, not by a
            # consumer task — so corruption on its committed attempt needs
            # coordinator-side recovery (quarantine + producer re-run), the
            # same contract every other fragment gets from the scheduler
            def _read_root():
                out = []
                rex = exchanges[root_id]
                attempt = rex.committed_parts_attempt(0)
                for b in rex.source_part(0, 0, attempt):
                    with decode_guard(rex.root, 0, attempt):
                        out.append(deserialize_page(b))
                return out

            root_pages = self._fte_read_recovering(scheduler, _read_root)
            merged = _page_from_host_chunks([_page_to_host(p) for p in root_pages])
            root = subplan.root_fragment.root
            assert isinstance(root, OutputNode)
            result = QueryResult(
                list(root.column_names),
                merged.to_pylist(),
                [c.type for c in merged.columns],
            )
            if feedback and node_actuals:
                from ..runtime import observability as obs

                try:
                    collector = obs.QueryStatsCollector()
                    self._observe_fragments(
                        subplan, collector, node_actuals,
                        skip_fragments=incomplete_frags,
                    )
                    result.query_stats = collector.snapshot()
                except Exception:  # lint: disable=bare-except-swallow -- stats feedback is advisory; a fold failure must not fail a finished query
                    pass
            if journal is not None:
                # finished BEFORE the profile attach: a fenced append must
                # fail the old leader here, and the attached journal copy
                # below then carries the complete record set (the on-disk
                # journal is removed with the query's exchange directory,
                # so the bundle's copy is the surviving postmortem artifact)
                journal.finished()
            if obs_stages is not None:
                now = time.monotonic()
                obs_stages.add_phase("root_read", now - obs_mark)
                obs_mark = now
                from ..runtime.fte_scheduler import attempt_log

                snap = obs_stages.snapshot()
                qs = result.query_stats or {}
                qs["stages"] = snap["stages"]
                qs["phases"] = snap["phases"]
                qs["fteQueryId"] = query_id
                qs["retries"] = [
                    r for r in attempt_log()
                    if r.get("query_id") == query_id
                ]
                qs["blacklist"] = scheduler.blacklist.snapshot()
                if journal is not None:
                    from ..runtime.ha import DispatchJournal as _DJ

                    qs["journal"], _ = _DJ.read(journal.path)
                result.query_stats = qs
                result.fte_query_id = query_id
                obs_phases = qs["phases"]
            return result
        except BaseException as e:
            if ha_on:
                from ..runtime.ha import (
                    CoordinatorCrashError,
                    FencedWriteError,
                )

                # a "dead" coordinator (chaos crash) or a fenced old leader
                # must leave journal + committed attempts on the substrate
                # for the takeover leader to adopt — cleanup here would
                # destroy exactly the state the handoff replays
                preserve = isinstance(
                    e, (CoordinatorCrashError, FencedWriteError)
                )
                if isinstance(e, FencedWriteError):
                    # the new leader resumes THIS query: name the journal
                    e.query_id = query_id
                    e.journal_path = (
                        journal.path if journal is not None else None
                    )
            raise
        finally:
            if not preserve:
                mgr.remove_query(query_id)
            if obs_phases is not None:
                obs_phases["cleanup"] = time.monotonic() - obs_mark
                obs_stages.add_phase("cleanup", obs_phases["cleanup"])

    def _fte_read_recovering(self, scheduler, read):
        """Coordinator-side exchange read under the same quarantine-and-rerun
        contract consumer TASKS get from the scheduler: corruption of a
        committed attempt quarantines it and re-runs the producer to a fresh
        commit before re-reading, budget-bounded by ``task_retry_attempts``."""
        from ..runtime.exchange_spi import ExchangeDataCorruption

        # budget is PER producer partition (mirroring per-task scheduler
        # budgets): independent corruption on two partitions must not
        # pool into one counter and fail the query after one recovery each
        recoveries: Dict[tuple, int] = {}
        while True:
            try:
                return read()
            except ExchangeDataCorruption as e:
                k = (e.root, e.partition)
                recoveries[k] = recoveries.get(k, 0) + 1
                if recoveries[k] >= scheduler.max_attempts:
                    raise
                scheduler.recover_exchange_corruption(e)

    def _make_fte_task(
        self,
        frag: PlanFragment,
        subplan: SubPlan,
        plan: LogicalPlan,
        input_specs: Dict[int, dict],
        out_spec_base: dict,
        p: int,
        n_parts: int,
        query_id: str,
        local_shared: Dict[int, object],
        shared_lock,
        pending_actuals: Optional[Dict[tuple, Dict[int, dict]]] = None,
        obs_stages=None,
    ):
        """Build the attempt closure the event-driven scheduler dispatches:
        ``run(attempt, worker, deadline)`` executes ONE task attempt —
        remotely when the scheduler picked a worker, in-process otherwise —
        and commits its output durably under that attempt number.

        ``pending_actuals``: per-ATTEMPT operator actuals stash — keyed
        (fid, partition, attempt) so the caller can fold exactly the
        scheduler-confirmed winning attempt into query-level stats.

        ``obs_stages``: the cluster observability plane's per-stage
        component accounting (exchange pull/push walls, XLA compile via the
        jax.monitoring window, the dispatch+drain remainder as device time;
        a remote attempt's whole round trip books as host wait — the
        coordinator's honest view of it). None = byte-identical off path."""
        from ..runtime.fte_plane import emit_durable_output, stage_durable_input

        fid = frag.fragment_id

        def run(attempt: int, worker: Optional[str], deadline) -> None:
            prev = self.last_task_attempts.get((fid, p), -1)
            self.last_task_attempts[(fid, p)] = max(prev, attempt)
            out_spec = {**out_spec_base, "attempt": attempt}
            if worker is not None:
                t0 = time.monotonic() if obs_stages is not None else 0.0
                self._run_fte_task_remote(
                    frag, subplan, input_specs, out_spec,
                    p, n_parts, worker, attempt, query_id, deadline,
                )
                if obs_stages is not None:
                    obs_stages.add(fid, host_secs=time.monotonic() - t0)
                return
            t0 = time.monotonic() if obs_stages is not None else 0.0
            staged = {}
            for pfid, spec in input_specs.items():
                d = spec.get("durable")
                if d is None:
                    staged[pfid] = [spec["page"]]
                elif d["mode"] == "all":
                    with shared_lock:
                        page = local_shared.get(pfid)
                        if page is None:
                            page = local_shared[pfid] = stage_durable_input(
                                d, subplan.types
                            )
                    staged[pfid] = [page]
                else:
                    staged[pfid] = [stage_durable_input(d, subplan.types)]
            executor = _FragmentExecutor(
                plan, self.metadata, self.session, staged, p, n_parts
            )
            self._attach_fragment_cache(executor, p, n_parts, blocking=False)
            self._attach_device_batching(executor, p, n_parts)
            executor.collect_actuals = pending_actuals is not None
            if obs_stages is not None:
                from ..runtime.observability import compile_window

                t1 = time.monotonic()
                with compile_window() as cw:
                    out = run_fragment_partition(executor, frag.root)
                t2 = time.monotonic()
                emit_durable_output(out_spec, out)
                t3 = time.monotonic()
                obs_stages.add(
                    fid,
                    exchange_pull_secs=t1 - t0,
                    compile_secs=cw.seconds,
                    device_secs=max(t2 - t1 - cw.seconds, 0.0),
                    exchange_push_secs=t3 - t2,
                )
            else:
                out = run_fragment_partition(executor, frag.root)
                emit_durable_output(out_spec, out)
            if pending_actuals is not None:
                # post-commit, attempt thread: resolve this attempt's row
                # counts now — the fold into query stats happens on the
                # scheduler thread for the WINNING attempt only
                pending_actuals[(fid, p, attempt)] = executor.finalize_actuals()

        return run

    def _run_fte_task_remote(
        self,
        frag: PlanFragment,
        subplan: SubPlan,
        input_specs: Dict[int, dict],
        out_spec: dict,
        p: int,
        n_parts: int,
        url: str,
        attempt: int,
        query_id: str,
        deadline=None,
    ) -> None:
        """One FTE task attempt on a remote worker: the descriptor carries
        durable-exchange LOCATIONS, not pages — the worker reads its inputs
        from and commits its output to the shared store directly (ref:
        FileSystemExchangeSink/Source; the coordinator moves descriptors
        only). The completion wait pulls a zero-byte marker (task state),
        never payload, and is BOUNDED by ``deadline`` (the scheduler's
        task_completion_timeout): a worker that accepts the POST then hangs
        raises TaskDeadlineExceeded instead of stalling the query forever.
        The scheduler picks ``url`` — excluding the previous attempt's
        worker and the node blacklist."""
        import time as _time
        import urllib.request

        from ..server.worker import (
            SIGNATURE_HEADER,
            TaskDescriptor,
            encode_task,
            pull_buffer,
            sign,
        )

        url = url.rstrip("/")
        inputs = {}
        for pfid, spec in input_specs.items():
            if "durable" in spec:
                inputs[pfid] = {"durable": spec["durable"]}
            else:  # range-exchange fallback: coordinator-materialized part
                # (already counted in fte_coordinator_payload_bytes when built)
                inputs[pfid] = {"inline": [spec["inline_blob"]]}
        tid = f"{query_id}_f{frag.fragment_id}_p{p}_a{attempt}"
        remaining = None
        if deadline is not None:
            remaining = max(1.0, deadline - _time.monotonic())
        desc = TaskDescriptor(
            root=frag.root,
            types=subplan.types,
            session_props=dict(self.session.properties),
            partition=p,
            n_workers=n_parts,
            inputs=inputs,
            output=out_spec,
            trace=TRACER.capture_ids(),
            deadline_secs=remaining,
            priority=_current_priority(),
        )
        body = encode_task(desc)
        rel = f"/v1/task/{tid}"
        req = urllib.request.Request(f"{url}{rel}", data=body, method="POST")
        req.add_header(SIGNATURE_HEADER, sign(self.secret, "POST", rel, body))
        post_timeout = 60 if remaining is None else max(1.0, min(60.0, remaining))
        with urllib.request.urlopen(req, timeout=post_timeout) as resp:
            resp.read()
        try:
            # completion marker only: raises TaskFailedError on task failure,
            # TaskDeadlineExceeded past the attempt deadline
            list(pull_buffer(url, tid, 0, self.secret, deadline=deadline))
        finally:
            try:
                dreq = urllib.request.Request(f"{url}{rel}", method="DELETE")
                dreq.add_header(
                    SIGNATURE_HEADER, sign(self.secret, "DELETE", rel)
                )
                urllib.request.urlopen(dreq, timeout=10).read()
            except OSError:  # lint: disable=bare-except-swallow -- best-effort remote task delete; worker TTL is the backstop
                pass

    def _execute_remote_streaming(self, subplan: SubPlan) -> QueryResult:
        """Pipelined scheduler: create EVERY fragment's tasks up front; tasks
        pull inputs worker-to-worker with token-acked page streams, so stages
        overlap (ref: PipelinedQueryScheduler.java:163 + HttpRemoteTask +
        DirectExchangeClient; SURVEY.md §3.3)."""
        import json
        import urllib.request
        import uuid

        from ..server.worker import (
            SIGNATURE_HEADER,
            TaskDescriptor,
            encode_task,
            sign,
        )

        secret = self.secret
        query_id = uuid.uuid4().hex[:12]
        frag_by_id = {f.fragment_id: f for f in subplan.fragments}
        root_id = subplan.root_fragment.fragment_id

        # after a failed attempt, re-probe workers so the QUERY retry lands
        # only on live ones (a dead worker would otherwise be re-picked —
        # discovery-integrated scheduling; ref: HeartbeatFailureDetector)
        live_urls = list(self.worker_urls)
        if getattr(self, "_probe_workers_next", False):
            live_urls = [u for u in self.worker_urls if _worker_alive(u, secret)]
            self._probe_workers_next = False
            if not live_urls:
                raise RuntimeError("no live workers")

        def parts_of(frag: PlanFragment) -> int:
            # FIXED_RANGE stays single-part on the PIPELINED tier only:
            # workers partition their own outputs and cannot agree on range
            # boundaries without a sampling barrier (the staged + FTE tiers
            # run range-partitioned via coordinator-computed cuts)
            if frag.partitioning in (Partitioning.SINGLE, Partitioning.FIXED_RANGE):
                return 1
            return self._parts_for(frag)

        # each fragment's consuming RemoteSource (fragments feed one consumer)
        consumer_of: Dict[int, Tuple[RemoteSourceNode, int]] = {}
        for frag in subplan.fragments:
            def collect(n: PlanNode, frag=frag):
                if isinstance(n, RemoteSourceNode):
                    consumer_of[n.fragment_id] = (n, parts_of(frag))

            visit_plan(frag.root, collect)

        def task_id(fid: int, p: int) -> str:
            # '<query>_f<fid>_p<p>' — the shape worker-side fair scheduling
            # parses the query id from (every tier uses it)
            return f"{query_id}_f{fid}_p{p}"

        # topology-aware placement (TopologyAwareNodeSelector.java:51):
        # counter-based nearest-first fill with per-worker capacity
        # (max_tasks_per_worker; 0 = unbounded) and tier SPILL-OVER —
        # locations come from worker ANNOUNCEMENTS when a node registry is
        # attached, overlaid on constructor config
        from ..runtime.nodes import TopologyPlacement

        effective_locations = dict(self.worker_locations)
        registry = getattr(self, "node_registry", None)
        if registry is not None:
            for n in registry.all_nodes():
                if n.location and not n.coordinator:
                    effective_locations[n.uri] = n.location
        cap = int(self.session.get("max_tasks_per_worker") or 0)
        if effective_locations and self.coordinator_location:
            placer = TopologyPlacement(
                self.coordinator_location, live_urls, effective_locations, cap
            )
        else:
            placer = None
        self.last_placement = placer  # observability: counts per worker

        def url_for(fid: int, p: int) -> str:
            # placer.assign memoizes per key; the hash fallback is pure —
            # consumers asking for a producer's url always agree with dispatch
            if placer is not None:
                return placer.assign((fid, p)).rstrip("/")
            return live_urls[(fid * 31 + p) % len(live_urls)].rstrip("/")

        def post_task(url: str, tid: str, desc: TaskDescriptor) -> None:
            import urllib.error

            from ..runtime.failure import RetryableQueryError

            body = encode_task(desc)
            rel = f"/v1/task/{tid}"
            req = urllib.request.Request(f"{url}{rel}", data=body, method="POST")
            req.add_header(SIGNATURE_HEADER, sign(secret, "POST", rel, body))
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
            except urllib.error.HTTPError as e:
                # a definitive rejection (bad signature/plan) — fail fast, a
                # retry against the same config cannot succeed
                raise RuntimeError(
                    f"worker {url} rejected task: {e.code} {e.read()[:200]!r}"
                ) from e
            except OSError as e:
                self._probe_workers_next = True
                raise RetryableQueryError(f"worker {url} unreachable: {e}") from e

        # children-first: producers exist (and start) before their consumers,
        # but nothing waits on anything — all stages run concurrently.
        # every created task is torn down in the finally below, including when
        # a later post fails (orphaned tasks would pin worker memory for TTL)
        created: List[Tuple[str, str]] = []
        tasks_to_post: List[tuple] = []
        for frag in subplan.fragments:
            n_parts = parts_of(frag)
            consumer = consumer_of.get(frag.fragment_id)
            if frag.fragment_id == root_id or consumer is None:
                out_spec = {"kind": "gather", "n": 1}
            else:
                rs, consumer_parts = consumer
                if rs.exchange_type == ExchangeType.REPARTITION:
                    out_spec = {
                        "kind": "partitioned",
                        "n": consumer_parts,
                        "keys": list(rs.partition_keys),
                        "symbols": list(rs.symbols),
                    }
                elif rs.exchange_type == ExchangeType.BROADCAST:
                    out_spec = {"kind": "broadcast", "n": consumer_parts}
                else:
                    out_spec = {"kind": "gather", "n": 1}
            remotes: List[RemoteSourceNode] = []
            visit_plan(
                frag.root,
                lambda n: remotes.append(n) if isinstance(n, RemoteSourceNode) else None,
            )
            for p in range(n_parts):
                inputs = {}
                for rs in remotes:
                    producer_parts = parts_of(frag_by_id[rs.fragment_id])
                    inputs[rs.fragment_id] = {
                        "exchange_type": rs.exchange_type.value,
                        "buffer": p,
                        "sources": [
                            {
                                "url": url_for(rs.fragment_id, pp),
                                "task": task_id(rs.fragment_id, pp),
                            }
                            for pp in range(producer_parts)
                        ],
                    }
                desc = TaskDescriptor(
                    root=frag.root,
                    types=subplan.types,
                    session_props=dict(self.session.properties),
                    partition=p,
                    n_workers=n_parts,
                    inputs=inputs,
                    output=out_spec,
                    trace=TRACER.capture_ids(),
                    priority=_current_priority(),
                )
                tasks_to_post.append(
                    (url_for(frag.fragment_id, p), task_id(frag.fragment_id, p), desc)
                )

        # pull the root task's single buffer like any exchange consumer
        # (shared wire protocol: server/worker.pull_buffer), then tear every
        # CREATED task down — including after a mid-posting failure, so
        # orphaned tasks never pin worker memory until the TTL backstop
        from ..runtime.failure import RetryableQueryError
        from ..runtime.serde import deserialize_page
        from ..server.worker import TaskFailedError, pull_buffer

        root_url = url_for(root_id, 0)
        root_task = task_id(root_id, 0)
        try:
            for url, tid, desc in tasks_to_post:
                post_task(url, tid, desc)
                created.append((url, tid))
            pages = [
                deserialize_page(blob)
                for blob in pull_buffer(root_url, root_task, 0, secret)
            ]
        except TaskFailedError as e:
            # deterministic query errors fail fast; only transport-flavored
            # task failures (a producer's puller lost its worker) retry
            if any(
                s in e.error_text
                for s in ("URLError", "ConnectionRefused", "ConnectionReset",
                          "unreachable", "TimeoutError", "RemoteDisconnected")
            ):
                self._probe_workers_next = True
                raise RetryableQueryError(str(e)) from e
            raise RuntimeError(str(e)) from e
        except OSError as e:
            self._probe_workers_next = True
            raise RetryableQueryError(f"query failed: {e}") from e
        finally:
            for url, tid in created:
                try:
                    rel = f"/v1/task/{tid}"
                    req = urllib.request.Request(f"{url}{rel}", method="DELETE")
                    req.add_header(SIGNATURE_HEADER, sign(secret, "DELETE", rel))
                    urllib.request.urlopen(req, timeout=10).read()
                except OSError:  # lint: disable=bare-except-swallow -- best-effort remote task cleanup; worker TTL is the backstop
                    pass
        merged = _page_from_host_chunks([_page_to_host(p) for p in pages])
        root = subplan.root_fragment.root
        assert isinstance(root, OutputNode)
        return QueryResult(
            list(root.column_names),
            merged.to_pylist(),
            [c.type for c in merged.columns],
        )

    def _adaptive_join_modes_durable(
        self, root: PlanNode, exchanges: Dict[int, object], parts_of: Dict[int, int]
    ) -> Dict[int, str]:
        """Stage-boundary re-optimization: for a partitioned equi-join whose
        two inputs are REPARTITION remote sources, read the ACTUAL build-side
        row count from the durable attempts' METADATA (no payload transits
        the coordinator); below the broadcast threshold, flip the build side
        to broadcast — each consumer part then reads every build part while
        the probe side keeps its normal hash part. Probe-side-outer kinds
        only — a broadcast build under RIGHT/FULL would duplicate unmatched
        build rows across parts."""
        from ..planner.plan import JoinKind, JoinNode

        threshold = int(self.session.get("broadcast_join_threshold_rows") or 0)
        if threshold <= 0:
            return {}
        modes: Dict[int, str] = {}

        def consider(n: PlanNode):
            if not isinstance(n, JoinNode):
                return
            if n.kind not in (JoinKind.INNER, JoinKind.LEFT):
                return
            left, right = n.left, n.right
            if not (
                isinstance(left, RemoteSourceNode)
                and isinstance(right, RemoteSourceNode)
                and left.exchange_type == ExchangeType.REPARTITION
                and right.exchange_type == ExchangeType.REPARTITION
                and left.fragment_id in exchanges
                and right.fragment_id in exchanges
                and right.fragment_id not in modes
            ):
                return
            build_rows = sum(
                int(exchanges[right.fragment_id].attempt_meta(pp).get("rows", 0))
                for pp in range(parts_of[right.fragment_id])
            )
            if build_rows < threshold:
                modes[right.fragment_id] = "broadcast"
                self.last_adaptive.append(
                    {
                        "rule": "partitioned_join_to_broadcast",
                        "build_fragment": right.fragment_id,
                        "probe_fragment": left.fragment_id,
                        "build_rows": build_rows,
                        "threshold": threshold,
                    }
                )

        visit_plan(root, consider)
        return modes

    def _run_exchange(
        self,
        rs: RemoteSourceNode,
        producer_pages: List[Page],
        n_consumer_parts: int,
        subplan: SubPlan,
    ) -> List[Page]:
        """The DCN-tier exchange: repartition/gather/broadcast producer outputs.
        (ref: §3.3 — pull-based page streams; host-mediated in round 1.)
        The FTE tier's adaptive broadcast flip acts through durable input
        specs instead ('all' vs 'part' reads), not through this function."""
        if rs.exchange_type == ExchangeType.GATHER:
            merged = self._merge_host(producer_pages)
            return [merged]
        if rs.exchange_type == ExchangeType.BROADCAST:
            merged = self._merge_host(producer_pages)
            return [merged for _ in range(n_consumer_parts)]
        # REPARTITION by hash of partition keys; REPARTITION_RANGE by sort-key
        # range cuts (distributed sort — part p holds the p-th key range, so
        # the parent merge-GATHER's part-order concat preserves global order)
        host_parts: List[List] = [[] for _ in range(n_consumer_parts)]
        chunk_cols = [_page_to_host(page) for page in producer_pages]
        chunk_cols = [c for c in chunk_cols if c and len(c[0][1])]
        if rs.exchange_type == ExchangeType.REPARTITION_RANGE:
            targets = host_range_targets(chunk_cols, rs, n_consumer_parts)
        else:
            key_idx = [rs.symbols.index(k) for k in rs.partition_keys]
            targets = [
                host_partition_targets(cols, key_idx, n_consumer_parts)
                for cols in chunk_cols
            ]
        for cols, target in zip(chunk_cols, targets):
            for part in range(n_consumer_parts):
                sel = target == part
                if sel.any():
                    host_parts[part].append(
                        [(c[0], c[1][sel], c[2][sel], c[3]) for c in cols]
                    )
        out = []
        for part in range(n_consumer_parts):
            out.append(self._build_page(host_parts[part], rs, subplan))
        return out

    def _merge_host(self, pages: List[Page]) -> Page:
        chunks = [_page_to_host(p) for p in pages]
        chunks = [c for c in chunks if len(c) == 0 or len(c[0][1]) > 0] or chunks[:1]
        return _page_from_host_chunks(chunks)

    def _build_page(self, chunk_list, rs: RemoteSourceNode, subplan: SubPlan) -> Page:
        if not chunk_list:
            # empty_page_for keeps multi-lane storage (vectors, long
            # decimals); a 1-D zero column here would break the consumer's
            # compiled programs
            return empty_page_for(
                rs.symbols, {s: subplan.types[s] for s in rs.symbols}
            )
        return _page_from_host_chunks(chunk_list)
