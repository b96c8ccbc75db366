"""Out-of-core execution for ARBITRARY fragment trees — joins included.

A split-at-a-time aggregation handles exactly one plan shape (scan ->
filter/project -> one aggregation), so no join runs over data larger than
the device. The reference streams *any* operator pipeline over
larger-than-memory data (operator/Driver.java:372 page pull;
operator/join/spilling/HashBuilderOperator.java:68 partitioned spill state
machine; SpillableHashAggregationBuilder). This module is the TPU-first
generalization: the distributed fragmenter's stage cut IS the out-of-core
execution plan, run on ONE chip with a disk-spillable host bucket store as
the exchange:

- `add_exchanges` + `create_fragments` (planner/fragmenter.py) already cut
  the plan at repartition boundaries and split aggregations into
  partial/final — exactly the decomposition grace hash join / partitioned
  aggregation needs. Nothing is re-derived here.
- A producer fragment never materializes its output: each execution unit's
  output page is fetched, hash-bucketed on host (the SAME value-stable rule
  the DCN exchange uses, parallel/runner.host_partition_targets), and
  appended to a `BucketStore` that overflows to disk beyond a byte budget
  (parallel LZ4 spill files, spi/host_pages.write_arrays_lz4).
- SOURCE fragments iterate scan splits in BATCHES of K splits per device
  dispatch; batch N+1 is decoded/assembled on the shared host-I/O pool
  (runtime/spiller.io_pool) while batch N's program runs, so datagen/decode
  no longer serializes with dispatch.
- FIXED_HASH fragments run bucket-at-a-time: every input edge of bucket b
  is co-partitioned by construction, so join build+probe and final
  aggregation see complete key groups. Device memory is bounded by
  (1 + prefetch_depth) buckets' padded inputs, not the table —
  double buffering trades one extra staged bucket of HBM for the overlap;
  prefetch_depth=0 restores the strict single-bucket bound. The loop is
  PIPELINED: a
  `_BucketPrefetcher` reads/decompresses the next buckets' partitions and
  starts their host->device transfers (double buffering via
  `jax.device_put`) under a bounded in-flight byte budget while the current
  bucket's program runs — the device never waits on host I/O unless the
  budget forces it ("Query Processing on Tensor Computation Runtimes",
  arxiv 2203.01877 overlap discipline).
- SINGLE fragments (query tails: final TopN/sort/output) gather the tiny
  upstream results and run once.

Static-shape discipline + compile reuse: bucket inputs are padded to a
SMALL set of canonical shape classes (4x-spaced capacities, `_shape_class`)
instead of per-bucket power-of-two sizes, so the whole bucket loop pays one
XLA compile per class instead of one per distinct bucket size. Inside each
unit program the PER-STAGE capacities narrow adaptively (the
runtime/adaptive machinery applied per fragment): the first unit runs at
full capacity recording per-stage actual row counts, every later unit runs
the TUNED program — join outputs and aggregations sized by measured
cardinality instead of the padded input capacity (a Q3-class scan unit's
partial aggregation over the join output is ~10x cheaper compacted). The
tuned vector is persisted per fragment fingerprint (runtime/capstore), so
repeat runs skip the tuning compile entirely (the Q18 `tune_secs: 655`
pathology).

Unsupported (falls back to in-core or partitioned-spill paths):
REPARTITION_RANGE (out-of-core distributed sort), cross joins (two scans in
one fragment), nested-lane columns crossing an exchange.
"""

from __future__ import annotations

import os
import tempfile
import time
from collections import deque
from dataclasses import replace as _dc_replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..metadata import Metadata, Session
from ..planner.fragmenter import (
    Partitioning,
    PlanFragment,
    RemoteSourceNode,
    SubPlan,
    add_exchanges,
    create_fragments,
)
import jax

from ..planner.plan import (
    ExchangeType,
    LogicalPlan,
    OutputNode,
    PlanNode,
    TableScanNode,
    visit_plan,
)
from ..spi.host_pages import read_arrays_lz4, write_arrays_lz4
from ..spi.page import Page
from ..parallel.runner import (
    _FragmentExecutor,
    _page_from_host_chunks,
    _page_to_host,
    empty_page_for,
    host_partition_targets,
    run_fragment_partition,
    scan_sources,
)
from . import capstore
from . import kernelcost
from . import observability as obs
from .adaptive import _AdaptiveTracedExecutor, candidate_nodes
from .executor import ExecutionError, Relation, _concat_pages, _round_capacity
from .observability import RECORDER
from .spiller import io_pool
from .traced import is_traceable
from .tracing import TRACER

HostChunk = List[Tuple]  # [(type, data, valid, dictionary), ...] per column


class OutOfCoreUnsupported(ExecutionError):
    pass


def _chunk_bytes(cols: HostChunk) -> int:
    return sum(d.nbytes + v.nbytes for _, d, v, _ in cols)


def _shape_class(n: int, base: int = 1024) -> int:
    """Canonical capacity class: 4x-spaced (1024, 4096, 16384, ...) instead
    of per-bucket powers of two. Varying bucket sizes collapse into a
    handful of classes, so the bucket loop compiles once per CLASS — at the
    cost of <=4x padding on the smallest buckets of a class. Delegates to
    capstore.capacity_class: the OOC bucket loop and the device-batching
    plane's batch keys must agree on class edges (see the boundary
    contract there)."""
    from .capstore import capacity_class

    return capacity_class(n, base)


class _DiskChunk:
    """One spilled chunk: data/valid arrays in an LZ4 spill file
    (spi/host_pages.write_arrays_lz4 — per-array frames compress/decompress
    in parallel on the shared I/O pool), types + dictionaries (tiny,
    code-table objects) retained in memory."""

    __slots__ = ("path", "types", "dicts", "nbytes", "rows")

    def __init__(self, path: str, cols: HostChunk, pool=None):
        self.path = path
        self.types = [c[0] for c in cols]
        self.dicts = [c[3] for c in cols]
        self.nbytes = _chunk_bytes(cols)
        self.rows = len(cols[0][1]) if cols else 0
        write_arrays_lz4(
            path, [c[1] for c in cols] + [c[2] for c in cols], pool=pool
        )

    def load(self, pool=None) -> HostChunk:
        arrs = read_arrays_lz4(self.path, pool=pool)
        k = len(self.types)
        return [
            (tp, arrs[i], arrs[k + i], dc)
            for i, (tp, dc) in enumerate(zip(self.types, self.dicts))
        ]


class BucketStore:
    """P-bucket columnar chunk store for one exchange edge: memory-first,
    newest chunks spill to disk once the in-memory byte budget is exceeded
    (the reference's FileSystemExchangeSink role, played by local disk;
    plugin/trino-exchange-filesystem/.../FileSystemExchangeSink.java)."""

    def __init__(self, n_buckets: int, budget_bytes: int, spool_dir: str, tag: str):
        self.n_buckets = n_buckets
        self.budget_bytes = budget_bytes
        self.spool_dir = spool_dir
        self.tag = tag
        self.chunks: List[List[object]] = [[] for _ in range(n_buckets)]
        self.mem_bytes = 0
        self.spilled_bytes = 0
        self._bucket_bytes = [0] * n_buckets
        self._seq = 0

    def append(self, bucket: int, cols: HostChunk, pool=None) -> None:
        if not cols or len(cols[0][1]) == 0:
            return
        size = _chunk_bytes(cols)
        self._bucket_bytes[bucket] += size
        if self.mem_bytes + size > self.budget_bytes:
            path = os.path.join(self.spool_dir, f"{self.tag}-{bucket}-{self._seq}.lz4")
            self._seq += 1
            with RECORDER.span("spill_write", "spill", tag=self.tag,
                               bucket=bucket, bytes=size):
                self.chunks[bucket].append(_DiskChunk(path, cols, pool=pool))
            obs.on_spill_write(size, event=False)
            self.spilled_bytes += size
        else:
            self.chunks[bucket].append(cols)
            self.mem_bytes += size

    def rows_of(self, bucket: int) -> int:
        total = 0
        for c in self.chunks[bucket]:
            total += c.rows if isinstance(c, _DiskChunk) else len(c[0][1])
        return total

    def bucket_nbytes(self, bucket: int) -> int:
        """Uncompressed bytes appended to ``bucket`` (the prefetcher's
        in-flight budget accounting)."""
        return self._bucket_bytes[bucket]

    def read(self, bucket: int, pool=None) -> List[HostChunk]:
        out: List[HostChunk] = []
        for c in self.chunks[bucket]:
            if isinstance(c, _DiskChunk):
                with RECORDER.span("spill_read", "spill", tag=self.tag,
                                   bucket=bucket, bytes=c.nbytes):
                    out.append(c.load(pool=pool))
                obs.on_spill_read(c.nbytes, event=False)
            else:
                out.append(c)
        return out

    def read_all(self, pool=None) -> List[HostChunk]:
        out: List[HostChunk] = []
        for b in range(self.n_buckets):
            out.extend(self.read(b, pool=pool))
        return out

    def drop(self) -> None:
        for lst in self.chunks:
            for c in lst:
                if isinstance(c, _DiskChunk):
                    try:
                        os.unlink(c.path)
                    except OSError:
                        pass
        self.chunks = [[] for _ in range(self.n_buckets)]
        self.mem_bytes = 0


def _split_chunk_by_targets(
    cols: HostChunk, targets: np.ndarray, n: int
) -> List[Optional[HostChunk]]:
    """One stable argsort + slicing instead of n boolean scans."""
    order = np.argsort(targets, kind="stable")
    sorted_t = targets[order]
    bounds = np.searchsorted(sorted_t, np.arange(n + 1))
    gathered = [(tp, d[order], v[order], dc) for tp, d, v, dc in cols]
    out: List[Optional[HostChunk]] = []
    for b in range(n):
        lo, hi = bounds[b], bounds[b + 1]
        if lo == hi:
            out.append(None)
            continue
        out.append([(tp, d[lo:hi], v[lo:hi], dc) for tp, d, v, dc in gathered])
    return out


_empty_page = empty_page_for


class _OOCFragmentExecutor(_FragmentExecutor):
    """Fragment executor whose table scans read a pre-assembled split-batch
    page instead of loading the whole table."""

    def __init__(self, plan, metadata, session, staged, scan_pages: Dict[int, Page]):
        super().__init__(plan, metadata, session, staged, partition=0, n_workers=1)
        self._scan_pages = scan_pages

    def _exec_TableScanNode(self, node: TableScanNode) -> Relation:
        page = self._scan_pages.get(id(node))
        if page is None:
            return super()._exec_TableScanNode(node)
        symbols = tuple(s for s, _ in node.assignments)
        return Relation(page, symbols)


class _AdaptiveUnitExecutor(_AdaptiveTracedExecutor):
    """Traced executor for ONE fragment execution unit: scans AND remote
    sources fed as page arguments, per-stage capacities narrowed to hints
    with (overflow, actual) recording — runtime/adaptive applied inside the
    out-of-core unit program. The whole unit is one XLA program — one
    device dispatch per split batch / bucket, where per-operator dispatch
    pays a host sync per operator."""

    def __init__(
        self, plan, metadata, session, scan_pages, remote_pages, capacities, records
    ):
        super().__init__(plan, metadata, session, scan_pages, capacities, records)
        self._remote_pages = remote_pages

    def _exec_RemoteSourceNode(self, node: RemoteSourceNode) -> Relation:
        return Relation(self._remote_pages[node.fragment_id], node.symbols)


class _BucketPrefetcher:
    """Pipelines the bucket loop: while bucket b's program runs on device,
    the next buckets' partitions are read from the store (disk chunks LZ4-
    decompressed inline on the pool thread), assembled into canonically-
    shaped pages, and `jax.device_put` so the host->device copy is in
    flight before the main loop asks for them (double buffering at
    ``depth=2``). In-flight host bytes stay under ``budget_bytes``; at most
    one bucket is admitted past the budget so the pipeline always makes
    progress. Consumption strictly follows submission order, so a miss only
    happens when prefetch is disabled or the budget starved the queue —
    the main loop then assembles inline (counted in ``misses``)."""

    def __init__(
        self,
        runner: "OutOfCoreRunner",
        hash_edges: List[RemoteSourceNode],
        buckets: List[int],
        caps: Dict[Tuple[int, int], int],
        depth: int,
        budget_bytes: int,
    ):
        self.runner = runner
        self.hash_edges = hash_edges
        self.buckets = buckets
        self.caps = caps
        self.depth = max(0, depth)
        self.budget = max(1, budget_bytes)
        self._next = 0
        self._futures: Dict[int, Tuple[object, int]] = {}
        self._inflight = 0
        self.hits = 0
        self.misses = 0
        self.max_inflight_bytes = 0
        self.max_depth = 0
        self.host_wait_secs = 0.0
        # cross-thread trace context: prefetch jobs run on the shared io_pool
        # whose threads have fresh Tracer stacks — capture the submitting
        # thread's span NOW so pool-side spans parent into the query trace
        # instead of orphaning (and the runner's collector stays active)
        self._trace_ctx = TRACER.capture()
        self._pump()

    def _job(self, b: int) -> Dict[int, Page]:
        with TRACER.attach(self._trace_ctx), obs.collecting(
            self.runner.collector
        ), TRACER.span("ooc.prefetch", bucket=b):
            with RECORDER.span("prefetch_build", "prefetch", bucket=b):
                return self._build(b)

    def _estimate(self, b: int) -> int:
        return sum(
            self.runner.stores[rs.fragment_id].bucket_nbytes(b)
            for rs in self.hash_edges
        )

    def _build(self, b: int, pool=None) -> Dict[int, Page]:
        return {
            rs.fragment_id: self.runner._input_page(
                rs, b, capacity=self.caps.get((rs.fragment_id, b)), pool=pool
            )
            for rs in self.hash_edges
        }

    def _pump(self) -> None:
        while self._next < len(self.buckets) and len(self._futures) < self.depth:
            b = self.buckets[self._next]
            est = self._estimate(b)
            if self._futures and self._inflight + est > self.budget:
                break  # budget-capped; retried after the next get()
            self._inflight += est
            self.max_inflight_bytes = max(self.max_inflight_bytes, self._inflight)
            RECORDER.instant("prefetch_issue", "prefetch", bucket=b, est_bytes=est)
            self._futures[b] = (io_pool().submit(self._job, b), est)
            self.max_depth = max(self.max_depth, len(self._futures))
            self._next += 1

    def get(self, b: int) -> Dict[int, Page]:
        ent = self._futures.pop(b, None)
        if ent is None:
            self.misses += 1
            if self._next < len(self.buckets) and self.buckets[self._next] == b:
                self._next += 1  # keep submission aligned with consumption
            pages = self._build(b, pool=io_pool())
            RECORDER.instant("prefetch_miss", "prefetch", bucket=b)
        else:
            fut, est = ent
            t0 = time.perf_counter()
            with RECORDER.span("prefetch_wait", "prefetch", bucket=b):
                pages = fut.result()
            self.host_wait_secs += time.perf_counter() - t0
            self._inflight -= est
            self.hits += 1
            RECORDER.instant("prefetch_complete", "prefetch", bucket=b)
        self._pump()
        return pages


class OutOfCoreRunner:
    """Drives one query's fragment tree out-of-core on a single chip."""

    def __init__(
        self,
        plan: LogicalPlan,
        metadata: Metadata,
        session: Session,
        n_buckets: int = 64,
        split_batch: int = 8,
        mem_budget_bytes: int = 2 << 30,
        spool_dir: Optional[str] = None,
        prefetch_depth: int = 2,
        prefetch_budget_bytes: int = 256 << 20,
    ):
        self.metadata = metadata
        self.session = session
        self.n_buckets = n_buckets
        self.split_batch = max(1, split_batch)
        self.mem_budget = mem_budget_bytes
        # pipeline knobs: how many buckets/split batches may be staged ahead
        # of the device (2 = classic double buffering) and how many host
        # bytes those staged inputs may pin
        self.prefetch_depth = max(0, prefetch_depth)
        self.prefetch_budget = max(1, prefetch_budget_bytes)
        # distributed sort would need REPARTITION_RANGE (global quantiles over
        # a stream); query tails sort SINGLE instead
        session_ooc = _dc_replace(
            session, properties={**session.properties, "distributed_sort": False}
        )
        distributed = add_exchanges(plan, metadata, session_ooc)
        self.subplan: SubPlan = create_fragments(distributed)
        self.types = self.subplan.types
        self._consumer_edge: Dict[int, RemoteSourceNode] = {}
        for frag in self.subplan.fragments:
            visit_plan(
                frag.root,
                lambda n: self._consumer_edge.__setitem__(n.fragment_id, n)
                if isinstance(n, RemoteSourceNode)
                else None,
            )
        self._validate()  # before mkdtemp: a rejected plan must not leak a dir
        self._own_spool = spool_dir is None
        self.spool_dir = spool_dir or tempfile.mkdtemp(prefix="trino-tpu-ooc-")
        self.stores: Dict[int, BucketStore] = {}
        # observability plane: the runner's stats collector (joins an
        # enclosing query collector when one is active — e.g. a server-side
        # query whose plan routed out-of-core). The trace tooling reads
        # the plane via collector.snapshot().
        self.collector = obs.current_collector() or obs.QueryStatsCollector()
        self.stats: Dict[str, object] = {
            "fragments": len(self.subplan.fragments),
            # pipeline overlap evidence: seconds the
            # main loop spent inside device dispatch+sync vs blocked on
            # prefetch results, plus prefetch hit/miss and shape-class counts
            "device_busy_secs": 0.0,
            "compile_secs": 0.0,
            "fallback_secs": 0.0,
            "host_wait_secs": 0.0,
            "emit_secs": 0.0,
            "prefetch_hits": 0,
            "prefetch_misses": 0,
            "prefetch_max_inflight_bytes": 0,
            "prefetch_max_depth": 0,
            "caps_from_store": 0,
        }
        # per-(fragment, capacity-vector) jitted unit programs + the record
        # order their actuals vector reports in
        self._unit_fns: Dict[Tuple[int, tuple], object] = {}
        self._unit_keys: Dict[Tuple[int, tuple], List[int]] = {}
        # per-fragment tuned per-stage capacities (node id -> capacity) at
        # the tuning unit's input capacity (_caps_ref), plus the per-input-
        # class rescaled vectors actually handed to programs
        self._unit_caps: Dict[int, Dict[int, int]] = {}
        self._caps_ref: Dict[int, int] = {}
        self._class_caps: Dict[Tuple[int, int], Dict[int, int]] = {}
        self._caps_tuned: Dict[int, bool] = {}
        self._candidates: Dict[int, list] = {}
        self._frag_fp: Dict[int, str] = {}
        self._traceable: Dict[int, bool] = {}
        self._shape_classes: set = set()

    # ------------------------------------------------------------ validation

    def _validate(self) -> None:
        for frag in self.subplan.fragments:
            scans: List[TableScanNode] = []
            visit_plan(
                frag.root,
                lambda n: scans.append(n) if isinstance(n, TableScanNode) else None,
            )
            if len(scans) > 1:
                raise OutOfCoreUnsupported(
                    "fragment with multiple scans (cross join?) cannot stream"
                )
            edge = self._consumer_edge.get(frag.fragment_id)
            if edge is not None and edge.exchange_type == ExchangeType.REPARTITION_RANGE:
                raise OutOfCoreUnsupported(
                    "REPARTITION_RANGE (distributed sort) not supported out-of-core"
                )

    # ------------------------------------------------------------- plumbing

    def _edge_buckets(self, fid: int) -> int:
        edge = self._consumer_edge.get(fid)
        if edge is not None and edge.exchange_type == ExchangeType.REPARTITION:
            return self.n_buckets
        return 1

    def _emit(self, frag: PlanFragment, page: Page) -> None:
        """Bucket one execution unit's output into the fragment's store."""
        t0 = time.perf_counter()
        try:
            with RECORDER.span("emit", "bucket", fragment=frag.fragment_id):
                store = self.stores[frag.fragment_id]
                cols = _page_to_host(page)
                if not cols:
                    return
                edge = self._consumer_edge.get(frag.fragment_id)
                if (
                    edge is None
                    or edge.exchange_type != ExchangeType.REPARTITION
                    or store.n_buckets == 1
                ):
                    store.append(0, cols, pool=io_pool())
                    return
                out_symbols = list(frag.root.output_symbols)
                key_idx = [out_symbols.index(k) for k in edge.partition_keys]
                targets = host_partition_targets(cols, key_idx, store.n_buckets)
                for b, chunk in enumerate(
                    _split_chunk_by_targets(cols, targets, store.n_buckets)
                ):
                    if chunk is not None:
                        store.append(b, chunk, pool=io_pool())
        finally:
            dt = time.perf_counter() - t0
            self.stats["emit_secs"] += dt
            self.collector.add_time("emit_secs", dt, fragment=frag.fragment_id)

    def _input_page(
        self,
        rs: RemoteSourceNode,
        bucket: Optional[int],
        capacity: Optional[int] = None,
        pool=None,
    ) -> Page:
        """Assemble one remote source's input page for one execution unit.
        ``capacity`` overrides the power-of-two default with a canonical
        shape class (bucket loop); ``pool`` parallelizes LZ4 decompression
        of spilled chunks — pass None when already ON a pool thread."""
        store = self.stores[rs.fragment_id]
        if rs.exchange_type == ExchangeType.REPARTITION and bucket is not None:
            chunks = store.read(bucket, pool=pool)
        else:  # GATHER / BROADCAST: complete producer output
            chunks = store.read_all(pool=pool)
        if not chunks:
            return _empty_page(rs.symbols, self.types)
        rows = sum(len(c[0][1]) for c in chunks)
        # static-shape discipline: canonical class when given (bucket loop
        # shares compiled programs across ALL buckets of a class), else
        # power-of-two padding
        cap = capacity if capacity is not None and capacity >= rows else (
            _round_capacity(max(rows, 1))
        )
        nbytes = sum(_chunk_bytes(c) for c in chunks)
        self.collector.add_count("h2d_bytes", nbytes)
        self.collector.add_count("input_rows", rows)
        RECORDER.instant(
            "h2d_transfer", "transfer", fragment=rs.fragment_id,
            bucket=-1 if bucket is None else bucket, bytes=nbytes, rows=rows,
        )
        # device_put starts the host->device copy NOW — from a prefetch
        # thread this is the double-buffered transfer overlapping compute
        return jax.device_put(_page_from_host_chunks(chunks, capacity=cap))

    def _remotes_of(self, frag: PlanFragment) -> List[RemoteSourceNode]:
        from ..planner.fragmenter import remote_sources

        return remote_sources(frag.root)

    def _fragment_traceable(self, frag: PlanFragment) -> bool:
        flag = self._traceable.get(frag.fragment_id)
        if flag is None:
            flag = is_traceable(
                LogicalPlan(frag.root, self.types),
                allow_joins=True,
                extra_types=(RemoteSourceNode,),
            )
            self._traceable[frag.fragment_id] = flag
        return flag

    def _unit_fn(self, frag: PlanFragment, caps: Dict[int, int]):
        """One jitted program per (fragment, per-stage capacity vector);
        jax's own cache handles the handful of canonical input shape
        classes. Returns (fn, keys) where ``keys`` lists the node ids in
        the order the actuals vector reports them."""
        fid = frag.fragment_id
        sig = tuple(sorted(caps.items()))
        key = (fid, sig)
        fn = self._unit_fns.get(key)
        if fn is not None:
            return fn, self._unit_keys[key]
        plan = LogicalPlan(frag.root, self.types)
        remote_fids = [rs.fragment_id for rs in self._remotes_of(frag)]
        root = frag.root
        keys_holder: List[int] = []

        def run(scan_page: Optional[Page], remote_pages: Tuple[Page, ...]):
            import jax.numpy as jnp

            scans = {} if scan_page is None else {0: scan_page}
            records: List[Tuple[int, object, object]] = []
            executor = _AdaptiveUnitExecutor(
                plan, self.metadata, self.session, scans,
                dict(zip(remote_fids, remote_pages)), dict(caps), records,
            )
            if isinstance(root, OutputNode):
                rel = executor.eval(root.source)
                symbols = root.symbols
            else:
                rel = executor.eval(root)
                symbols = root.output_symbols
            page = Page(
                tuple(rel.column_for(s) for s in symbols), rel.page.active
            )
            keys_holder.clear()
            keys_holder.extend(k for k, _, _ in records)
            overflow = jnp.int64(0)
            for _, o, _ in records:
                overflow = overflow + o.astype(jnp.int64)
            for o in executor.overflows:
                overflow = overflow + o.astype(jnp.int64)
            actuals = (
                jnp.stack([a for _, _, a in records])
                if records
                else jnp.zeros((0,), dtype=jnp.int64)
            )
            return page, overflow, actuals

        fn = kernelcost.jit(run, label="ooc_unit")
        self._unit_fns[key] = fn
        self._unit_keys[key] = keys_holder
        return fn, keys_holder

    # ------------------------------------------ per-stage capacity reuse

    def _caps_key(self, frag: PlanFragment) -> str:
        fp = self._frag_fp.get(frag.fragment_id)
        if fp is None:
            fp = capstore.plan_fingerprint(LogicalPlan(frag.root, self.types))
            self._frag_fp[frag.fragment_id] = fp
        return (fp + ":ooc-caps") if fp else ""

    def _frag_candidates(self, frag: PlanFragment) -> list:
        fid = frag.fragment_id
        nodes = self._candidates.get(fid)
        if nodes is None:
            nodes = candidate_nodes(LogicalPlan(frag.root, self.types))
            self._candidates[fid] = nodes
        return nodes

    def _seed_caps(self, frag: PlanFragment) -> Dict[int, int]:
        """The fragment's REF-scale per-stage capacity vector: tuned on the
        FIRST unit and reused by every later unit, seeded from the capstore
        fingerprint when a previous run of the same fragment shape already
        tuned it — one tuning compile per plan shape, ever, instead of a
        tune per bucket. The stored vector carries the tuning unit's input
        capacity as its last element so a later process can rescale."""
        fid = frag.fragment_id
        caps = self._unit_caps.get(fid)
        if caps is not None:
            return caps
        caps = {}
        key = self._caps_key(frag)
        if key:
            vec = capstore.load(key)
            nodes = self._frag_candidates(frag)
            if vec is not None and len(vec) == len(nodes) + 1 and vec[-1]:
                for node, cap in zip(nodes, vec):
                    if cap is not None:
                        caps[id(node)] = int(cap)
                self._caps_ref[fid] = int(vec[-1])
                self._caps_tuned[fid] = True
                self.stats["caps_from_store"] += 1
                self.collector.add_count("caps_from_store")
        self._unit_caps[fid] = caps
        return caps

    def _store_caps(self, frag: PlanFragment) -> None:
        key = self._caps_key(frag)
        fid = frag.fragment_id
        if not key or not self._caps_ref.get(fid):
            return
        caps = self._unit_caps.get(fid, {})
        capstore.save(
            key,
            [caps.get(id(n)) for n in self._frag_candidates(frag)]
            + [self._caps_ref[fid]],
        )

    def _caps_for(self, frag: PlanFragment, in_cap: int) -> Dict[int, int]:
        """Per-stage capacities for a unit whose input capacity class is
        ``in_cap``: the ref-scale tuned vector, linearly rescaled when this
        unit's input class differs from the tuning unit's (a scan fragment
        tunes on a cheap single-split unit, then full split batches run at
        8x the input — stage cardinalities scale roughly with input rows,
        and the overflow retry catches the cases where they don't)."""
        fid = frag.fragment_id
        cached = self._class_caps.get((fid, in_cap))
        if cached is not None:
            return cached
        base = self._seed_caps(frag)
        ref = self._caps_ref.get(fid)
        if not base or not ref or not in_cap or in_cap == ref:
            caps = dict(base)
        else:
            r = in_cap / ref
            caps = {
                k: max(1024, _round_capacity(int(v * r) + 16))
                for k, v in base.items()
            }
        self._class_caps[(fid, in_cap)] = caps
        return caps

    def _tune_caps(
        self, frag: PlanFragment, in_cap: int, keys: List[int], actuals
    ) -> None:
        """Record the first successful unit's measured per-stage counts as
        the fragment's ref-scale capacity vector (x1.5 headroom +
        power-of-two rounding absorbs unit-to-unit variation; an
        overflowing later unit grows its class and recompiles once)."""
        fid = frag.fragment_id
        caps = {
            k: _round_capacity(int(act * 1.5) + 16)
            for k, act in zip(keys, np.asarray(actuals))
        }
        self._unit_caps[fid] = caps
        self._caps_ref[fid] = in_cap
        self._caps_tuned[fid] = True
        self._class_caps[(fid, in_cap)] = dict(caps)
        self._store_caps(frag)

    def _run_unit(
        self,
        frag: PlanFragment,
        staged: Dict[int, List[Page]],
        scan_pages: Dict[int, Page],
    ) -> Page:
        fid = frag.fragment_id
        if self._fragment_traceable(frag):
            scan_page = next(iter(scan_pages.values())) if scan_pages else None
            remote_fids = [rs.fragment_id for rs in self._remotes_of(frag)]
            remote_pages = tuple(staged[f][0] for f in remote_fids)
            in_cap = scan_page.capacity if scan_page is not None else max(
                (p.capacity for p in remote_pages), default=0
            )
            caps = self._caps_for(frag, in_cap)
            for attempt in range(10):
                fn, keys = self._unit_fn(frag, caps)
                try:
                    n_compiled = fn._cache_size()
                except Exception:
                    n_compiled = None
                t0 = time.perf_counter()
                # device batching plane: an OOC unit is ONE program launch —
                # it books the launch counter, and when batching is on it
                # yields the admission gate between units so higher-priority
                # point-query batches are no longer head-of-line-blocked by
                # a long bucket loop
                from .device_scheduler import launch_slot, on_program_launch

                try:
                    gated = bool(self.session.get("device_batching"))
                except KeyError:
                    gated = False
                with launch_slot(gated), RECORDER.span(
                    "unit", "bucket", fragment=fid, attempt=attempt
                ), obs.compile_window() as cw:
                    on_program_launch()
                    page, overflow, actuals = fn(scan_page, remote_pages)
                    ovf = int(np.asarray(overflow))  # blocks until device done
                elapsed = time.perf_counter() - t0
                # attribute trace+compile time separately so device_busy_secs
                # reflects actual overlap, not cold compiles
                try:
                    compiled = n_compiled is not None and fn._cache_size() > n_compiled
                except Exception:
                    compiled = False
                key = "compile_secs" if compiled else "device_busy_secs"
                self.stats[key] += elapsed
                # the jax.monitoring listener already credited cw.seconds of
                # backend-compile time to the QUERY total — book only the
                # remainder there (or compile time would count twice), but
                # give the fragment its full share so fragments still sum
                # to the query-level numbers
                self.collector.add_time(
                    key, max(elapsed - cw.seconds, 0.0), fragment=fid
                )
                if cw.seconds:
                    self.collector.add_fragment_time(
                        fid, "compile_secs", cw.seconds
                    )
                if ovf:
                    self.collector.add_count("overflow_retries")
                if ovf == 0:
                    if not self._caps_tuned.get(fid):
                        self._tune_caps(frag, in_cap, keys, actuals)
                    return page
                # a stage overflowed its capacity (the untuned first unit
                # at full capacity never does; a rescaled later unit can):
                # grow every point to at least its observed count and retry
                grown = dict(caps)
                for k, act in zip(keys, np.asarray(actuals)):
                    base = _round_capacity(int(act * (1.5 + attempt)) + 16)
                    grown[k] = max(base, caps.get(k, 0))
                caps = grown
                self._class_caps[(fid, in_cap)] = caps
                # back-propagate to the ref-scale vector + capstore: an
                # undersized persisted vector must not make every other
                # class — and every future process — re-pay this overflow
                # dispatch and recompile
                ref = self._caps_ref.get(fid)
                if self._caps_tuned.get(fid) and ref:
                    r = (in_cap / ref) if in_cap else 1.0
                    base_vec = self._unit_caps.setdefault(fid, {})
                    for k, cap in grown.items():
                        back = _round_capacity(int(cap / r) if r else cap)
                        if back > base_vec.get(k, 0):
                            base_vec[k] = back
                    self._store_caps(frag)
                    # other classes' cached vectors rescaled from the old
                    # undersized base: drop them so they re-derive from the
                    # grown vector instead of re-paying this overflow
                    for ck in [
                        ck
                        for ck in self._class_caps
                        if ck[0] == fid and ck[1] != in_cap
                    ]:
                        del self._class_caps[ck]
            raise ExecutionError("OOC unit capacity tuning did not converge")
        plan = LogicalPlan(frag.root, self.types)
        ex = _OOCFragmentExecutor(plan, self.metadata, self.session, staged, scan_pages)
        t0 = time.perf_counter()
        with RECORDER.span("unit_fallback", "bucket", fragment=fid):
            page = run_fragment_partition(ex, frag.root)
        # host-synced op-at-a-time execution, NOT device-saturating work —
        # booked separately so device_busy_secs stays honest
        dt = time.perf_counter() - t0
        self.stats["fallback_secs"] += dt
        self.collector.add_time("fallback_secs", dt, fragment=fid)
        return page

    # ------------------------------------------------------------- stages

    def _execute_source(self, frag: PlanFragment) -> None:
        scan: List[TableScanNode] = []
        visit_plan(
            frag.root,
            lambda n: scan.append(n) if isinstance(n, TableScanNode) else None,
        )
        node = scan[0]
        splits, col_indexes, provider = scan_sources(self.metadata, node)

        # non-repartition inputs (broadcast builds, gathered subquery results)
        staged = {
            rs.fragment_id: [self._input_page(rs, None, pool=io_pool())]
            for rs in self._remotes_of(frag)
        }
        # the FIRST unit is always a single split: it doubles as the
        # per-stage capacity tuning unit (_tune_caps), so keep it cheap —
        # every later batch runs the tuned (rescaled) program.
        # Unconditional (not gated on tuning state) so unit boundaries —
        # and therefore float combination order — are identical between
        # cold and capstore-warm runs.
        if len(splits) > 1:
            batches = [splits[:1]] + [
                splits[i : i + self.split_batch]
                for i in range(1, len(splits), self.split_batch)
            ]
        else:
            batches = [
                splits[i : i + self.split_batch]
                for i in range(0, max(len(splits), 1), self.split_batch)
            ]

        trace_ctx = TRACER.capture()

        def assemble(batch) -> Page:
            # pool-side: re-attach the query's trace context + collector
            # (spiller.io_pool threads have fresh thread-local stacks)
            with TRACER.attach(trace_ctx), obs.collecting(self.collector):
                with RECORDER.span(
                    "scan_batch", "scan", fragment=frag.fragment_id,
                    splits=len(batch),
                ):
                    if batch:
                        pages = [
                            provider.create_page_source(sp, col_indexes)
                            for sp in batch
                        ]
                        page = pages[0] if len(pages) == 1 else _concat_pages(pages)
                    else:  # empty table still needs one unit (partial global aggs)
                        page = _empty_page(
                            tuple(s for s, _ in node.assignments), self.types
                        )
                    # start the host->device copy from the worker thread (double
                    # buffering: batch N+1 transfers while batch N computes)
                    return jax.device_put(page)

        units = 0
        if self.prefetch_depth < 1:
            for batch in batches:  # serial fallback (prefetch disabled)
                out = self._run_unit(frag, staged, {id(node): assemble(batch)})
                self._emit(frag, out)
                units += 1
        else:
            from .memory import page_bytes

            pending: deque = deque()
            idx = 0
            est_bytes: Optional[int] = None  # measured from consumed batches
            while idx < len(batches) or pending:
                # the byte budget caps staged batches too: once a batch's
                # real size is known, admit only as many as fit (always >=1
                # so the pipeline keeps moving)
                if est_bytes:
                    limit = max(
                        1, min(self.prefetch_depth, self.prefetch_budget // est_bytes)
                    )
                else:
                    limit = self.prefetch_depth
                while idx < len(batches) and len(pending) < limit:
                    pending.append(io_pool().submit(assemble, batches[idx]))
                    idx += 1
                t0 = time.perf_counter()
                page = pending.popleft().result()
                dt = time.perf_counter() - t0
                self.stats["host_wait_secs"] += dt
                self.collector.add_time(
                    "host_wait_secs", dt, fragment=frag.fragment_id
                )
                est_bytes = max(est_bytes or 0, page_bytes(page))
                out = self._run_unit(frag, staged, {id(node): page})
                self._emit(frag, out)
                units += 1
        self.stats[f"f{frag.fragment_id}_units"] = units

    def _bucket_caps(
        self, hash_edges: List[RemoteSourceNode], buckets: List[int]
    ) -> Dict[Tuple[int, int], int]:
        """Canonical shape class per (edge, bucket): 4x-spaced classes mean
        a 32-bucket loop typically sees 1-2 distinct input shapes per edge —
        one compile per class, not per bucket."""
        caps: Dict[Tuple[int, int], int] = {}
        for rs in hash_edges:
            store = self.stores[rs.fragment_id]
            for b in buckets:
                cls = _shape_class(max(store.rows_of(b), 1))
                caps[(rs.fragment_id, b)] = cls
                self._shape_classes.add((rs.fragment_id, cls))
        return caps

    def _execute_buckets(self, frag: PlanFragment) -> None:
        remotes = self._remotes_of(frag)
        hash_edges = [
            rs for rs in remotes if rs.exchange_type == ExchangeType.REPARTITION
        ]
        if not hash_edges:
            # no co-partitioned inputs (all broadcast/gather): one unit
            self._emit(frag, self._execute_single(frag))
            self.stats[f"f{frag.fragment_id}_units"] = 1
            return
        shared = {
            rs.fragment_id: [self._input_page(rs, None, pool=io_pool())]
            for rs in remotes
            if rs.exchange_type != ExchangeType.REPARTITION
        }
        # empty buckets emit nothing for every operator
        buckets = [
            b
            for b in range(self.n_buckets)
            if any(self.stores[rs.fragment_id].rows_of(b) for rs in hash_edges)
        ]
        caps = self._bucket_caps(hash_edges, buckets)
        prefetcher = _BucketPrefetcher(
            self, hash_edges, buckets, caps,
            self.prefetch_depth, self.prefetch_budget,
        )
        units = 0
        for b in buckets:
            staged = dict(shared)
            for fid, page in prefetcher.get(b).items():
                staged[fid] = [page]
            out = self._run_unit(frag, staged, {})
            self._emit(frag, out)
            units += 1
        self.stats[f"f{frag.fragment_id}_units"] = units
        self.stats["host_wait_secs"] += prefetcher.host_wait_secs
        self.stats["prefetch_hits"] += prefetcher.hits
        self.stats["prefetch_misses"] += prefetcher.misses
        self.collector.add_time(
            "host_wait_secs", prefetcher.host_wait_secs,
            fragment=frag.fragment_id,
        )
        self.collector.add_count("prefetch_hits", prefetcher.hits)
        self.collector.add_count("prefetch_misses", prefetcher.misses)
        self.stats["prefetch_max_inflight_bytes"] = max(
            self.stats["prefetch_max_inflight_bytes"],
            prefetcher.max_inflight_bytes,
        )
        self.stats["prefetch_max_depth"] = max(
            self.stats["prefetch_max_depth"], prefetcher.max_depth
        )

    def _execute_single(self, frag: PlanFragment) -> Page:
        staged = {
            rs.fragment_id: [self._input_page(rs, None, pool=io_pool())]
            for rs in self._remotes_of(frag)
        }
        return self._run_unit(frag, staged, {})

    # ------------------------------------------------------------- driver

    def execute(self) -> Tuple[List[str], Page]:
        with obs.collecting(self.collector), RECORDER.span(
            "ooc_query", "query", fragments=len(self.subplan.fragments)
        ):
            return self._execute()

    def _execute(self) -> Tuple[List[str], Page]:
        try:
            final_page: Optional[Page] = None
            root_id = self.subplan.root_fragment.fragment_id
            for frag in self.subplan.fragments:
                has_scan: List[TableScanNode] = []
                visit_plan(
                    frag.root,
                    lambda n: has_scan.append(n)
                    if isinstance(n, TableScanNode)
                    else None,
                )
                if frag.fragment_id == root_id:
                    final_page = self._execute_single(frag)
                    break
                self.stores[frag.fragment_id] = BucketStore(
                    self._edge_buckets(frag.fragment_id),
                    self.mem_budget,
                    self.spool_dir,
                    f"f{frag.fragment_id}",
                )
                if has_scan:
                    self._execute_source(frag)
                elif frag.partitioning in (
                    Partitioning.FIXED_HASH,
                    Partitioning.FIXED_ARBITRARY,
                ):
                    self._execute_buckets(frag)
                else:
                    self._emit(frag, self._execute_single(frag))
                # every fragment has exactly ONE consumer (each REMOTE
                # exchange cuts its own fragment), so its producers' stores
                # are dead as soon as it finishes: free host memory + spool
                # eagerly — peak usage is bounded by adjacent stages, not the
                # whole fragment tree
                for fid in frag.input_fragments:
                    store = self.stores.get(fid)
                    if store is not None:
                        store.drop()  # spilled_bytes counter survives drop
            assert final_page is not None
            root = self.subplan.root_fragment.root
            assert isinstance(root, OutputNode)
            self.stats["spilled_bytes"] = sum(
                s.spilled_bytes for s in self.stores.values()
            )
            self.stats["shape_classes"] = len(self._shape_classes)
            compiles = 0
            for fn in self._unit_fns.values():
                try:
                    compiles += fn._cache_size()
                except Exception:
                    pass
            self.stats["compiles"] = compiles
            return list(root.column_names), final_page
        finally:
            for s in self.stores.values():
                s.drop()
            if self._own_spool:
                try:
                    os.rmdir(self.spool_dir)
                except OSError:
                    pass


def execute_out_of_core(
    plan: LogicalPlan,
    metadata: Metadata,
    session: Session,
    n_buckets: int = 64,
    split_batch: int = 8,
    mem_budget_bytes: int = 2 << 30,
    prefetch_depth: int = 2,
    prefetch_budget_bytes: int = 256 << 20,
) -> Tuple[List[str], Page]:
    runner = OutOfCoreRunner(
        plan,
        metadata,
        session,
        n_buckets=n_buckets,
        split_batch=split_batch,
        mem_budget_bytes=mem_budget_bytes,
        prefetch_depth=prefetch_depth,
        prefetch_budget_bytes=prefetch_budget_bytes,
    )
    return runner.execute()
