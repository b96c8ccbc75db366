"""Device batching plane: ragged multi-query packing of compatible fragments.

The motivating case is a mixed Q1/Q3/Q6/Q13 replay from many clients: the
chip runs one fragment program at a time, so admission control merely
reorders a serial queue. The LLM-serving literature supplies the fix
("Ragged Paged Attention", arXiv:2604.15464: continuous batching of ragged,
shape-heterogeneous requests into one kernel; "Query Processing on Tensor
Computation Runtimes", arXiv:2203.01877: amortizing program dispatch across
work items is where tensor-runtime SQL wins live traffic). This module is
the scheduler that sits between the executors and the chip:

- **Work items, not launches.** Batchable fragment subtrees
  (scan→filter→project→(partial-)agg, the same shape the fragment cache
  recognizes) SUBMIT to the scheduler instead of dispatching their operator
  programs directly. The *batch key* is the compiled-program cache key we
  already have: the plancodec structural fingerprint of the subtree plus
  the capstore canonical capacity class (+ layout signature) of its input —
  items sharing a key would compile the SAME XLA program, so they can share
  one launch.

- **Ragged lanes.** A group of compatible items stacks its input pages
  along a new leading batch dim (all lanes sit at one canonical capacity
  class; per-lane row counts ride the active masks — the ragged part) and
  executes the subtree ONCE as a ``jax.jit(jax.vmap(lane_fn))`` program
  whose per-lane outputs are demuxed back to their owning queries. Lanes
  whose input page is the *same device array* (the shared-scan case below)
  deduplicate: the computation runs once and fans out — bit-identical by
  construction. A group that degenerates to one unique lane executes the
  plain serial per-operator programs, so the single-query path stays
  byte-identical with batching on.

- **Priority admission between launches.** Launches serialize through an
  admission gate ordered by (resource-group scheduling weight, queue age):
  a big OOC query's unit launches (runtime/ooc.py routes them through the
  same gate) no longer head-of-line-block a hundred Q6-class point queries
  — between any two launches the highest-priority oldest waiter goes next.

- **Shared-scan elimination.** The fragment cache's single-flight dedup
  generalizes from *identical prefixes* to *overlapping scans*: concurrent
  queries whose leaf scans cover the same table + conjuncts (the statstore
  canonical leaf key) subsume into ONE scan whose immutable device pages
  fan out to every waiter. Keys carry the connector version token
  (cache_table_version), so a post-DML arrival can never share a pre-DML
  page; unversioned or cache-bypass catalogs never share.

Failure isolation: a mid-batch failure (chaos kill, OOM) never poisons
peers — the batched launch falls back to per-lane serial execution, so only
the genuinely failing lane's query fails; a shared-scan winner that dies
publishes the error and waiters fall back to scanning themselves.

Everything is gated behind the ``device_batching`` session knob (default
off): with it off no binding is attached and the execution path is
byte-identical to the pre-plane engine (one ``is None`` attribute read).

Observability: paired ``batch_admit``/``batch_launch``/``batch_demux``
flight spans (lane count, packed rows, launch key on the E-args),
``trino_tpu_batched_fragments_total`` / ``trino_tpu_batch_lane_occupancy``
/ ``trino_tpu_device_programs_total`` /
``trino_tpu_shared_scan_{hits,misses}_total`` metrics, and
``tools/obs_smoke.py run_batching_smoke`` in tier-1.
"""

from __future__ import annotations

import heapq
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from .tracing import TRACER

# how long a shared-scan entry may serve after its flight completed: long
# enough for back-to-back dashboard arrivals to subsume, short enough that
# lingering device pages cannot pile up (entries are also LRU-bounded)
SHARED_SCAN_TTL_SECS = 10.0
SHARED_SCAN_MAX_ENTRIES = 32
# how long a completed subtree subsumption may keep serving — the
# CONTINUOUS-BATCHING WINDOW, deliberately short: under load, same-class
# queries arrive within it and amortize into one computation (throughput
# scales with concurrency); at low load it expires between arrivals and
# every query recomputes (this is a batching window, not a result cache —
# the warm-path cache plane owns longer-lived reuse). Bit-identity holds
# at ANY length: the key pins the input pages' identities, so a lingered
# result can never be staler than the scans a recomputation would read.
SUBSUME_LINGER_SECS = 0.25
SUBSUME_MAX_ENTRIES = 64
# how long a lane waits on its batch leader (or a scan waiter on the scan
# winner) before giving up and executing itself — a hung peer must never
# wedge a query (the fragment cache's single-flight contract)
LANE_WAIT_SECS = 120.0


# --------------------------------------------------------------- observability


def _counter(name: str, labels=None):
    from .metrics import REGISTRY

    helps = {
        "trino_tpu_device_programs_total":
            "device program launches at the operator/fragment boundary "
            "(a packed ragged batch counts once; serial operators count "
            "one per program)",
        "trino_tpu_batched_fragments_total":
            "fragment work items served by multi-lane ragged batch launches",
        "trino_tpu_subsumed_fragments_total":
            "fragment subtrees served by a concurrent identical execution "
            "(whole-subtree single-flight subsumption)",
        "trino_tpu_shared_scan_hits_total":
            "leaf scans served from a concurrent overlapping scan "
            "(shared-scan elimination)",
        "trino_tpu_shared_scan_misses_total":
            "leaf scans that executed (shared-scan flight winners + "
            "unshareable scans)",
    }
    return REGISTRY.counter(name, labels or {}, help=helps[name])


def _occupancy_histogram():
    from .metrics import REGISTRY

    # lanes per launch: 1, 2, 4, 8, ... (powers of two match the padded
    # batch shapes the launcher actually compiles)
    return REGISTRY.histogram(
        "trino_tpu_batch_lane_occupancy",
        buckets=[1, 2, 4, 8, 16, 32],
        help="work-item lanes packed per device batch launch",
    )


_programs_counter = None


def on_program_launch(n: int = 1) -> None:
    """One device program launch at the operator/fragment boundary — the
    counter the batching tests read (fewer launches is the win).
    Ticked per operator program on the serial path (executor._eval_node)
    and ONCE per packed ragged launch here; the counter object is memoized
    — the hot-path cost is one lock-guarded float add."""
    global _programs_counter
    c = _programs_counter
    if c is None:
        c = _programs_counter = _counter("trino_tpu_device_programs_total")
    c.inc(n)
    # the same tick on the span that launched (the operator's `op:`): a
    # statement's `launches` is the sum over its tree
    TRACER.count("launches", n)


def program_launches() -> float:
    return _counter("trino_tpu_device_programs_total").value


# ------------------------------------------------------------------- priority


_priority_tls = threading.local()


class priority_scope:
    """Thread-local resource-group priority for everything this thread
    submits to the scheduler (QueryManager installs it with the admitted
    ticket's group scheduling weight)."""

    def __init__(self, weight: float):
        self.weight = float(weight)

    def __enter__(self):
        self._prev = getattr(_priority_tls, "weight", None)
        _priority_tls.weight = self.weight
        return self

    def __exit__(self, *exc):
        if self._prev is None:
            del _priority_tls.weight
        else:
            _priority_tls.weight = self._prev
        return False


def current_priority() -> float:
    return float(getattr(_priority_tls, "weight", 1.0))


class _LaunchGate:
    """Priority admission between launches: one launch holds the gate at a
    time, and on release the waiter with the highest (weight, age) key is
    admitted — the scheduler's "admit new items between program launches"
    contract. FIFO within a weight (arrival time breaks ties)."""

    def __init__(self):
        self._cond = threading.Condition()
        self._busy = False
        self._waiting: List[Tuple[float, float, int]] = []  # heap
        self._seq = 0

    def acquire(self, priority: float) -> None:
        with self._cond:
            self._seq += 1
            token = (-float(priority), time.monotonic(), self._seq)
            heapq.heappush(self._waiting, token)
            try:
                while self._busy or self._waiting[0] != token:
                    self._cond.wait(timeout=1.0)
            except BaseException:
                # an interrupted waiter must not leave its token at the
                # heap head — that would wedge the process-global gate
                self._waiting.remove(token)
                heapq.heapify(self._waiting)
                self._cond.notify_all()
                raise
            heapq.heappop(self._waiting)
            self._busy = True

    def release(self) -> None:
        with self._cond:
            self._busy = False
            self._cond.notify_all()

    def __enter__(self):
        self.acquire(current_priority())
        return self

    def __exit__(self, *exc):
        self.release()
        return False


# ------------------------------------------------------------ batchable chain


def _split_chain(root):
    """AggregationNode root -> (bottom-up [input.., root] chain above the
    input node, input node). The chain is the pure part the scheduler can
    trace once and vmap; the input node (scan/exchange/...) is evaluated by
    the owning executor (shared-scan elimination hooks the scan there)."""
    from ..planner.plan import FilterNode, ProjectNode

    chain = [root]
    cur = root.source
    while isinstance(cur, (FilterNode, ProjectNode)):
        chain.append(cur)
        cur = cur.source
    chain.reverse()
    return chain, cur


def _chain_statically_batchable(root, session) -> bool:
    """Cheap pre-input checks: aggregate shapes a host-sync-free lane
    function can express (the direct-indexed / global paths of
    aggregate_relation). The domain check (dictionary sizes) needs the
    input relation and happens in :meth:`BatchBinding.execute`."""
    from .executor import _DIRECT_AGG_FUNCS, _LANE_AGGS, _RESORT_AGGS

    for _, a in root.aggregations:
        if a.distinct or a.ordering:
            return False
        if a.function not in _DIRECT_AGG_FUNCS:
            return False
        if a.function in _LANE_AGGS or a.function in _RESORT_AGGS:
            return False
    # the spill path host-syncs sizes and hash-partitions — serial only
    try:
        if int(session.get("spill_operator_threshold_bytes") or 0):
            return False
    except KeyError:
        pass
    # Pallas kernels are not exercised under vmap — keep them serial. The
    # mode vocabulary resolves through the central knob registry (the same
    # policy executor._pallas_mode applies), so the two launch sites cannot
    # drift. The megakernel plane (pallas_fusion) composes freely: batchable
    # chains are scan-rooted and join-free, so a fused join/agg fragment
    # never appears inside a ragged lane body — fusion and batching serve
    # disjoint fragment shapes of the same query.
    from .. import knobs

    try:
        if knobs.resolve_pallas_aggregation(
            session.get("pallas_aggregation")
        ) != "off":
            return False
    except KeyError:
        pass
    return True


def _layout_sig(page) -> Tuple:
    """Input layout half of the batch key: everything the traced program
    shape depends on beyond the plan structure — dtypes, capacity, nested
    lane widths, dictionary CONTENT identity (fingerprints: two lanes with
    content-equal dictionaries run one program over either's codes)."""
    def col_sig(c) -> Tuple:
        return (
            str(c.data.dtype), tuple(c.data.shape[1:]),
            None if c.dictionary is None else c.dictionary.fingerprint(),
            None if c.lengths is None else str(c.lengths.dtype),
            None if c.elem_valid is None else tuple(c.elem_valid.shape[1:]),
            tuple(col_sig(k) for k in c.children),
        )

    return (page.capacity, tuple(col_sig(c) for c in page.columns))


def _apply_chain_node(rel, node, types):
    """One pure chain step — the EXACT per-operator programs the serial
    executor dispatches (_exec_FilterNode/_exec_ProjectNode/the
    host-sync-free aggregation paths), reused so a lane computes the same
    bytes batched or not. Traceable: no host syncs anywhere."""
    import jax.numpy as jnp

    from ..ops.compiler import compile_expression
    from ..planner.plan import AggregationNode, FilterNode, ProjectNode
    from ..sql.ir import Reference
    from .executor import (
        Page,
        Relation,
        _direct_agg_domains,
        _jit_aggregate,
        _jit_direct_aggregate,
        _jit_filter,
        _jit_project,
        _needed_agg_symbols,
    )

    if isinstance(node, FilterNode):
        fn, _ = compile_expression(node.predicate, rel.layout(), rel.capacity)
        page = _jit_filter(fn, rel.env(), rel.page)
        return Relation(page, rel.symbols, rel.sorted_by)
    if isinstance(node, ProjectNode):
        layout = rel.layout()
        compiled = []
        symbols = []
        alias_of = {}
        for sym, expr in node.assignments:
            fn, out_dict = compile_expression(expr, layout, rel.capacity)
            type_ = types.get(sym) or expr.type
            compiled.append((fn, type_, out_dict))
            symbols.append(sym)
            if isinstance(expr, Reference):
                alias_of[expr.symbol] = sym
        page = _jit_project(tuple(compiled), rel.env(), rel.page)
        sorted_by = []
        for s in rel.sorted_by:
            out = alias_of.get(s)
            if out is None:
                break
            sorted_by.append(out)
        return Relation(page, tuple(symbols), tuple(sorted_by))
    if isinstance(node, AggregationNode):
        out_symbols = node.group_keys + tuple(s for s, _ in node.aggregations)
        domains = _direct_agg_domains(rel, node)
        if domains is not None:
            page = _jit_direct_aggregate(
                node.group_keys, node.aggregations, domains, rel.symbols,
                rel.page, "off",
            )
            return Relation(page, out_symbols)
        # global aggregation (no group keys): the serial path's
        # _maybe_compact is skipped here — compaction only drops masked
        # rows, whose where()-zeroed contributions are exact identities
        # for every reduction in _DIRECT_AGG_FUNCS, so the output bytes
        # match the serial program's
        needed = _needed_agg_symbols(node)
        cols = tuple(rel.column_for(s) for s in needed)
        page = _jit_aggregate(
            node.group_keys, node.aggregations, needed, 1, 0,
            Page(cols, rel.page.active), None, jnp.int32(1),
        )
        return Relation(page, out_symbols)
    raise AssertionError(f"unbatchable chain node {type(node).__name__}")


def _domains_resolvable(rel, root) -> bool:
    """The input-dependent half of batchability: grouped aggregations must
    take the direct-indexed path (small static key domains) — the sort
    path host-syncs its group count and cannot trace."""
    from .executor import _direct_agg_domains

    if not root.group_keys:
        return True
    return _direct_agg_domains(rel, root) is not None


# ----------------------------------------------------------------- work items


@dataclass
class _Lane:
    """One submitted work item: a fragment subtree execution waiting to be
    packed. ``rel`` is the evaluated input relation; the leader fills
    ``result``/``error`` (or flips ``fallback`` so the owner self-serves)."""

    key: Tuple
    rel: Any
    chain: List
    types: Dict
    # resource-group weight at submit time: the GROUP launches at its
    # highest lane's priority (queue age is the gate's own acquire time)
    priority: float
    event: threading.Event = field(default_factory=threading.Event)
    result: Any = None
    error: Optional[BaseException] = None
    fallback: bool = False


class _Group:
    """Lanes admitted under one batch key; the first submitter is the
    leader and closes admission after the window."""

    def __init__(self, key: Tuple):
        self.key = key
        self.lanes: List[_Lane] = []
        self.closed = False


class _SubsumeFlight:
    """Single-flight ticket for one whole-subtree execution: concurrent
    queries whose subtree shares the structural fingerprint AND the same
    shared-scan input pages (object identity — versioned, so equal pages
    imply equal data) are ONE computation; the winner publishes its output
    Relation and the losers' queries consume it bit-identically.

    A completed flight LINGERS for ``SUBSUME_LINGER_SECS`` (the continuous-
    batching window): same-class arrivals that drift past the winner's
    in-flight window still subsume instead of recomputing. This is exactly
    as fresh as the shared-scan linger it is anchored to — the key holds
    the input pages' identities, and a DML bumps the version under the
    scan key, so a lingered result can never be staler than the pages a
    recomputation would read."""

    def __init__(self):
        self.event = threading.Event()
        self.rel: Any = None
        self.error: Optional[BaseException] = None
        self.done = False
        self.completed_at = 0.0
        # the input pages whose id()s ride the flight key: pinned HERE so
        # a freed page's recycled address can never match a lingering key
        self.pins: Tuple = ()


@dataclass
class _ScanEntry:
    """Shared-scan single-flight ticket + short-lived published result."""

    event: threading.Event
    created: float
    # published by the winner: (page, (sym, col) assignments, sorted_by
    # COLUMN names); errors publish ``error`` instead
    page: Any = None
    assignments: Tuple = ()
    sorted_cols: Tuple = ()
    error: Optional[BaseException] = None
    done: bool = False
    # weakref to the executing PlanExecutor: a winner re-reading its OWN
    # entry (the subsume pre-pass resolves leaves, then the executor's
    # real eval fetches again) is one logical fetch, not a cross-query
    # share — suppressed by EXECUTOR identity, never by thread id (pool
    # threads are reused across queries)
    winner_ref: Any = None


class DeviceScheduler:
    """Process-wide scheduler (one chip, one instance — ``SCHEDULER``).

    Thread model: there is no daemon thread. The first submitter of a batch
    key becomes the group LEADER: it holds admission open for
    ``batch_admit_window_ms``, then stacks whatever lanes joined, takes the
    launch gate, runs ONE program, and demuxes. Joiners block on their lane
    event and fall back to self-execution if the leader dies or times out.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._pending: Dict[Tuple, _Group] = {}
        self._fn_cache: Dict[Tuple, Any] = {}
        self._scans: "OrderedDict[Tuple, _ScanEntry]" = OrderedDict()
        self._subsume: "OrderedDict[Tuple, _SubsumeFlight]" = OrderedDict()
        self._plans: "OrderedDict[Tuple, _SubsumeFlight]" = OrderedDict()
        # per-plan-node memo for the submit pre-pass (fingerprints, plan
        # profiles, leaf keys): plan flights hand concurrent queries the
        # SAME plan objects, so the wave-of-16 herd computes these once.
        # Entries hold the node itself — id() stays valid while cached.
        self._node_memo: "OrderedDict[Tuple, Tuple[Any, Any]]" = OrderedDict()
        self.gate = _LaunchGate()
        # observability for tests (metrics are the production surface)
        self.batched_launches = 0
        self.single_launches = 0
        self.scan_executions = 0
        self.scan_shares = 0
        self.subsumed = 0
        self.plans_shared = 0
        self.vector_batched_launches = 0
        self.vector_broadcast_routes = 0

    # ------------------------------------------------------------- batching

    def execute(self, binding: "BatchBinding", executor, root):
        """The executor-facing entry (PlanExecutor.eval): run the subtree
        under ``root`` through the batching plane, or return None to fall
        through to plain per-node execution.

        Two dedup tiers compose here:

        1. *Whole-subtree subsumption* — concurrent queries whose subtree
           fingerprint AND shared-scan input pages match are one
           computation (single-flight, winner fans out). This covers
           join-bearing subtrees the ragged launcher cannot trace.
        2. *Lane packing* — for traceable scan→filter→project→agg chains,
           distinct-input items sharing a program pack into one ragged
           vmapped launch.
        """
        from ..planner.plan import AggregationNode, VectorTopNNode
        from .observability import RECORDER

        # the ragged chain machinery traces aggregation-rooted subtrees;
        # sort/TopN/VectorTopN roots (and agg roots it cannot trace) still
        # get the subsumption tier — the serial winner computes anything
        batchable = isinstance(root, AggregationNode) and \
            _chain_statically_batchable(root, binding.session)
        # vector serving tier: VectorTopN items differing only in their
        # constant query vector coalesce into one stacked launch (identical
        # statements dedup via subsumption FIRST — the tiers compose)
        vector = (
            isinstance(root, VectorTopNNode)
            and executor.allow_host_sync
            and binding.vector_batching()
        )
        sub = self._subsume_enter(binding, executor, root)
        if sub is None and not batchable and not vector:
            return None
        skey = flight = None
        if sub is not None:
            skey, flight, winner = sub
            if not winner:
                ok = flight.event.wait(LANE_WAIT_SECS)
                if ok and flight.error is None and flight.rel is not None:
                    self.subsumed += 1
                    _counter("trino_tpu_subsumed_fragments_total").inc()
                    RECORDER.instant(
                        "fragment_subsumed", "batch", key=skey[0][:16]
                    )
                    return flight.rel
                # dead/failed winner: compute ourselves, holding no flight
                skey = flight = None
        try:
            rel = self._execute_item(binding, executor, root, batchable,
                                     vector)
        except BaseException as e:
            if flight is not None:
                flight.error = e
                self._subsume_exit(skey, flight)
                flight = None
            raise
        if flight is not None:
            flight.rel = rel
            self._subsume_exit(skey, flight)
        return rel

    def _subsume_enter(self, binding: "BatchBinding", executor, root):
        """-> (skey, flight, is_winner) or None when this subtree cannot
        subsume: a leaf that is not a versioned-shareable table scan, a
        nondeterministic expression (two executions may legitimately
        differ), or no fingerprint. The pre-pass resolves every leaf scan
        through shared-scan elimination — page IDENTITY is the data half
        of the key (versioned keys make equal pages imply equal data)."""
        from ..planner.plan import TableScanNode
        from .cachestore import profile_plan, session_props_key

        leaves: List = []

        def walk(n):
            if not n.sources:
                leaves.append(n)
                return
            for s in n.sources:
                walk(s)

        walk(root)
        if not leaves or not all(
            isinstance(l, TableScanNode) for l in leaves
        ):
            return None
        if any(self._scan_key(binding, l) is None for l in leaves):
            return None
        profile = self._memo("profile", root, profile_plan)
        if not profile.fingerprint or not profile.cache_safe:
            return None
        inner = executor._exec_TableScanNode
        pages = [
            self.shared_scan(binding, executor, leaf, inner).page
            for leaf in leaves
        ]
        skey = (
            profile.fingerprint, tuple(id(p) for p in pages),
            session_props_key(binding.session), binding.registry,
        )
        now = time.monotonic()
        with self._lock:
            self._sweep_locked(now)
            flight = self._subsume.get(skey)
            if flight is not None and flight.done and (
                flight.error is not None
                or now - flight.completed_at > SUBSUME_LINGER_SECS
            ):
                del self._subsume[skey]
                flight = None
            if flight is None:
                flight = self._subsume[skey] = _SubsumeFlight()
                flight.pins = tuple(pages)
                self._subsume.move_to_end(skey)
                while len(self._subsume) > SUBSUME_MAX_ENTRIES:
                    old_key, old = next(iter(self._subsume.items()))
                    if not old.done:  # never evict an in-flight winner
                        break
                    del self._subsume[old_key]
                return skey, flight, True
            self._subsume.move_to_end(skey)
            return skey, flight, False

    def _sweep_locked(self, now: float) -> None:
        """Reclaim EVERY expired done entry (device pages / pinned result
        Relations must not sit in HBM waiting for a same-key re-access
        that may never come). Called under _lock from the entry points;
        both maps are small by construction, so the walk is cheap."""
        for k in [
            k for k, e in self._scans.items()
            if e.done and (
                e.error is not None
                or now - e.created > SHARED_SCAN_TTL_SECS
            )
        ]:
            del self._scans[k]
        for k in [
            k for k, f in self._subsume.items()
            if f.done and (
                f.error is not None
                or now - f.completed_at > SUBSUME_LINGER_SECS
            )
        ]:
            del self._subsume[k]
        for k in [
            k for k, f in self._plans.items()
            if f.done and (
                f.error is not None
                or now - f.completed_at > SUBSUME_LINGER_SECS
            )
        ]:
            del self._plans[k]

    def _memo(self, tag: str, node, fn):
        """Bounded per-node-identity memo (the entry pins the node, so a
        recycled id can never serve a stale value)."""
        key = (tag, id(node))
        with self._lock:
            hit = self._node_memo.get(key)
            if hit is not None and hit[0] is node:
                self._node_memo.move_to_end(key)
                return hit[1]
        val = fn(node)
        with self._lock:
            self._node_memo[key] = (node, val)
            self._node_memo.move_to_end(key)
            while len(self._node_memo) > 512:
                self._node_memo.popitem(last=False)
        return val

    def _subsume_exit(self, skey, flight: _SubsumeFlight) -> None:
        with self._lock:
            flight.done = True
            flight.completed_at = time.monotonic()
            if flight.error is not None and self._subsume.get(skey) is flight:
                # failed flights never linger (the next arrival recomputes)
                del self._subsume[skey]
        flight.event.set()

    # ------------------------------------------------------------ plan flights

    def plan_flight(self, key: Tuple, compute):
        """Single-flight planning for identical concurrent statements: the
        wave-of-16 thundering herd parses/plans/optimizes ONCE; everyone
        else rides the winner's frozen plan (plans are immutable — the plan
        cache already serves one object to concurrent executions). Same
        continuous-batching linger as subtree subsumption; the CALLER gates
        on the plan tier's correctness rules (nondeterministic text,
        history_based_stats, open transactions)."""
        now = time.monotonic()
        with self._lock:
            flight = self._plans.get(key)
            if flight is not None and flight.done and (
                flight.error is not None
                or now - flight.completed_at > SUBSUME_LINGER_SECS
            ):
                del self._plans[key]
                flight = None
            if flight is None:
                flight = self._plans[key] = _SubsumeFlight()
                self._plans.move_to_end(key)
                while len(self._plans) > SUBSUME_MAX_ENTRIES:
                    ok, old = next(iter(self._plans.items()))
                    if not old.done:
                        break
                    del self._plans[ok]
                winner = True
            else:
                self._plans.move_to_end(key)
                winner = False
        if not winner:
            if flight.event.wait(LANE_WAIT_SECS) and flight.error is None \
                    and flight.rel is not None:
                self.plans_shared += 1
                return flight.rel
            return compute()  # dead/failed winner: plan it ourselves
        try:
            plan = compute()
        except BaseException as e:
            with self._lock:
                flight.error = e
                flight.done = True
                flight.completed_at = time.monotonic()
                if self._plans.get(key) is flight:
                    del self._plans[key]
            flight.event.set()
            raise
        with self._lock:
            flight.rel = plan
            flight.done = True
            flight.completed_at = time.monotonic()
        flight.event.set()
        return plan

    def _execute_item(self, binding: "BatchBinding", executor, root,
                      batchable: bool, vector: bool = False):
        """One work item past subsumption: the lane/group machinery for
        traceable chains, the vector serving tier for VectorTopN roots,
        plain serial execution otherwise."""
        from .observability import RECORDER

        if vector:
            return self._execute_vector_item(binding, executor, root)
        if not batchable:
            rel = executor._eval_node(root)
            # _eval_node booked the root (and children) already — tell the
            # eval() hook not to book it a second time
            executor._batch_root_booked = root
            return rel
        chain, input_node = _split_chain(root)
        # the input subtree evaluates through the OWNING executor — scans
        # get shared-scan elimination, remote sources read their staged
        # pages, and per-node stats/actuals below the chain stay exact
        rel = executor.eval(input_node)
        if not _domains_resolvable(rel, root):
            # grouped agg without small static domains: finish serially on
            # the exact serial path (aggregate_relation, host syncs and
            # all) — bit-identical by construction
            return self._run_serial_chain(executor, rel, chain, count=True)
        from .plancodec import fingerprint

        fp = self._memo("fp", root, fingerprint)
        if not fp:
            return self._run_serial_chain(executor, rel, chain, count=True)
        # NOTE: the partition scope is deliberately NOT in the batch key —
        # lanes carry their own input data, so partition p and p' of one
        # fragment (same program, different splits) are exactly the ragged
        # case that should pack. The scope DOES key shared scans below.
        key = (fp, binding.registry, _layout_sig(rel.page))
        lane = _Lane(
            key=key, rel=rel, chain=chain, types=dict(executor.types),
            priority=binding.priority(),
        )
        max_lanes = binding.max_lanes()
        with self._lock:
            g = self._pending.get(key)
            if g is not None and not g.closed and len(g.lanes) < max_lanes:
                g.lanes.append(lane)
                leader = False
            else:
                g = _Group(key)
                g.lanes.append(lane)
                self._pending[key] = g
                leader = True
        if leader:
            try:
                with RECORDER.span(
                    "batch_admit", "batch", key=key[0][:16]
                ) as sp:
                    # hold admission open so concurrent compatible items
                    # pack (pointless when the knob caps groups at one)
                    window = binding.admit_window_secs()
                    if window > 0 and max_lanes > 1:
                        time.sleep(window)
                    with self._lock:
                        g.closed = True
                        if self._pending.get(key) is g:
                            del self._pending[key]
                    sp["lanes"] = len(g.lanes)
                self._run_group(g)
            except BaseException:
                # an interrupted leader must not strand its group: close
                # it, wake every unserved lane onto the serial fallback
                with self._lock:
                    g.closed = True
                    if self._pending.get(key) is g:
                        del self._pending[key]
                for l in g.lanes:
                    if l.result is None and l.error is None:
                        l.fallback = True
                    l.event.set()
                raise
        else:
            lane.event.wait(LANE_WAIT_SECS)
        if lane.error is not None:
            raise lane.error
        if lane.result is None or lane.fallback:
            # leader died/hung or the batched launch failed: only lanes
            # that ALSO fail on their own serial run may fail
            return self._run_serial_chain(
                executor, lane.rel, lane.chain, count=True
            )
        return lane.result

    def _run_serial_chain(self, executor, rel, chain, count: bool):
        """The serial tail of a submitted item: the same per-operator
        programs _eval_node would dispatch, minus per-node bookkeeping
        (the caller books the root — the fragment-cache-hit convention)."""
        return self._serial_chain(
            rel, chain, executor.types, executor._pallas_mode(), count
        )

    @staticmethod
    def _serial_chain(rel, chain, types, pallas_mode: str, count: bool):
        from ..planner.plan import AggregationNode
        from .executor import aggregate_relation

        for node in chain:
            if isinstance(node, AggregationNode):
                rel = aggregate_relation(rel, node, types, pallas_mode)
            else:
                rel = _apply_chain_node(rel, node, types)
            if count:
                on_program_launch()
        return rel

    # ------------------------------------------------------- vector serving

    def _execute_vector_item(self, binding: "BatchBinding", executor, root):
        """Vector serving tier: one VectorTopN work item. Eligible items
        (a constant-query similarity score, or broadcast embedding-JOIN
        provenance) group under the MASKED plan fingerprint — the plan with
        the lead score's query constant blanked to NULL — plus the input
        layout signature and session key, linger for the admit window like
        the agg tier, and launch as ONE statically-unrolled device program
        (executor._jit_vector_topn_lanes) whose per-lane closures keep each
        lane's OWN query constant. Lanes are NEVER deduplicated by input
        page identity here — identical pages with different query constants
        are exactly the case being batched (identical whole statements
        already collapsed in the subsumption tier above). Ineligible shapes
        run the plain fused serial program."""
        from ..ops import tensor as T
        from .cachestore import session_props_key
        from .executor import _maybe_compact
        from .observability import RECORDER
        from .plancodec import fingerprint

        rel = executor.eval(root.source)
        if executor.allow_host_sync:
            rel = _maybe_compact(rel)
        bsyms = getattr(rel.page, "_vector_broadcast", None) or frozenset()
        fp = None
        plan = T.vector_batch_masked_node(root, bsyms)
        if plan is not None:
            masked, kind = plan
            if kind == "bcast":
                self.vector_broadcast_routes += 1
                RECORDER.instant("vector_broadcast_route", "batch")
            fp = fingerprint(masked) or None
        if fp is None:
            # not a stackable lane: the one fused serial program (the root's
            # launch books here; eval() still accounts the root normally)
            on_program_launch()
            return executor.run_vector_topn(root, rel)
        key = (
            "vec", fp, binding.registry,
            session_props_key(binding.session), _layout_sig(rel.page),
        )
        lane = _Lane(
            key=key, rel=rel, chain=[root], types=dict(executor.types),
            priority=binding.priority(),
        )
        max_lanes = binding.max_lanes()
        with self._lock:
            g = self._pending.get(key)
            if g is not None and not g.closed and len(g.lanes) < max_lanes:
                g.lanes.append(lane)
                leader = False
            else:
                g = _Group(key)
                g.lanes.append(lane)
                self._pending[key] = g
                leader = True
        if leader:
            try:
                with RECORDER.span(
                    "batch_admit", "batch", key=fp[:16]
                ) as sp:
                    window = binding.admit_window_secs()
                    if window > 0 and max_lanes > 1:
                        time.sleep(window)
                    with self._lock:
                        g.closed = True
                        if self._pending.get(key) is g:
                            del self._pending[key]
                    sp["lanes"] = len(g.lanes)
                self._run_vector_group(g)
            except BaseException:
                with self._lock:
                    g.closed = True
                    if self._pending.get(key) is g:
                        del self._pending[key]
                for l in g.lanes:
                    if l.result is None and l.error is None:
                        l.fallback = True
                    l.event.set()
                raise
        else:
            lane.event.wait(LANE_WAIT_SECS)
        if lane.error is not None:
            raise lane.error
        if lane.result is None or lane.fallback:
            # leader died/hung or the batched launch failed: per-lane fused
            # serial fallback — only a lane that ALSO fails on its own run
            # may fail, and it computes the same bytes it would have batched
            on_program_launch()
            return executor.run_vector_topn(root, lane.rel)
        return lane.result

    def _run_vector_group(self, group: _Group) -> None:
        """Leader-side vector launch: compile every lane's OWN assignments
        (each compiled closure closes over that lane's query constant — the
        trace-time-constant environment the serial program folds), run the
        statically-unrolled batched program ONCE under the launch gate, and
        demux per-lane result pages. Never raises — a failure flips the
        whole group onto the per-lane fused-serial fallback."""
        from ..ops import tensor as T
        from ..ops.compiler import compile_expression
        from .executor import Relation, _jit_vector_topn_lanes

        lanes = group.lanes
        try:
            priority = max(l.priority for l in lanes)
            _occupancy_histogram().observe(len(lanes))
            specs, envs, pages = [], [], []
            dim = 0
            for lane in lanes:
                node = lane.chain[0]
                layout = lane.rel.layout()
                compiled = []
                for sym, expr in node.assignments:
                    fn, out_dict = compile_expression(
                        expr, layout, lane.rel.capacity
                    )
                    type_ = lane.types.get(sym) or expr.type
                    compiled.append((fn, type_, out_dict))
                specs.append((
                    tuple(compiled),
                    tuple(s for s, _ in node.assignments),
                    node.orderings, node.count,
                ))
                envs.append(lane.rel.env())
                pages.append(lane.rel.page)
                info = T.assignments_vector_info(node.assignments)
                if info:
                    dim = max(dim, info[1])
            packed_rows = sum(l.rel.capacity for l in lanes)
            with T.vector_batch_launch_span(
                len(lanes), packed_rows, dim, lanes[0].chain[0].count
            ):
                self.gate.acquire(priority)
                try:
                    out = _jit_vector_topn_lanes(
                        tuple(specs), tuple(envs), tuple(pages)
                    )
                finally:
                    self.gate.release()
                self.vector_batched_launches += 1
                on_program_launch()
            T.on_vector_kernel()
            T.on_vector_batched(len(lanes))
            if len(lanes) > 1:
                _counter("trino_tpu_batched_fragments_total").inc(len(lanes))
            for lane, page in zip(lanes, out):
                node = lane.chain[0]
                lane.result = Relation(
                    page, tuple(s for s, _ in node.assignments)
                )
        except BaseException:
            for lane in lanes:
                lane.fallback = True
        finally:
            for lane in lanes:
                lane.event.set()

    def _run_group(self, group: _Group) -> None:
        """Leader-side: dedup lanes by input page identity, launch once,
        demux, wake every lane. Never raises — failures either land on the
        whole group's fallback flag (lanes self-serve serially) or on a
        single lane's error."""
        from .observability import RECORDER

        lanes = group.lanes
        try:
            unique: "OrderedDict[int, List[_Lane]]" = OrderedDict()
            for lane in lanes:
                unique.setdefault(id(lane.rel.page), []).append(lane)
            reps = [ls[0] for ls in unique.values()]
            # the group launches at its HIGHEST lane's priority: a
            # high-weight joiner must not queue at its low-weight
            # leader's rank
            priority = max(l.priority for l in lanes)
            _occupancy_histogram().observe(len(lanes))
            if len(lanes) > 1:
                _counter("trino_tpu_batched_fragments_total").inc(len(lanes))
            if len(reps) == 1:
                # one unique input (shared scans collapse identical
                # queries here): run the exact serial programs once and
                # fan the immutable result out to every lane
                rep = reps[0]
                with RECORDER.span("batch_launch", "batch") as sp:
                    self.gate.acquire(priority)
                    try:
                        result = self._launch_single(rep)
                    finally:
                        self.gate.release()
                    sp["lanes"] = len(lanes)
                    sp["unique_lanes"] = 1
                    sp["packed_rows"] = rep.rel.capacity
                    sp["key"] = group.key[0][:16]
                with RECORDER.span("batch_demux", "batch", lanes=len(lanes)):
                    for lane in lanes:
                        lane.result = result
                return
            self._launch_ragged(group, reps, unique, priority)
        except BaseException:
            for lane in lanes:
                lane.fallback = True
        finally:
            for lane in lanes:
                lane.event.set()

    def _launch_single(self, lane: _Lane):
        # batchable chains pre-check pallas to the "off" resolution, so the
        # shared serial walk is exactly the owning executor's computation
        self.single_launches += 1
        return self._serial_chain(
            lane.rel, lane.chain, lane.types, "off", count=True
        )

    def _launch_ragged(self, group, reps: List[_Lane], unique,
                       priority: float = 1.0) -> None:
        """>= 2 distinct inputs sharing a program: stack along a new lane
        dim (ragged row counts ride the active masks), ONE vmapped launch,
        slice per-lane outputs back out."""
        import jax
        import jax.numpy as jnp

        from .executor import Relation
        from .observability import RECORDER

        template = reps[0]
        pages = [self._normalize_page(l.rel.page, template.rel.page)
                 for l in reps]
        n = len(pages)
        # pad the lane dim to a power of two so the compiled batch shapes
        # stay a small set (padding lanes repeat lane 0 with a dead mask
        # and are never demuxed)
        padded = 1
        while padded < n:
            padded *= 2
        if padded > n:
            dead = jax.tree_util.tree_map(
                lambda a: jnp.zeros_like(a), pages[0]
            )
            pages = pages + [dead] * (padded - n)
        fn_key = (group.key, padded)
        with self._lock:
            fn = self._fn_cache.get(fn_key)
        if fn is None:
            chain, types = template.chain, template.types
            symbols = template.rel.symbols
            sorted_by = template.rel.sorted_by

            def lane_fn(page):
                rel = Relation(page, symbols, sorted_by)
                for node in chain:
                    rel = _apply_chain_node(rel, node, types)
                return rel.page

            from . import kernelcost

            fn = kernelcost.jit(
                jax.vmap(lane_fn), label="ragged_batch_lanes"
            )
            with self._lock:
                self._fn_cache[fn_key] = fn
                # runaway guard: distinct (key, width) programs are few by
                # construction; a blown cache means keys are unstable
                while len(self._fn_cache) > 256:
                    self._fn_cache.pop(next(iter(self._fn_cache)))
        packed_rows = sum(l.rel.capacity for l in reps)
        with RECORDER.span("batch_launch", "batch") as sp:
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *pages
            )
            self.gate.acquire(priority)
            try:
                out = fn(stacked)
            finally:
                self.gate.release()
            self.batched_launches += 1
            on_program_launch()
            sp["lanes"] = len(group.lanes)
            sp["unique_lanes"] = n
            sp["packed_rows"] = packed_rows
            sp["key"] = group.key[0][:16]
        out_symbols = self._chain_output_symbols(template)
        with RECORDER.span("batch_demux", "batch", lanes=len(group.lanes)):
            for i, lanes in enumerate(unique.values()):
                lane_page = jax.tree_util.tree_map(lambda a, i=i: a[i], out)
                rel = Relation(lane_page, out_symbols)
                for lane in lanes:
                    lane.result = rel

    @staticmethod
    def _chain_output_symbols(lane: _Lane) -> Tuple[str, ...]:
        root = lane.chain[-1]
        return tuple(root.group_keys) + tuple(
            s for s, _ in root.aggregations
        )

    @staticmethod
    def _normalize_page(page, template):
        """Re-attach the template lane's dictionary objects (equal content
        by key construction) so the stacked pytree has ONE aux treedef."""
        from ..spi.page import Column, Page

        def norm(c, t):
            return Column(
                c.type, c.data, c.valid, t.dictionary, c.lengths,
                c.elem_valid,
                tuple(norm(k, tk) for k, tk in zip(c.children, t.children)),
            )

        if page is template:
            return page
        return Page(
            tuple(norm(c, t) for c, t in zip(page.columns, template.columns)),
            page.active,
        )

    # ---------------------------------------------------------- shared scans

    def shared_scan(self, binding: "BatchBinding", executor, node, inner):
        """Single-flight overlapping-scan dedup: the first query to need a
        (table, conjuncts, columns, version, partition-scope) scan executes
        it; concurrent (and briefly subsequent) queries reuse the immutable
        device pages. Unkeyable or unversioned scans execute normally."""
        key = self._scan_key(binding, node)
        if key is None:
            self.scan_executions += 1
            _counter("trino_tpu_shared_scan_misses_total").inc()
            on_program_launch()
            return inner(node)
        now = time.monotonic()
        with self._lock:
            self._sweep_locked(now)
            entry = self._scans.get(key)
            if entry is None:
                import weakref

                entry = _ScanEntry(
                    event=threading.Event(), created=now,
                    winner_ref=(
                        weakref.ref(executor) if executor is not None
                        else None
                    ),
                )
                self._scans[key] = entry
                self._scans.move_to_end(key)
                while len(self._scans) > SHARED_SCAN_MAX_ENTRIES:
                    self._scans.popitem(last=False)
                winner = True
            else:
                self._scans.move_to_end(key)
                winner = False
        if winner:
            try:
                rel = inner(node)
                entry.page = rel.page
                entry.assignments = tuple(node.assignments)
                # sorted_by published as COLUMN names: symbol spaces differ
                # across the queries that share this scan
                sym_to_col = dict(node.assignments)
                entry.sorted_cols = tuple(
                    sym_to_col[s] for s in rel.sorted_by
                )
            except BaseException as e:
                entry.error = e
                raise
            finally:
                entry.done = True
                entry.event.set()
            self.scan_executions += 1
            _counter("trino_tpu_shared_scan_misses_total").inc()
            on_program_launch()
            return rel
        if not entry.event.wait(LANE_WAIT_SECS) or entry.error is not None:
            # hung or failed winner: self-serve (and let the entry expire)
            self.scan_executions += 1
            _counter("trino_tpu_shared_scan_misses_total").inc()
            on_program_launch()
            return inner(node)
        return self._rebind_scan(executor, node, entry)

    def _rebind_scan(self, executor, node, entry: _ScanEntry):
        """A shared page re-expressed in THIS query's symbol space."""
        from .executor import Relation
        from .observability import RECORDER

        winner = entry.winner_ref() if entry.winner_ref is not None else None
        if winner is None or winner is not executor:
            # a genuine cross-query share — the winner re-reading the entry
            # it just produced (subsume pre-pass, then the real eval) is
            # just avoiding a redundant scan, not eliminating anyone else's
            self.scan_shares += 1
            _counter("trino_tpu_shared_scan_hits_total").inc()
            RECORDER.instant(
                "shared_scan_hit", "batch",
                table=str(node.table.schema_table),
            )
        col_to_sym = {c: s for s, c in node.assignments}
        symbols = tuple(s for s, _ in node.assignments)
        sorted_by = []
        for col in entry.sorted_cols:
            sym = col_to_sym.get(col)
            if sym is None:
                break
            sorted_by.append(sym)
        return Relation(entry.page, symbols, tuple(sorted_by))

    def _scan_key(self, binding: "BatchBinding", node) -> Optional[Tuple]:
        from .cachestore import BYPASS, table_version
        from .statstore import leaf_key_for

        leaf = self._memo("leaf", node, leaf_key_for)
        if leaf is None:
            return None
        h = node.table
        # a time-travel pin (FOR VERSION) reads a snapshot the leaf key
        # knows nothing about — it MUST key separately from a current-
        # version scan of the same table/conjuncts (the result cache's
        # profile_plan extracts the same pin)
        pinned = None
        ch = h.connector_handle
        if isinstance(ch, dict) and "snapshot_id" in ch:
            pinned = str(ch["snapshot_id"])
        version = table_version(
            binding.metadata, h.catalog, h.schema_table.schema,
            h.schema_table.table, pinned,
        )
        if version is None or version == BYPASS:
            # unversioned: equal keys would not imply equal data across a
            # linger window; bypass rather than risk a stale share
            return None
        return (
            binding.registry, binding.scope, leaf, version,
            tuple(c for _, c in node.assignments),
        )

    # --------------------------------------------------------------- testing

    def reset_stats(self) -> None:
        with self._lock:
            self.batched_launches = 0
            self.single_launches = 0
            self.scan_executions = 0
            self.scan_shares = 0
            self.subsumed = 0
            self.plans_shared = 0
            self.vector_batched_launches = 0
            self.vector_broadcast_routes = 0
            self._scans.clear()
            # drop only COMPLETED lingering flights: an in-flight winner's
            # ticket must survive a concurrent stats reset
            for k in [k for k, f in self._subsume.items() if f.done]:
                del self._subsume[k]
            for k in [k for k, f in self._plans.items() if f.done]:
                del self._plans[k]


@dataclass
class BatchBinding:
    """What a PlanExecutor needs to route work through the scheduler:
    resolution context plus the partition scope (partition p of n scans
    different splits than p' of n' — lanes and shared scans must never
    alias across partitions), mirroring cachestore.FragmentBinding."""

    scheduler: DeviceScheduler
    metadata: Any
    session: Any
    scope: str = ""
    # CatalogManager.cache_nonce of the owning runner: same-named catalogs
    # in two runners may hold different data
    registry: str = ""

    def execute(self, executor, node):
        return self.scheduler.execute(self, executor, node)

    def shared_scan(self, executor, node, inner):
        return self.scheduler.shared_scan(self, executor, node, inner)

    def priority(self) -> float:
        return current_priority()

    def vector_batching(self) -> bool:
        try:
            return bool(self.session.get("vector_query_batching"))
        except KeyError:
            return False

    def max_lanes(self) -> int:
        try:
            return max(1, int(self.session.get("batch_max_lanes") or 1))
        except KeyError:
            return 8

    def admit_window_secs(self) -> float:
        try:
            return max(
                0.0, float(self.session.get("batch_admit_window_ms") or 0)
            ) / 1000.0
        except KeyError:
            return 0.002


def register_metrics() -> None:
    """Eagerly register every batching metric family with its HELP text:
    exposition (and the smoke's HELP lint) must see the families before
    the first event of each kind happens to occur — a burst that dedups
    purely by subsumption would otherwise never register the lane-packing
    counters."""
    for name in (
        "trino_tpu_device_programs_total",
        "trino_tpu_batched_fragments_total",
        "trino_tpu_subsumed_fragments_total",
        "trino_tpu_shared_scan_hits_total",
        "trino_tpu_shared_scan_misses_total",
    ):
        _counter(name)
    _occupancy_histogram()
    from ..ops.tensor import register_vector_serving_metrics

    register_vector_serving_metrics()


def attach(executor, metadata, session, catalogs=None, scope: str = "") -> None:
    """Install a BatchBinding on ``executor`` when the ``device_batching``
    knob is on (the one call every entry point makes; off = no attribute,
    byte-identical path)."""
    try:
        enabled = bool(session.get("device_batching"))
    except KeyError:
        enabled = False
    if not enabled:
        return
    register_metrics()
    executor.device_batching = BatchBinding(
        SCHEDULER, metadata, session, scope=scope,
        registry=getattr(catalogs, "cache_nonce", "") if catalogs else "",
    )


def launch_slot(enabled: bool = True):
    """Admission-gate slot for NON-batchable launches that should still
    yield between programs (the OOC unit loop): a context manager holding
    the gate at this thread's priority. ``enabled=False`` is a no-op so
    call sites stay one-liners."""
    import contextlib

    if not enabled:
        return contextlib.nullcontext()
    return SCHEDULER.gate


SCHEDULER = DeviceScheduler()
