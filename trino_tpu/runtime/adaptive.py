"""Adaptive capacity narrowing for whole-query traced programs.

Round-3 verdict: the traced single-program tier carries FULL static
capacities through every stage — a selective query (TPC-H Q18's HAVING
keeps 57 of 1.5M groups) pays padded gathers/sorts at 6M capacity in every
downstream operator, and the operator-at-a-time tier pays per-dispatch
host syncs instead. This module closes that gap while keeping the whole
plan ONE XLA program (zero mid-plan host syncs):

- ``plan_capacities`` seeds per-node output capacities from the CBO
  estimator (planner/stats.py) — selectivity propagated into static shapes,
  the XLA analogue of the reference's DeterminePartitionCount /
  CostCalculator feeding physical planning (sql/planner/optimizations/
  DeterminePartitionCount.java:88, cost/CostCalculatorWithEstimatedExchanges).
- ``_AdaptiveTracedExecutor`` compacts relations *inside the trace* to
  those capacities (stable scatter-compaction, no sort) and records an
  (overflow, actual) pair per narrowing point.
- ``AdaptiveQuery.tune`` runs the program, host-checks only the tiny
  (overflow, actuals) vector, and recompiles with measured capacities:
  overflowed points grow to their true counts, over-provisioned points
  shrink. The fixpoint (usually 1-2 compiles, both persistent-cache-keyed)
  is a program whose every stage is shaped by ACTUAL cardinalities — the
  single-chip analogue of the reference's adaptive replanning
  (sql/planner/AdaptivePlanner.java:87), applied to shapes instead of
  exchange types.

Why capacities, not streaming: on TPU every operator is a static-shape XLA
program; the padded-capacity tax is gathers (~60ns/element on v5e) and sort
passes over dead rows. Tight capacities turn Q18's post-HAVING pipeline
from 6M-wide to 128-wide — the same effect pipelined paging has on the JVM
(operator/Driver.java:372) achieved the TPU-native way.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..metadata import Metadata, Session
from ..ops import kernels as K
from ..planner.plan import (
    AggregationNode,
    FilterNode,
    JoinNode,
    LogicalPlan,
    PlanNode,
    ProjectNode,
    TableScanNode,
    UnnestNode,
    visit_plan,
)
from ..planner.stats import StatsEstimator
from ..spi.page import Column, Page
from ..spi.types import BOOLEAN
from ..sql.ir import Reference
from . import capstore
from . import kernelcost
from .executor import (
    _DIRECT_AGG_FUNCS,
    _MASKED_REDUCE_AGGS,
    DIRECT_GROUP_LIMIT,
    ExecutionError,
    Relation,
    _permute_column,
    _round_capacity,
)
from .traced import _TracedExecutor, _prepare_traced, is_traceable

# narrowing candidates: nodes whose OUTPUT row count the CBO can estimate
# and whose output the trace can compact. Joins narrow at their capacity
# choice (no extra gather); the rest compact post-node.
_COMPACT_NODES = (TableScanNode, FilterNode, AggregationNode, UnnestNode)

# never compact below this (tiny buffers churn the jit cache for no win)
_MIN_CAP = 1024
# compaction must at least halve the capacity to pay for its gather
_MIN_SHRINK = 2


def _mask_top_valid(c: Column, keep: jnp.ndarray) -> Column:
    """AND the top-level validity with ``keep`` (rows past the compacted
    count hold clamped-gather garbage; inactive rows must not look valid)."""
    return Column(
        c.type, c.data, c.valid & keep, c.dictionary,
        lengths=c.lengths, elem_valid=c.elem_valid, children=c.children,
    )


def trace_compact(new_cap: int, page: Page) -> Tuple[Page, jnp.ndarray, jnp.ndarray]:
    """Stable in-trace compaction: active rows move to the front of a
    ``new_cap``-row page. ``K.live_indices`` + one gather of ``new_cap`` rows
    per column, with the count left on the device. (``executor._jit_compact``
    finds its rows the same way and moves its columns by ``K.gather_rows``.)

    Returns (page, overflow, true_count); rows past ``new_cap`` are dropped
    and counted in ``overflow`` (the caller retries with a larger capacity).
    """
    n = page.active.shape[0]
    total = jnp.sum(page.active.astype(jnp.int32)).astype(jnp.int64)
    idx = K.live_indices(page.active, new_cap)
    new_active = idx < n
    perm = jnp.minimum(idx, n - 1)
    cols = tuple(
        _mask_top_valid(_permute_column(c, perm), new_active) for c in page.columns
    )
    overflow = jnp.maximum(total - new_cap, 0)
    return Page(cols, new_active), overflow, total


def tight_capacity(actual: int) -> int:
    """The capacity class a point settles at once its true count is known:
    the count and a quarter more."""
    actual = int(actual)
    return _round_capacity(actual + (actual >> 2) + 16)


def settled_capacity(actual: int, unhinted: int) -> Optional[int]:
    """The hint a point keeps once its true count is known: its tight class
    where that at least halves the capacity it would run at unhinted
    (narrowing that does not halve does not pay for itself) or where the
    unhinted capacity cannot hold the count (a join that expands, a skewed
    exchange); else none."""
    tight = max(tight_capacity(actual), _MIN_CAP)
    if tight * _MIN_SHRINK <= unhinted or int(actual) > unhinted:
        return tight
    return None


def masked_readers(plan: LogicalPlan, scan_pages: Dict[int, Page]) -> frozenset:
    """Ids of the scans and filters whose rows reach, through projections
    alone, an aggregation that reads each column once under the mask: a
    global one of plain reductions, or a direct-indexed grouped one (every
    group key a column of the scan with a small static domain). Making the
    page dense first costs more than that one pass (the rule of
    ``executor.aggregate_relation``). ``scan_pages``: by scan, in plan order."""
    scans: List[PlanNode] = []
    visit_plan(
        plan.root, lambda n: scans.append(n) if isinstance(n, TableScanNode) else None
    )
    found = set()

    def direct(node: AggregationNode, chain: List[PlanNode]) -> bool:
        """``executor._direct_agg_domains``, from the scan's page."""
        if not chain or not isinstance(chain[-1], TableScanNode):
            return False
        scan = chain[-1]
        page = scan_pages.get(scans.index(scan))
        if page is None or any(
            a.function not in _DIRECT_AGG_FUNCS or a.distinct
            for _, a in node.aggregations
        ):
            return False
        total = 1
        for symbol in node.group_keys:
            for below in chain[:-1]:
                if isinstance(below, ProjectNode):
                    expr = dict(below.assignments).get(symbol)
                    if not isinstance(expr, Reference):
                        return False
                    symbol = expr.symbol
            names = [s for s, _ in scan.assignments]
            if symbol not in names:
                return False
            c = page.columns[names.index(symbol)]
            if c.dictionary is not None:
                total *= len(c.dictionary) + 1
            elif c.type == BOOLEAN:
                total *= 3
            else:
                return False
        return 1 <= total <= DIRECT_GROUP_LIMIT

    def visit(node: PlanNode):
        if not isinstance(node, AggregationNode):
            return
        chain: List[PlanNode] = []
        below = node.source
        while isinstance(below, (ProjectNode, FilterNode, TableScanNode)):
            chain.append(below)
            if isinstance(below, TableScanNode):
                break
            below = below.source
        if node.group_keys:
            reads_masked = direct(node, chain)
        else:
            reads_masked = all(
                a.function in _MASKED_REDUCE_AGGS and not a.ordering and not a.distinct
                for _, a in node.aggregations
            )
        if reads_masked:
            found.update(id(n) for n in chain if not isinstance(n, ProjectNode))

    visit_plan(plan.root, visit)
    return frozenset(found)


class _AdaptiveTracedExecutor(_TracedExecutor):
    """Traced executor with per-node capacity hints: joins allocate their
    hinted output capacity directly; a grouped aggregation computes into its
    hinted group capacity; scan/filter/agg/unnest outputs compact to their
    hint when that at least halves the buffer (not under a global
    aggregation that reads them once: ``masked_readers``). Every candidate
    point records (key, overflow, true_count) for the host-side tuner, and
    ``ran[key]`` keeps the static capacity the point ran at beside the one it
    would run at unhinted."""

    def __init__(
        self,
        plan,
        metadata,
        session,
        scan_pages: Dict[int, Page],
        capacities: Dict[int, int],
        records: List[Tuple[int, jnp.ndarray, jnp.ndarray]],
        join_capacity_factor: float = 1.0,
    ):
        super().__init__(plan, metadata, session, scan_pages, join_capacity_factor)
        self.capacities = capacities
        self.records = records
        self.ran: Dict[int, Tuple[int, int]] = {}
        self._join_key: Optional[int] = None
        self._read_masked = masked_readers(plan, scan_pages)

    def _record(self, key, overflow, actual, capacity: int, unhinted: int) -> None:
        self.records.append((key, overflow, actual))
        self.ran[key] = (capacity, unhinted)

    def eval(self, node: PlanNode) -> Relation:
        rel = super().eval(node)
        key = id(node)
        # a grouped aggregation sized by _choose_group_capacity is on record
        if isinstance(node, _COMPACT_NODES) and key not in self.ran:
            hint = self.capacities.get(key)
            cap = rel.capacity
            if (
                hint is not None
                and key not in self._read_masked
                and max(hint, _MIN_CAP) * _MIN_SHRINK <= cap
            ):
                new_cap = max(hint, _MIN_CAP)
                page, ovf, total = trace_compact(new_cap, rel.page)
                self._record(key, ovf, total, new_cap, cap)
                rel = Relation(page, rel.symbols, rel.sorted_by)
            else:
                actual = jnp.sum(rel.page.active.astype(jnp.int64))
                self._record(key, jnp.int64(0), actual, cap, cap)
        return rel

    def _choose_group_capacity(self, node, num_groups, in_cap: int) -> int:
        hint = self.capacities.get(id(node))
        cap = in_cap
        if hint is not None:
            cap = min(_round_capacity(max(hint, _MIN_CAP)), in_cap)
        actual = num_groups.astype(jnp.int64)
        self._record(id(node), jnp.maximum(actual - cap, 0), actual, cap, in_cap)
        return cap

    def _join_relations(self, node: JoinNode, left: Relation, right: Relation,
                        allow_fusion: bool = True):
        prev = self._join_key
        self._join_key = id(node)
        try:
            # allow_fusion is moot here: traced executors never host-sync,
            # so the megakernel gate (_fusion_enabled) is always off
            return super()._join_relations(node, left, right, allow_fusion)
        finally:
            self._join_key = prev

    def _choose_join_capacity(self, emit, probe_cap: int, build_cap: int, totals=None) -> int:
        key = self._join_key
        hint = self.capacities.get(key) if key is not None else None
        unhinted = _round_capacity(max(int(probe_cap * self.join_capacity_factor), 1))
        cap = unhinted if hint is None else _round_capacity(max(hint, _MIN_CAP))
        actual = jnp.sum(emit).astype(jnp.int64)
        ovf = jnp.maximum(actual - cap, 0)
        # always keyed (key is the JoinNode id, set by _join_relations for
        # every join) so the tuner can grow ANY overflowing join — an
        # unkeyed overflow could never converge
        self._record(key, ovf, actual, cap, unhinted)
        return cap


def candidate_nodes(plan: LogicalPlan, extra: tuple = ()) -> List[PlanNode]:
    """Narrowing candidates in canonical preorder — the cross-process-stable
    ordering the persisted capacity vector (runtime/capstore) is keyed by.
    ``extra``: further node types a tier sizes (the mesh tier's exchanges)."""
    nodes: List[PlanNode] = []

    def visit(node: PlanNode):
        if isinstance(node, _COMPACT_NODES + (JoinNode,) + extra):
            nodes.append(node)

    visit_plan(plan.root, visit)
    return nodes


def plan_capacities(
    plan: LogicalPlan,
    metadata: Metadata,
    margin: float = 2.0,
    estimator: Optional[StatsEstimator] = None,
) -> Dict[int, int]:
    """CBO-estimated output capacity per narrowing candidate (keyed by node
    identity — stable for the lifetime of the plan object). ``estimator``:
    one that already knows nodes this plan cannot estimate by itself (the
    mesh tier's remote sources); a shard's part of an estimate is asked for
    as a smaller ``margin``."""
    est = estimator or StatsEstimator(metadata, plan.types)
    caps: Dict[int, int] = {}

    for node in candidate_nodes(plan):
        try:
            r = est.rows(node)
        except Exception:  # estimator gaps must never kill execution
            r = None
        if r is not None and np.isfinite(r):
            caps[id(node)] = _round_capacity(int(r * margin) + 16)
    return caps


def compile_query_adaptive(
    plan: LogicalPlan,
    metadata: Metadata,
    session: Session,
    capacities: Dict[int, int],
):
    """Build (jittable_fn, example_pages, names, keys): the whole plan as one
    program returning (page, total_overflow, per-point true counts). ``keys``
    lists the node ids in the exact order the actuals vector reports them
    (captured from an abstract eval_shape trace — no compile)."""
    if not is_traceable(plan, allow_joins=True):
        raise ExecutionError("plan contains non-traceable nodes")
    example_pages, root = _prepare_traced(plan, metadata, session)
    keys_holder: List[int] = []

    def run(*pages: Page):
        records: List[Tuple[int, jnp.ndarray, jnp.ndarray]] = []
        executor = _AdaptiveTracedExecutor(
            plan, metadata, session, dict(enumerate(pages)), capacities, records
        )
        rel = executor.eval(root.source)
        cols = [rel.column_for(s) for s in root.symbols]
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
        return Page(tuple(cols), rel.page.active), overflow, actuals

    jax.eval_shape(run, *example_pages)  # abstract trace: populates keys_holder
    return run, example_pages, list(root.column_names), list(keys_holder)


class AdaptiveQuery:
    """One query's adaptive-capacity lifecycle: CBO-seeded compile, then a
    measured-capacity fixpoint. ``tune()`` is the entry point; after it
    returns, ``self.jfn``/``self.pages`` hold the tuned program."""

    def __init__(
        self,
        plan: LogicalPlan,
        metadata: Metadata,
        session: Session,
        margin: float = 2.0,
        persist: bool = True,
    ):
        self.plan = plan
        self.metadata = metadata
        self.session = session
        self.margin = margin
        self.caps = plan_capacities(plan, metadata, margin)
        self.compiles = 0
        self.attempts = 0
        self.jfn: Optional[Callable] = None
        self.pages: List[Page] = []
        self.names: List[str] = []
        self.keys: List[int] = []
        # cross-query/session tuned-capacity reuse (runtime/capstore): a hit
        # seeds the exact fixpoint vector, so tune() is one (persistently
        # XLA-cached) compile + one verification run instead of a grow/shrink
        # loop — the round-5 answer to per-instance re-tuning cost.
        self._candidates = candidate_nodes(plan)
        self._persist = persist
        self.fingerprint = capstore.plan_fingerprint(plan) if persist else ""
        self.seeded_from_store = False
        if persist:
            vec = capstore.load(self.fingerprint)
            if vec is not None and len(vec) == len(self._candidates):
                for node, cap in zip(self._candidates, vec):
                    if cap is not None:
                        self.caps[id(node)] = int(cap)
                    else:
                        self.caps.pop(id(node), None)
                self.seeded_from_store = True

    def _store_tuned(self) -> None:
        if not self._persist:
            return
        capstore.save(
            self.fingerprint,
            [self.caps.get(id(n)) for n in self._candidates],
        )

    def _compile(self):
        fn, pages, names, keys = compile_query_adaptive(
            self.plan, self.metadata, self.session, self.caps
        )
        self.jfn = kernelcost.jit(fn, label="adaptive_query")
        self.pages, self.names, self.keys = pages, names, keys
        self.compiles += 1

    def tune(self, max_attempts: int = 6) -> Tuple[Page, List[str]]:
        """Run to the capacity fixpoint. Each retry fixes the first
        overflowing point permanently (its true count is exact once its
        inputs are exact), so the loop terminates in <= #points attempts;
        in practice CBO seeds converge in 1-2."""
        self._compile()
        for attempt in range(max_attempts):
            self.attempts += 1
            page, overflow, actuals = self.jfn(*self.pages)
            ovf = int(np.asarray(overflow))
            tuned: Dict[int, int] = {}
            for key, act in zip(self.keys, np.asarray(actuals)):
                tuned[key] = tight_capacity(act)
            if ovf == 0:
                # tight already? keep; otherwise one shrink recompile
                if all(self.caps.get(k) == c for k, c in tuned.items()):
                    self._store_tuned()
                    return page, self.names
                self.caps = {**self.caps, **tuned}
                self._compile()
                page, overflow, actuals = self.jfn(*self.pages)
                if int(np.asarray(overflow)) == 0:
                    self._store_tuned()
                    return page, self.names
                # data moved under us between runs — fall through to grow
            if attempt == max_attempts - 1:
                break  # raising next; don't pay a compile that never runs
            # overflow: grow every point to at least its observed count
            # (the first overflowed point's count is exact; downstream
            # undercounts get another attempt), escalating with attempts
            grown: Dict[int, int] = {}
            for key, act in zip(self.keys, np.asarray(actuals)):
                base = _round_capacity(int(act * (1.5 + attempt)) + 16)
                grown[key] = max(base, self.caps.get(key, 0))
            self.caps = {**self.caps, **grown}
            self._compile()
        raise ExecutionError(
            f"adaptive capacity tuning did not converge in {max_attempts} attempts"
        )

    def run(self) -> Page:
        """Steady-state dispatch of the tuned program (no host-side tuning)."""
        page, _, _ = self.jfn(*self.pages)
        return page


def execute_adaptive(
    plan: LogicalPlan, metadata: Metadata, session: Session
) -> Tuple[List[str], Page]:
    """One-shot adaptive execution (names, result page)."""
    q = AdaptiveQuery(plan, metadata, session)
    page, names = q.tune()
    return names, page
