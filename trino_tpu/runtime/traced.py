"""Traced whole-query execution: plan -> one jittable function over scan pages.

Reference blueprint: the end state of PageFunctionCompiler-style codegen taken to
its XLA conclusion — instead of operator-at-a-time programs, an entire join-free
fragment (scan -> filter -> project -> aggregate -> topn) traces into ONE fused
XLA program. This is the unit __graft_entry__ exposes. Joins need a host sync
to size their output (see executor.py), so compile_query refuses plans that
contain joins; runtime/adaptive.py and parallel/mesh_runner.py trace joins at
a static capacity with an overflow count (_TracedExecutor).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..metadata import Metadata, Session
from ..planner.plan import (
    AggregationNode,
    FilterNode,
    JoinNode,
    LimitNode,
    LogicalPlan,
    OutputNode,
    PlanNode,
    ProjectNode,
    SemiJoinNode,
    SortNode,
    TableScanNode,
    TopNNode,
    UnionNode,
    ValuesNode,
    visit_plan,
)
from ..spi.page import Page
from .executor import PlanExecutor, Relation, ExecutionError, _round_capacity

_TRACEABLE = (
    TableScanNode,
    FilterNode,
    ProjectNode,
    AggregationNode,
    SortNode,
    TopNNode,
    LimitNode,
    OutputNode,
)

# nodes that trace with a STATIC output capacity + overflow accounting (the
# caller must host-check the program's overflow scalar and retry larger)
_TRACEABLE_WITH_JOINS = _TRACEABLE + (
    JoinNode,
    SemiJoinNode,
    UnionNode,
    ValuesNode,
)


def is_traceable(
    plan: LogicalPlan, allow_joins: bool = False, extra_types: tuple = ()
) -> bool:
    ok = True
    allowed = (_TRACEABLE_WITH_JOINS if allow_joins else _TRACEABLE) + tuple(
        extra_types
    )

    def check(node: PlanNode):
        nonlocal ok
        if not isinstance(node, allowed):
            ok = False
        if isinstance(node, AggregationNode) and any(
            a.distinct for _, a in node.aggregations
        ):
            # distinct dedup host-syncs its intermediate capacity
            ok = False

    visit_plan(plan.root, check)
    return ok


class _TracedExecutor(PlanExecutor):
    """PlanExecutor with scans fed from arguments and no nested per-op jit:
    the entire eval happens inside one outer trace. Joins get a STATIC output
    capacity (probe capacity x ``join_capacity_factor``) and report overflow
    in ``self.overflows`` instead of host-syncing exact sizes — callers check
    the summed overflow after the run and retry with a larger factor. A
    grouped aggregation computes into a STATIC group capacity chosen the same
    way (``_choose_group_capacity``): here its input's, which cannot overflow;
    runtime/adaptive.py hands it a hint and counts the groups that did not
    fit."""

    allow_host_sync = False

    def __init__(
        self,
        plan,
        metadata,
        session,
        scan_pages: Dict[int, Page],
        join_capacity_factor: float = 1.0,
    ):
        super().__init__(plan, metadata, session)
        self._scan_pages = scan_pages
        self._scan_counter = 0
        self.join_capacity_factor = join_capacity_factor
        self.overflows: List[jnp.ndarray] = []

    def _choose_join_capacity(self, emit, probe_cap: int, build_cap: int, totals=None) -> int:
        cap = _round_capacity(max(int(probe_cap * self.join_capacity_factor), 1))
        self.overflows.append(
            jnp.maximum(jnp.sum(emit).astype(jnp.int64) - cap, 0)
        )
        return cap

    def _choose_group_capacity(self, node, num_groups, in_cap: int) -> int:
        """Static capacity of a grouped aggregation's output and of the
        segment temporaries it computes into. Groups never outnumber rows, so
        the input's capacity needs no overflow entry."""
        return in_cap

    def _exec_TableScanNode(self, node: TableScanNode) -> Relation:
        page = self._scan_pages[self._scan_counter]
        self._scan_counter += 1
        symbols = tuple(s for s, _ in node.assignments)
        return Relation(page, symbols)

    def _exec_AggregationNode(self, node: AggregationNode):
        # no host sync for output capacity under tracing: a static group
        # capacity (_choose_group_capacity)
        import jax.numpy as jnp

        from .executor import (
            Page,
            _jit_aggregate,
            _jit_group_sort,
            _needed_agg_symbols,
        )

        from .executor import _direct_agg_domains, _jit_direct_aggregate

        distinct = [a for _, a in node.aggregations if a.distinct]
        if distinct:
            return super()._exec_AggregationNode(node)
        rel = self.eval(node.source)
        domains = _direct_agg_domains(rel, node)
        if domains is not None:
            page = _jit_direct_aggregate.__wrapped__(
                node.group_keys, node.aggregations, domains, rel.symbols, rel.page,
                self._pallas_mode(),
            )
            return Relation(
                page, node.group_keys + tuple(s for s, _ in node.aggregations)
            )
        needed = _needed_agg_symbols(node)
        if node.group_keys:
            sorted_page, new_group, num_groups = _jit_group_sort.__wrapped__(
                node.group_keys, needed, rel.symbols, rel.page
            )
            out_cap = self._choose_group_capacity(node, num_groups, rel.capacity)
        else:
            cols = tuple(rel.column_for(s) for s in needed)
            sorted_page = Page(cols, rel.page.active)
            new_group, num_groups, out_cap = None, jnp.int32(1), 1
        # array_agg needs a host-synced lane width — unavailable under tracing
        page = _jit_aggregate.__wrapped__(
            node.group_keys,
            node.aggregations,
            needed,
            out_cap,
            0,
            sorted_page,
            new_group,
            num_groups,
        )
        return Relation(page, node.group_keys + tuple(s for s, _ in node.aggregations))


def _prepare_traced(plan: LogicalPlan, metadata: Metadata, session: Session):
    """Shared traced-compile scaffolding: gather scan pages in eval order
    (scan counter order == DFS order) and validate the root."""
    scans: List[TableScanNode] = []

    def collect(node: PlanNode):
        if isinstance(node, TableScanNode):
            scans.append(node)

    visit_plan(plan.root, collect)

    base = PlanExecutor(plan, metadata, session)
    example_pages: List[Page] = []
    for scan in scans:
        rel = base._exec_TableScanNode(scan)
        example_pages.append(rel.page)

    root = plan.root
    assert isinstance(root, OutputNode)
    return example_pages, root


def compile_query(
    plan: LogicalPlan, metadata: Metadata, session: Session
) -> Tuple[Callable[..., Page], List[Page], List[str]]:
    """Build (jittable_fn, example_scan_pages, output_column_names).

    ``jittable_fn(*scan_pages) -> Page`` runs the whole plan; scan pages are
    gathered once from the connectors as example inputs (callers may re-feed
    fresh pages of the same layout, e.g. per-split streaming).
    """
    if not is_traceable(plan):
        raise ExecutionError("plan contains nodes that require host syncs (joins)")
    example_pages, root = _prepare_traced(plan, metadata, session)

    def run(*pages: Page) -> Page:
        executor = _TracedExecutor(
            plan, metadata, session, dict(enumerate(pages))
        )
        rel = executor.eval(root.source)
        cols = [rel.column_for(s) for s in root.symbols]
        return Page(tuple(cols), rel.page.active)

    return run, example_pages, list(root.column_names)
