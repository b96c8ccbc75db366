"""Plan executor: evaluates optimized plans as vectorized device programs.

Reference blueprint: the worker hot path (SURVEY.md §3.2) — LocalExecutionPlanner
(LocalExecutionPlanner.java:412) turning fragments into operator pipelines, and the
operators of §2.5 (ScanFilterAndProjectOperator, HashAggregationOperator,
HashBuilder/LookupJoinOperator, TopNOperator, WindowOperator...).

TPU-first redesign: instead of Trino's page-at-a-time pull loop (Driver.java:372
moving 4KB pages between operators), each operator is a *whole-split vectorized
transform* Page -> Page with static shapes; a split is one fused XLA program's
worth of data (SURVEY.md §7: morsel = split, pad-and-mask everywhere). Pipeline
breakers (agg/join/sort) consume concatenated split pages.

Each operator evaluation is one cached jit program (the compilation caching model
of PageFunctionCompiler: cache per (plan-node structure, input layout); plan nodes
are frozen dataclasses, so they hash as static jit arguments directly). Joins are
two programs with a host sync between them to pick the static output capacity
(SURVEY.md §7 "fixed-capacity bucketed batches").
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import knobs
from ..metadata import Metadata, Session
from . import kernelcost
from .device_scheduler import on_program_launch
from .failure import FailureInjector
from .metrics import REGISTRY
from .observability import on_spill_read, on_spill_write
from .tracing import OP_PREFIX, SYNC_PREFIX, TRACER
from ..ops import kernels as K
from ..ops.compiler import CVal, ColumnLayout, CompileError, compile_expression
from ..spi.connector import Split
from ..spi.page import Column, Dictionary, Page, capacity_class
from ..spi.types import (
    BIGINT,
    BOOLEAN,
    DOUBLE,
    DecimalType,
    Type,
    is_floating,
    is_integral,
    is_string,
)
from ..sql.ir import Reference
from ..planner.plan import (
    Aggregation,
    AggregationNode,
    AggregationStep,
    EnforceSingleRowNode,
    ExchangeNode,
    FilterNode,
    JoinKind,
    JoinNode,
    LimitNode,
    LogicalPlan,
    OutputNode,
    PlanNode,
    ProjectNode,
    SemiJoinNode,
    SortNode,
    TableFunctionNode,
    TableScanNode,
    TopNNode,
    UnionNode,
    ValuesNode,
    VectorTopNNode,
    WindowNode,
)


class ExecutionError(RuntimeError):
    pass


def _null_column(c: Column, cap: int) -> Column:
    """An all-NULL column shaped like ``c`` with row capacity ``cap`` (every
    array leaf zeroed — validity masks become all-False)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((cap,) + tuple(a.shape[1:]), a.dtype), c
    )


def _permute_column(c: Column, perm) -> Column:
    """Row-gather a column by ``perm`` (nested parts ride along on axis 0)."""
    return Column(
        c.type, c.data[perm], c.valid[perm], c.dictionary,
        lengths=None if c.lengths is None else c.lengths[perm],
        elem_valid=None if c.elem_valid is None else c.elem_valid[perm],
        children=tuple(_permute_column(k, perm) for k in c.children),
    )


def _flat_arrays(cols: Sequence[Column], extra=()):
    """Which of ``cols`` are flat (no children, no lanes, no lengths) and the
    arrays ``_permute_columns`` hands ``K.gather_rows`` for them: data and
    validity of each flat column, then ``extra``."""
    flat = [not c.children and c.data.ndim == 1 and c.lengths is None for c in cols]
    return flat, [a for c, f in zip(cols, flat) if f for a in (c.data, c.valid)] + list(extra)


def _permute_columns(cols: Sequence[Column], perm, extra=()):
    """``[_permute_column(c, perm) for c in cols]`` and ``[a[perm] for a in
    extra]``, with every flat array (data, validity, the extra masks) moved by
    one ``K.gather_rows``; nested and multi-lane parts go as
    ``_permute_column`` moves them. Returns (columns, moved extra)."""
    flat, arrays = _flat_arrays(cols, extra)
    moved = K.gather_rows(arrays, perm)
    out, at = [], 0
    for c, f in zip(cols, flat):
        if f:
            out.append(Column(c.type, moved[at], moved[at + 1], c.dictionary))
            at += 2
        else:
            out.append(_permute_column(c, perm))
    return tuple(out), moved[at:]


def _slice_column(c: Column, n: int) -> Column:
    return Column(
        c.type, c.data[:n], c.valid[:n], c.dictionary,
        lengths=None if c.lengths is None else c.lengths[:n],
        elem_valid=None if c.elem_valid is None else c.elem_valid[:n],
        children=tuple(_slice_column(k, n) for k in c.children),
    )


def _cval_of(c: Column) -> CVal:
    return CVal(
        c.data, c.valid, c.dictionary, c.lengths, c.elem_valid,
        tuple(_cval_of(k) for k in c.children),
    )


def _child_dicts(c: Column) -> tuple:
    """Nested dictionary tree for ColumnLayout.child_dicts (tuple per map/row
    child, Dictionary/None per scalar/array child)."""
    return tuple(
        _child_dicts(k) if k.children else k.dictionary for k in c.children
    )


def _column_of(type_, v: CVal, fallback_dict=None) -> Column:
    """CVal -> Column, rebuilding nested children with their declared types."""
    kid_types = type_.child_types() if hasattr(type_, "child_types") else ()
    kids = tuple(_column_of(kt, kv) for kt, kv in zip(kid_types, v.children))
    return Column(
        type_, v.data, v.valid, v.dictionary or fallback_dict,
        lengths=v.lengths, elem_valid=v.elem_valid, children=kids,
    )


@dataclass
class Relation:
    """A Page plus the plan symbols its columns carry.

    ``sorted_by``: symbols the rows are ordered by (a physical data property
    propagated from connector-declared sort order through order-preserving
    operators — scan/filter/project/probe-major join/compact; ref
    sql/planner LocalProperties + spi/connector sort-order metadata). Grouped
    aggregation uses it to skip the group sort; the fast path SELF-VERIFIES
    monotonicity on device and falls back, so a wrong declaration costs one
    pass, never correctness."""

    page: Page
    symbols: Tuple[str, ...]
    sorted_by: Tuple[str, ...] = ()
    # live rows, where an operator has read the count to the host already
    # (a compaction check, a join's output size, a group count); None where
    # nobody has. For the operators' spans only: nothing executes by it.
    rows: Optional[int] = None

    def env(self) -> Dict[str, CVal]:
        return {
            s: _cval_of(c) for s, c in zip(self.symbols, self.page.columns)
        }

    def layout(self) -> Dict[str, ColumnLayout]:
        return {
            s: ColumnLayout(c.type, c.dictionary, _child_dicts(c))
            for s, c in zip(self.symbols, self.page.columns)
        }

    def column_for(self, symbol: str) -> Column:
        return self.page.columns[self.symbols.index(symbol)]

    @property
    def capacity(self) -> int:
        return self.page.capacity


def _concat_pages(pages: List[Page]) -> Page:
    """Concatenate split pages; string columns with differing dictionaries are
    re-encoded into a merged sorted dictionary (codes are only comparable
    within one dictionary); nested columns pad/recurse via _concat_cols."""
    if len(pages) == 1:
        return pages[0]
    cols = [
        _concat_cols([p.columns[i] for p in pages], pages[0].columns[i].type)
        for i in range(pages[0].num_columns)
    ]
    active = jnp.concatenate([p.active for p in pages])
    return Page(tuple(cols), active)


def _concat_scan_pages(pages: List[Page]) -> Page:
    """A scan's split pages as one page, packed where that is copies and
    nothing else (`_pack_pages`), else the plain concatenation. Rows keep
    their order within and across splits either way: the span `scan_pack`
    is there where the pages were packed."""
    if len(pages) == 1:
        return pages[0]
    packed = _pack_pages(pages)
    return _concat_pages(pages) if packed is None else packed


def _pack_pages(pages: List[Page]) -> Optional[Page]:
    """The live rows of page 0, then of page 1, and so on, at the front of a
    page of their capacity class (`capacity_class`, the connectors' own); None
    where that is not whole-page copies: a nested column, a page whose live
    rows are not a prefix of it (observed, one read for all pages: the span
    `sync:scan_pack`), or a class no smaller than the capacities together.
    A split of a generated table is padded to its table's longest split, so
    18 splits of `lineitem` at SF3 hold 18.0M rows in 37.7M of capacity, and
    every later operator would run over the padding."""
    flat = all(
        not c.children and c.lengths is None and c.elem_valid is None
        and (c.data.dtype, c.data.shape[1:]) == (c0.data.dtype, c0.data.shape[1:])
        for p in pages for c, c0 in zip(p.columns, pages[0].columns)
    )
    if not flat:
        return None
    capacity_in = sum(p.capacity for p in pages)
    stats = jnp.stack([_jit_live_prefix(p.active) for p in pages])
    with TRACER.span(SYNC_PREFIX + "scan_pack") as sync:
        stats = np.asarray(stats)
        live_rows = sync.attributes["value"] = int(stats[:, 0].sum())
    capacity_out = capacity_class(live_rows)
    prefix_live = bool((stats[:, 0] == stats[:, 1]).all())
    if not prefix_live or capacity_out >= capacity_in:
        return None
    with TRACER.span(
        "scan_pack", pages=len(pages), capacity_in=capacity_in,
        live_rows=live_rows, capacity_out=capacity_out,
    ):
        offsets = np.cumsum(stats[:, 0]) - stats[:, 0]
        # room for the last page's padding, cut off at the end
        room = capacity_out + max(p.capacity for p in pages)

        def pack(arrays):
            out = jnp.zeros((room,) + arrays[0].shape[1:], arrays[0].dtype)
            for a, at in zip(arrays, offsets):
                out = _jit_pack_page(out, a, np.int32(at))
            return out[:capacity_out]

        cols = []
        for i, c0 in enumerate(pages[0].columns):
            chunks = [p.columns[i] for p in pages]
            dictionary, datas = _unify_dictionaries(chunks)
            cols.append(Column(
                c0.type, pack(datas), pack([c.valid for c in chunks]), dictionary
            ))
        active = jnp.arange(capacity_out, dtype=jnp.int32) < live_rows
    return Page(tuple(cols), active)


@kernelcost.jit
def _jit_live_prefix(active):
    """(rows live, position after the last live row): equal when the live
    rows are a prefix of the page."""
    position = jnp.arange(1, active.shape[0] + 1, dtype=jnp.int32)
    return jnp.stack([
        jnp.sum(active.astype(jnp.int32)),
        jnp.max(jnp.where(active, position, 0)),
    ])


@partial(kernelcost.jit, donate_argnums=(0,))
def _jit_pack_page(out, page, at):
    """``page`` written whole into ``out`` from row ``at``: its padding lands
    where the next page's rows will. One program per pair of shapes."""
    return jax.lax.dynamic_update_slice_in_dim(out, page, at, axis=0)


class _KeyView:
    """column_for shim over resolved group-key source columns — the
    direct-indexed domain computation consults only the key columns'
    type/dictionary, so the fused planner can run it before the joined
    page exists."""

    def __init__(self, cols: Dict[str, Column]):
        self._cols = cols

    def column_for(self, symbol: str) -> Column:
        return self._cols[symbol]


def _sync_int(x, site: str) -> int:
    """The device-to-host read of one integer on the operator path: the host
    waits here for everything dispatched before it. One span `sync:<site>`
    under the operator that waits (runtime/tracing.py); the statement's
    `host_syncs` counts them."""
    with TRACER.span(SYNC_PREFIX + site) as span:
        value = span.attributes["value"] = int(x)
    return value


def _sync_ints(x, site: str) -> List[int]:
    """``_sync_int`` for a short vector of integers: still ONE device-to-host
    read and one `sync:<site>` span, whose ``value`` is the first of them."""
    with TRACER.span(SYNC_PREFIX + site) as span:
        values = [int(v) for v in np.asarray(x)]
        span.attributes["value"] = values[0]
    return values


def _live_rows(active, site: str) -> int:
    """Rows a page holds, read back to the host (a sync: `_sync_int`)."""
    return _sync_int(jnp.sum(active.astype(jnp.int32)), site)


JOIN_ROWS_COUNTER = "trino_tpu_join_rows_total"
JOIN_ROWS_HELP = (
    "rows a join read and wrote, by side: probe, build (live rows where the "
    "executor had counted them, else the page's capacity), out (matches emitted)"
)
JOINS_COUNTER = "trino_tpu_joins_total"
JOINS_HELP = (
    "joins executed, by kind as executed: INNER, LEFT (a RIGHT join runs as a LEFT "
    "one with its sides swapped), FULL, CROSS"
)
JOIN_EXPANSIONS_COUNTER = "trino_tpu_join_expansions_total"
JOIN_EXPANSIONS_HELP = (
    "join expansions, by form: unique (no probe row emits more than one row, the "
    "slots are the emitting rows in order) or general (a scatter over the probe rows)"
)
JOIN_RANK_FORMS_COUNTER = "trino_tpu_join_rank_forms_total"
JOIN_RANK_FORMS_HELP = (
    "joins by the way their ranks came back from the match's merged order: emitting "
    "(the probe rows that emit, sorted alone) or merged (a sort of every merged row)"
)
GROUP_ROWS_COUNTER = "trino_tpu_group_rows_total"
GROUP_ROWS_HELP = (
    "rows that entered an aggregation (live rows where counted, else the page's "
    "capacity), by path: direct, presorted, sort, global"
)

DISTINCT_COUNTER = "trino_tpu_distinct_aggregations_total"
DISTINCT_HELP = (
    "aggregations with a DISTINCT argument, each run as a dedup on the group "
    "keys and the column, then the aggregation over the dedup"
)


def _note(**attributes) -> None:
    """Attributes on the operator's span (``op:<PlanNode>``, the current one)."""
    span = TRACER.current()
    if span is not None:
        span.attributes.update(attributes)


def _rows_or_capacity(rel: "Relation") -> int:
    return rel.capacity if rel.rows is None else rel.rows


def _count_join_rows(**sides: int) -> None:
    """``trino_tpu_join_rows_total{side}`` += rows, for each side given."""
    for side, rows in sides.items():
        REGISTRY.counter(JOIN_ROWS_COUNTER, {"side": side}, help=JOIN_ROWS_HELP).inc(rows)


def _join_key_words(node, probe: "Relation", build: "Relation", key_bits) -> int:
    """32-bit words a join's key is matched in (the operands of the match's
    merge sort of n + m rows beside its one tag, the position and the row's
    class; the way back is a sort of its own), as ``K.join_match`` packs them: a narrowed
    column takes its bits, an integer column its type's width where both sides
    agree, anything else 64 (an order key); a cross join matches one word."""
    bits = 0
    for i, (probe_sym, build_sym) in enumerate(node.criteria):
        if key_bits is not None and key_bits[i] is not None:
            bits += key_bits[i]
            continue
        pk, bk = probe.column_for(probe_sym).data.dtype, build.column_for(build_sym).data.dtype
        bits += pk.itemsize * 8 if pk == bk and jnp.issubdtype(pk, jnp.signedinteger) else 64
    return max(1, -(-bits // 32))


def _type_counts(cols) -> Dict[str, int]:
    """{SQL type: how many of ``cols`` have it}, as a span carries it."""
    out: Dict[str, int] = {}
    for c in cols:
        out[c.type.display()] = out.get(c.type.display(), 0) + 1
    return out


def _sort_passes(bits: int) -> int:
    """Passes ``K.sort_perm`` holds for keys of ``bits`` bits in all: 32-bit
    words of packed keys (the operator's span carries them, ``sort_passes``)."""
    return -(-bits // 32)


def _key_bits(c: Column) -> int:
    """Width in bits of a column as a group key (``_group_key_parts`` packs a
    dictionary's codes in as many; the span's ``sort_passes`` follow from it)."""
    if c.data.ndim == 2:
        return 128
    if c.dictionary is not None:
        return max(1, (len(c.dictionary) - 1).bit_length())
    return 1 if c.data.dtype == jnp.bool_ else (
        64 if jnp.issubdtype(c.data.dtype, jnp.floating) else c.data.dtype.itemsize * 8
    )


@dataclass
class OperatorStats:
    """Per-plan-node execution stats (ref: operator/OperatorStats.java — the
    numbers EXPLAIN ANALYZE and the web UI surface, SURVEY.md §5.1).

    Time attribution (sync mode: every operator is fenced with
    block_until_ready, so the splits are exact): ``device_secs`` is the
    post-dispatch drain (exclusive — children are fenced before the parent
    dispatches), ``compile_secs`` is XLA backend-compile time attributed by
    the jax.monitoring listener (inclusive of children, like ``wall_secs``).
    Host time is DERIVED by consumers as exclusive wall - device - compile,
    not stored — one formula, no second number to drift."""

    node: PlanNode
    wall_secs: float
    output_rows: int
    output_capacity: int
    device_secs: float = 0.0
    compile_secs: float = 0.0


def resolve_actuals(
    actuals: Dict[int, dict], dyn_filters: Dict[int, tuple]
) -> Dict[int, dict]:
    """Resolve an executor's deferred per-node actuals (``actuals``,
    ``dyn_filters``) to plain ints, once the query has drained
    (statstore.observe_query's input). A function of the two dicts and not
    of the executor: the served path runs it after the statement is
    FINISHED (statstore.Feedback), holding the 4-byte counts and the
    bounded masks and neither the executor nor its pages. Counting
    runs in NUMPY on the host (np.asarray of a drained mask is free on
    the CPU backend, one small D2H elsewhere) — jnp reductions here
    would dispatch a fresh XLA program per mask and dominate the plane's
    cost (the Q6 A/B regression that numpy counting removes)."""
    import numpy as np

    out: Dict[int, dict] = {}
    for key, ent in actuals.items():
        rows = sum(int(np.asarray(c)) for c in ent["counts"])
        null_frac = None
        if ent["valids"] and rows > 0:
            nulls = cells = 0
            for active, valids in ent["valids"]:
                a = np.asarray(active)
                page_rows = int(np.count_nonzero(a))
                for v in valids:
                    nulls += int(np.count_nonzero(a & ~np.asarray(v)))
                    cells += page_rows  # THIS page's rows, not the total
            null_frac = (nulls / cells) if cells else None
        out[key] = {
            "rows": rows,
            "capacity": ent["capacity"],
            "bytes": ent["bytes"],
            "null_frac": null_frac,
        }
    # dynamic-filter hit rate resolves HERE, per executor: the synthetic
    # filter node only exists in this executor's lifetime, and pre/post
    # rows from different partitions must pair up before any summing
    # (post[last partition] / pre[all partitions] would understate the
    # selectivity by the partition count)
    for join_id, (fnode_id, probe_id) in dyn_filters.items():
        ent = out.get(join_id)
        post = out.get(fnode_id)
        pre = out.get(probe_id)
        if ent is not None and post is not None and pre is not None:
            ent["dyn_post"] = post["rows"]
            ent["dyn_pre"] = pre["rows"]
    return out


class PlanExecutor:
    """Evaluates a LogicalPlan bottom-up. One instance per query execution."""

    # False in traced subclasses: no host syncs (join sizing, dynamic filters)
    # may happen mid-plan — everything stays inside one XLA program.
    allow_host_sync = True

    def _choose_join_capacity(self, emit, probe_cap: int, build_cap: int, totals=None):
        """Join output capacity: host-sync the exact emitted row count (the
        operator-at-a-time model; traced executors override with a static
        bound + overflow accounting). ``totals`` is (rows emitted, matches
        among them, most rows one probe row emits, probe rows that emit),
        which ``_jit_join_match`` computed: the one read carries all four.
        Returns (capacity, what was read): the four, or None where nothing
        but the rows emitted was read (the fused join's ``emit``) or nothing
        at all (traced)."""
        if totals is None:
            read = None
            total = _sync_int(jnp.sum(emit), "join_capacity")
        else:
            read = _sync_ints(totals, "join_capacity")
            total = read[0]
        _note(rows_out=total)
        _count_join_rows(out=total)
        return _round_capacity(max(total, 1)), read

    def __init__(
        self,
        plan: LogicalPlan,
        metadata: Metadata,
        session: Session,
        collect_stats: bool = False,
    ):
        self.plan = plan
        self.metadata = metadata
        self.session = session
        self.types = plan.types
        self.collect_stats = collect_stats
        self.stats: Dict[int, OperatorStats] = {}  # keyed by id(node)
        # statistics feedback plane (runtime/statstore.py): per-node deferred
        # actuals. Off by default — the feedback-plane entry points (local
        # runner, fragment executors) flip it on; traced/OOC executors, whose
        # pages are tracers or per-bucket slices, keep it off.
        self.collect_actuals = False
        self.actuals: Dict[int, dict] = {}  # keyed by id(node)
        # warm-path cache plane (runtime/cachestore.py): entry points that
        # opt in set a FragmentBinding here; eval() then serves cacheable
        # scan->filter->(partial-)agg subtrees from the committed
        # materialization instead of re-executing them
        self.fragment_cache = None
        self.fragment_cache_hits = 0
        # device batching plane (runtime/device_scheduler.py): entry points
        # that opt in (device_batching knob) set a BatchBinding here; eval()
        # then submits batchable subtrees as work items that pack with
        # compatible fragments from concurrent queries into one ragged
        # launch, and leaf scans dedup through shared-scan elimination
        self.device_batching = None
        # id(node) -> provenance text ("fragment reused from query q-17")
        # rendered by EXPLAIN ANALYZE
        self.cache_provenance: Dict[int, str] = {}
        # ANN index tier (connectors/vector_index.py): id(scan node) ->
        # {"probed", "total", "nprobe"} for pruned IVF scans — read by the
        # recall sampler in run_vector_topn and by EXPLAIN ANALYZE
        self.ann_probe_stats: Dict[int, dict] = {}
        # join node -> (synthetic dynamic-filter node id, probe node id)
        self.dyn_filters: Dict[int, Tuple[int, int]] = {}
        # {id(join node): {build key symbol: (least, most) of its live values}}
        # as `_dynamic_filter_predicate` read them; `_join_key_widths` uses them
        self._join_key_bounds: Dict[int, Dict[str, Tuple[int, int]]] = {}
        self._pinned: List[PlanNode] = []  # synthetic nodes the keys above reference
        from .memory import query_memory_context

        limit = int(session.get("query_max_memory_bytes") or 0) or None
        # attaches to the active memory scope's pool (QueryManager execution:
        # blocking backpressure + killer); plain accounting otherwise
        self.memory = query_memory_context(limit)
        # operator-state spill stats (io.trino.spiller SpillMetrics analogue)
        self.spill_count = 0
        self.spilled_bytes = 0
        # megakernel plane: the launch site (server/worker.py) plants the
        # fragment's output partitioning here — (key_symbols, n_parts) — so
        # a fused root can run the repartition epilogue as its output stage
        self.repartition_hint = None
        # kernel cost plane (runtime/kernelcost.py): id(node) -> aggregated
        # XLA cost-model attribution for this query's launches. Only filled
        # in stats mode with the kernel_cost session property on (EXPLAIN
        # ANALYZE VERBOSE forces it) — otherwise the cost hook never fires
        # and the execution path is byte-identical.
        self.kernel_cost_enabled = kernelcost.session_enabled(session)
        self.kernel_costs: Dict[int, dict] = {}
        self._kc_seq = 0
        self._kc_plan_fp: Optional[str] = None

    # ------------------------------------------------------------------ entry

    def execute(self) -> Tuple[List[str], Page]:
        root = self.plan.root
        assert isinstance(root, OutputNode)
        rel = self.eval(root.source)
        cols = [rel.column_for(s) for s in root.symbols]
        return list(root.column_names), Page(tuple(cols), rel.page.active)

    # ------------------------------------------------------------------ nodes

    def eval(self, node: PlanNode) -> Relation:
        if self.fragment_cache is not None and isinstance(node, AggregationNode):
            rel = self.fragment_cache.fetch_or_execute(self, node)
            if id(node) in self.cache_provenance:
                # served from the fragment tier: children never ran — book
                # only this node's output (stats for EXPLAIN ANALYZE, memory
                # accounting, actuals for the feedback plane)
                if self.collect_stats:
                    rows = _live_rows(rel.page.active, "stats")
                    self.stats[id(node)] = OperatorStats(
                        node=node, wall_secs=0.0, output_rows=rows,
                        output_capacity=rel.capacity, device_secs=0.0,
                        compile_secs=0.0,
                    )
                if self.collect_actuals:
                    self._stash_actual(node, rel)
                self._account(node, rel)
            return rel
        if (
            self.device_batching is not None
            and isinstance(
                node, (AggregationNode, SortNode, TopNNode, VectorTopNNode)
            )
            and not self.collect_stats
        ):
            # device batching plane: submit the subtree as a work item;
            # None = not batchable here, fall through to plain execution.
            # Like a fragment-cache hit, only the subtree ROOT is booked
            # (intermediate chain nodes ran inside the packed launch) —
            # unless the scheduler ran the subtree through _eval_node
            # itself (subsumption winner), which booked everything.
            rel = self.device_batching.execute(self, node)
            if rel is not None:
                if getattr(self, "_batch_root_booked", None) is node:
                    self._batch_root_booked = None
                    return rel
                if self.collect_actuals:
                    self._stash_actual(node, rel)
                self._account(node, rel)
                return rel
        return self._eval_node(node)

    def _eval_node(self, node: PlanNode) -> Relation:
        # one span per operator, under `execution` or the operator that
        # consumes it: its `launches`, `sync:` and `compact` children land
        # on it; the flight recorder keeps it under the category `operator`
        with TRACER.span(OP_PREFIX + type(node).__name__, cat="operator"):
            return self._eval_node_spanned(node)

    def _eval_node_spanned(self, node: PlanNode) -> Relation:
        method = getattr(self, "_exec_" + type(node).__name__, None)
        if method is None:
            raise ExecutionError(f"no executor for {type(node).__name__}")
        if self.device_batching is not None and isinstance(node, TableScanNode):
            # shared-scan elimination: overlapping leaf scans of concurrent
            # queries subsume into one execution (stats/actuals/chaos for
            # this node still book normally around the wrapped method)
            inner = method
            method = (
                lambda n, _inner=inner:
                self.device_batching.shared_scan(self, n, _inner)
            )
        if self.allow_host_sync and not (
            self.device_batching is not None
            and isinstance(node, TableScanNode)
        ):
            # device-program launch accounting at the operator boundary
            # (the batching A/B metric; a packed ragged launch books once
            # inside the scheduler instead). Traced executors
            # (allow_host_sync=False) run inside ONE fused program — their
            # per-node walk is a trace, not a launch. Scans under the
            # batching plane book inside shared_scan: a scan SERVED from a
            # concurrent overlapping scan uploads nothing and launches
            # nothing.
            on_program_launch()
        injector = FailureInjector.current()
        if injector is not None:
            injector.maybe_fail(type(node).__name__)
        if not self.collect_stats:
            # kernel_cost session property: attribute on the regular path
            # too (no fences, so no measured device_secs — ledger rows
            # carry classification but not pct-of-roofline). With the
            # property off this is a nullcontext: byte-identical execution.
            with self._kernel_cost_scope(node):
                rel = method(node)
            if self.collect_actuals:
                self._stash_actual(node, rel)
            self._account(node, rel)
            return rel
        import time as _time

        from .observability import compile_window

        t0 = _time.perf_counter()
        with compile_window() as cw:
            with self._kernel_cost_scope(node):
                rel = method(node)
        t1 = _time.perf_counter()
        # sync fence: exact device/host attribution needs the drain
        # isolated from the next dispatch (the opt-in cost of stats mode)
        jax.block_until_ready(rel.page.active)
        t2 = _time.perf_counter()
        rows = _live_rows(rel.page.active, "stats")
        self.stats[id(node)] = OperatorStats(
            node=node,
            wall_secs=t2 - t0,
            output_rows=rows,
            output_capacity=rel.capacity,
            device_secs=t2 - t1,
            compile_secs=cw.seconds,
        )
        if self.collect_actuals:
            self._stash_actual(node, rel)
        self._account(node, rel)
        return rel

    def _kernel_cost_scope(self, node: PlanNode):
        """Recording scope for the kernel cost plane: every jitted program
        launched while this node's method runs attributes its XLA cost
        analysis to this node (scopes nest with evaluation, innermost wins,
        so a child evaluated mid-method books to the child)."""
        import contextlib

        if not self.kernel_cost_enabled:
            return contextlib.nullcontext()
        from . import capstore, statstore
        from .observability import current_collector

        if self._kc_plan_fp is None:
            try:
                self._kc_plan_fp = capstore.plan_fingerprint(self.plan)
            except Exception:  # noqa: BLE001 — keying only, never fail eval
                self._kc_plan_fp = "plan"
        self._kc_seq += 1
        kind = type(node).__name__
        # cross-process-stable node key: stats-mode evaluation order is
        # deterministic for a given plan, so the sequence number
        # disambiguates same-kind siblings without a preorder walk
        node_key = f"{self._kc_plan_fp}:{self._kc_seq}:{kind}"
        agg = self.kernel_costs.setdefault(
            id(node),
            {"flops": 0.0, "bytes_accessed": 0.0, "peak_hbm_bytes": 0,
             "programs": 0, "unavailable": 0},
        )
        collector = current_collector()

        def sink(record: dict) -> None:
            agg["programs"] += 1
            if record.get("status") == "ok":
                agg["flops"] += float(record.get("flops") or 0.0)
                agg["bytes_accessed"] += float(
                    record.get("bytes_accessed") or 0.0
                )
                if record.get("peak_hbm_bytes"):
                    # programs launch serially within one operator: the
                    # node watermark is the largest single launch
                    agg["peak_hbm_bytes"] = max(
                        agg["peak_hbm_bytes"], int(record["peak_hbm_bytes"])
                    )
            else:
                agg["unavailable"] += 1
            if collector is not None:
                collector.add_kernel_cost(kind, record)

        return kernelcost.attributing(
            node_key, kind, sink,
            query_id=statstore.current_query_id() or "",
        )

    # ------------------------------------------------ cardinality actuals

    # valid-mask retention bound for NULL-fraction sampling: beyond this
    # capacity the masks would pin real device memory until query end, so
    # null_frac degrades to None instead (the row COUNT is a pinned 4-byte
    # device scalar either way — large pages never pin their masks)
    _NULL_FRAC_CAP = 1 << 20

    def _stash_actual(self, node: PlanNode, rel: Relation) -> None:
        """Defer this node's actual row count: dispatch ONE tiny async
        reduction per operator page and pin only its 4-byte device scalar —
        pinning the mask itself would hold a byte per row of every
        intermediate until query end. Scans/filters (the nodes selectivity
        estimation is calibrated on) additionally keep their column valid
        masks for NULL fractions, bounded by _NULL_FRAC_CAP. Host syncs
        happen ONCE in finalize_actuals after the result has drained."""
        ent = self.actuals.get(id(node))
        if ent is None:
            ent = self.actuals[id(node)] = {
                "counts": [], "valids": [], "capacity": 0, "bytes": 0,
            }
        ent["counts"].append(jnp.sum(rel.page.active, dtype=jnp.int32))
        ent["capacity"] += rel.capacity
        if (
            isinstance(node, (TableScanNode, FilterNode))
            and rel.page.columns
            and rel.capacity <= self._NULL_FRAC_CAP
        ):
            ent["valids"].append(
                (rel.page.active, tuple(c.valid for c in rel.page.columns))
            )

    def finalize_actuals(self) -> Dict[int, dict]:
        """This executor's actuals resolved (:func:`resolve_actuals`)."""
        return resolve_actuals(self.actuals, self.dyn_filters)

    def _account(self, node: PlanNode, rel: Relation) -> None:
        """Memory accounting per operator output (lib/trino-memory-context)."""
        from .memory import page_bytes

        nbytes = page_bytes(rel.page)
        ctx = self.memory.new_local(type(node).__name__)
        ctx.set_bytes(nbytes)
        if self.collect_actuals:
            ent = self.actuals.get(id(node))
            if ent is not None:
                ent["bytes"] += nbytes

    def _exec_TableScanNode(self, node: TableScanNode) -> Relation:
        connector = self.metadata.connector_for(node.table)
        handle = node.table
        if node.constraint.domains:
            absorbed = self.metadata.apply_filter(handle, node.constraint)
            if absorbed is not None:
                handle = absorbed
        splits = connector.split_manager().get_splits(handle)
        ch = handle.connector_handle
        if isinstance(ch, dict) and "ann_probe" in ch and splits:
            # ANN centroid pre-pass pruned the IVF cluster splits — surface
            # it like partition pruning (EXPLAIN ANALYZE + recall sampler)
            info = splits[0].info if isinstance(splits[0].info, dict) else {}
            probed = len(splits)
            total = int(info.get("total_clusters", probed))
            nprobe = int(ch["ann_probe"].get("nprobe", probed))
            self.ann_probe_stats[id(node)] = {
                "probed": probed, "total": total, "nprobe": nprobe,
            }
            self.cache_provenance[id(node)] = (
                f"ann: probed {probed}/{total} clusters (nprobe={nprobe})"
            )
        symbols = tuple(s for s, _ in node.assignments)
        meta = self.metadata.get_table_metadata(node.table)
        col_indexes = [meta.column_index(c) for _, c in node.assignments]
        if not splits:
            # all splits pruned: 1-row page with nothing active (zero-capacity
            # arrays break .at[0] initializers in downstream kernels).
            # empty_page_for keeps multi-lane storage (vectors, long
            # decimals) and the string dictionary sentinel layout-correct.
            from ..spi.host_pages import empty_page_for

            page = empty_page_for(symbols, {s: self.types[s] for s in symbols})
            return Relation(page, symbols)
        provider = connector.page_source_provider()
        counts = None  # per-page active rows, only when something computed it
        if node.limit is not None and len(splits) > 1:
            # stop-early scan (PushLimitIntoTableScan): read splits until the
            # row target is covered; the LimitNode above enforces exactness
            pages = []
            counts = []
            rows = 0
            for sp in splits:
                p = provider.create_page_source(sp, col_indexes)
                pages.append(p)
                counts.append(_live_rows(p.active, "scan_limit"))
                rows += counts[-1]
                if rows >= node.limit:
                    break
            splits = splits[: len(pages)]
        else:
            pages = _load_splits(provider, splits, col_indexes, self.session)
        # split boundary: SplitCompletedEvent dispatch (spi/eventlistener) —
        # one thread-local read when no listener asked for split events; the
        # limit branch's counts are reused (no second device sync per split)
        from .events import split_event_sink

        sink = split_event_sink()
        if sink is not None:
            if counts is None:
                counts = [_live_rows(p.active, "split_event") for p in pages]
            for sp, p, n in zip(splits, pages, counts):
                sink({
                    "catalog": handle.catalog,
                    "table": str(handle.schema_table),
                    "splitId": sp.split_id,
                    "totalSplits": sp.total_splits,
                    "rows": n,
                })
        # connector-declared sort order -> symbol space (splits are generated
        # over ascending key ranges, so the concat preserves it)
        col_to_sym = {c: s for s, c in node.assignments}
        sorted_by = []
        for col in getattr(meta, "sorted_by", ()):
            sym = col_to_sym.get(col)
            if sym is None:
                break
            sorted_by.append(sym)
        return Relation(_concat_scan_pages(pages), symbols, tuple(sorted_by))

    def _exec_FilterNode(self, node: FilterNode) -> Relation:
        rel = self.eval(node.source)
        fn, _ = compile_expression(node.predicate, rel.layout(), rel.capacity)
        page = _jit_filter(fn, rel.env(), rel.page)
        # masking never reorders rows
        return Relation(page, rel.symbols, rel.sorted_by)

    def _exec_ProjectNode(self, node: ProjectNode) -> Relation:
        rel = self.eval(node.source)
        return self._project_relation(node, rel)

    def _compile_assignments(self, assignments, rel: Relation):
        """Compile a projection's (symbol, expr) assignments against an
        evaluated relation — ONE implementation shared by the project walk
        and the fused top-k node, so the fused path's 'same compiled
        closures as the serial pair' bit-identity guarantee is structural."""
        layout = rel.layout()
        compiled = []
        for sym, expr in assignments:
            fn, out_dict = compile_expression(expr, layout, rel.capacity)
            type_ = self.types.get(sym) or expr.type
            compiled.append((fn, type_, out_dict))
        return tuple(compiled)

    def _project_relation(self, node: ProjectNode, rel: Relation) -> Relation:
        """Project an already-evaluated relation (shared by the standard walk
        and the megakernel plane's serial-finish fallback, which must not
        re-evaluate the project's source subtree)."""
        compiled = self._compile_assignments(node.assignments, rel)
        symbols = []
        alias_of = {}  # output symbol -> input symbol (identity projections)
        for sym, expr in node.assignments:
            symbols.append(sym)
            if isinstance(expr, Reference):
                alias_of[expr.symbol] = sym
        from ..ops import tensor as _tensor

        vinfo = _tensor.assignments_vector_info(node.assignments)
        if vinfo is None:
            page = _jit_project(tuple(compiled), rel.env(), rel.page)
        else:
            # a similarity/model projection: one MXU-shaped launch — book it
            # on the tensor plane's counter with the paired kernel span
            with _tensor.vector_kernel_span(rel.capacity, vinfo[1]):
                page = _jit_project(tuple(compiled), rel.env(), rel.page)
            _tensor.on_vector_kernel()
        sorted_by = []
        for s in rel.sorted_by:
            out = alias_of.get(s)
            if out is None:
                break
            sorted_by.append(out)
        payload = rel.page.__dict__.get("_megakernel_epilogue")
        if payload and payload.get("keys"):
            # a fused source computed the exchange dest in-kernel; a
            # projection is row-preserving (active rides through unchanged),
            # so the dest stays valid as long as every partition key passes
            # through as an identity reference — carry it to the new page
            # under the aliased names
            renamed = tuple(alias_of.get(k) for k in payload["keys"])
            if all(r is not None for r in renamed):
                from ..ops.megakernels import attach_epilogue

                attach_epilogue(
                    page, payload["dest"],
                    tuple(symbols.index(r) for r in renamed),
                    payload["n_parts"], keys=renamed,
                )
        return Relation(page, tuple(symbols), tuple(sorted_by))

    def _exec_UnnestNode(self, node) -> Relation:
        """UNNEST: flatten [cap, W] element lanes to a [cap*W] row grid (ref
        operator/unnest/UnnestOperator.java — its per-position appendRange loop
        becomes one static reshape; rows past each array's length stay
        inactive)."""
        from ..spi.types import ArrayType as _At

        rel = self.eval(node.source)
        unnest_cols = [rel.column_for(s) for s, _ in node.unnest_symbols]
        w = 1
        for c in unnest_cols:
            arr = c if isinstance(c.type, _At) else c.children[0]
            w = max(w, int(arr.data.shape[1]) if arr.data.ndim > 1 else 1)
        page = _jit_unnest(
            tuple(rel.symbols.index(s) for s in node.replicate_symbols),
            tuple(rel.symbols.index(s) for s, _ in node.unnest_symbols),
            w,
            node.ordinality_symbol is not None,
            rel.page,
        )
        return Relation(page, tuple(node.output_symbols))

    # ------------------------------------------------------------ aggregation

    def _exec_AggregationNode(self, node: AggregationNode) -> Relation:
        distinct_aggs = [a for _, a in node.aggregations if a.distinct]
        if distinct_aggs:
            return self._exec_distinct_aggregation(node)
        fused = self._try_fused_join_aggregate(node)
        if fused is not None:
            return fused
        rel = self.eval(node.source)
        thresh = self._spill_threshold()
        if thresh and self.allow_host_sync and node.group_keys:
            from .memory import page_bytes

            total = page_bytes(rel.page)
            if total > thresh:
                return self._spill_partitioned_aggregate(rel, node, total, thresh)
        return aggregate_relation(rel, node, self.types, self._pallas_mode())

    def _pallas_mode(self) -> str:
        """Resolve the pallas_aggregation session property to a static mode:
        'tpu' (compiled kernels), 'interpret' (pl.pallas_call interpret mode —
        the CPU test hook), or 'off'. THE policy (why AUTO keeps the XLA
        formulation, with the v5e measurements) lives in the central knob
        registry: knobs.resolve_pallas_aggregation."""
        try:
            mode = self.session.get("pallas_aggregation")
        except KeyError:
            mode = "auto"
        return knobs.resolve_pallas_aggregation(mode)

    # ------------------------------------------------- megakernel plane

    def _fusion_enabled(self) -> bool:
        """pallas_fusion session gate. Off (the default) keeps the execution
        path byte-identical to the serial op-chain (the device_batching
        contract). Stats mode stays serial so EXPLAIN ANALYZE attributes
        per-operator time; traced executors (allow_host_sync=False) run one
        fused XLA program already and host-sync nothing mid-plan."""
        if not self.allow_host_sync or self.collect_stats:
            return False
        try:
            return bool(self.session.get("pallas_fusion"))
        except KeyError:
            return False

    def _fusion_interpret(self) -> bool:
        try:
            mode = self.session.get("pallas_interpret")
        except KeyError:
            mode = "auto"
        return knobs.resolve_pallas_interpret(mode, jax.default_backend())

    def _epilogue_spec_for(self, symbols: Tuple[str, ...]):
        """(key_idx, n_parts) when this fragment's output feeds a hash
        exchange whose keys the produced symbols cover (the launch site —
        server/worker.py — plants ``repartition_hint`` before execution), so
        the megakernel computes the exchange destination as its output stage
        and ops/repartition skips the standalone hash program."""
        hint = getattr(self, "repartition_hint", None)
        if not hint:
            return None
        keys, n_parts = hint
        if not keys or n_parts <= 1:
            return None
        if not all(k in symbols for k in keys):
            return None
        return tuple(symbols.index(k) for k in keys), int(n_parts)

    def _fused_join_spec(self, kind, node: JoinNode, probe, build,
                         pkeys, bkeys):
        """Shared shape gate: compiler recognition + physical key check.
        Returns the MegakernelSpec or None (fallback ticked)."""
        from ..ops import megakernels as MK
        from ..ops.compiler import megakernel_key_check, plan_megakernel

        spec, reason = plan_megakernel(
            kind, node.criteria, node.filter is not None,
            probe.page, build.page,
        )
        if spec is None:
            MK.on_pallas_fallback(reason)
            return None
        for cols in (pkeys, bkeys):
            ok, reason = megakernel_key_check(cols)
            if not ok:
                MK.on_pallas_fallback(reason)
                return None
        return spec

    def _try_fused_join(
        self, kind, node: JoinNode, probe: Relation, build: Relation,
        pkeys, bkeys, luts,
    ) -> Optional[Relation]:
        """Attempt the fused hash-join megakernel for an already-normalized
        (RIGHT-swapped) join: ops/compiler.plan_megakernel recognizes the
        shape, ops/megakernels runs build+probe+expand (+ the repartition
        dest) as Pallas launches. Returns the fused Relation, or None after
        a labeled fallback tick — the caller runs the serial op-chain. A
        compiled (non-interpret) launch that fails raises instead."""
        from ..ops import megakernels as MK

        spec = self._fused_join_spec(kind, node, probe, build, pkeys, bkeys)
        if spec is None:
            return None
        interp = self._fusion_interpret()
        out_symbols = probe.symbols + build.symbols
        try:
            pr = MK.probe_phase(
                pkeys, bkeys, luts, probe.page.active, build.page.active,
                spec.left_outer, interp,
            )
            if pr is None:
                return None  # bucket skew; fallback already ticked
            out_capacity, _ = self._choose_join_capacity(
                pr["emit"], probe.capacity, build.capacity
            )
            epi_spec = self._epilogue_spec_for(out_symbols)
            page, dest = MK.expand_phase(
                pr, pkeys, bkeys, luts, probe.page, build.page,
                out_capacity, out_symbols, None, None, epi_spec, interp,
            )
        except Exception:
            if not interp:
                # a compiled launch that Mosaic refuses is the query's
                # error, with the compiler's message: a kernel must not
                # quietly give way to its reference on the chip
                raise
            MK.on_pallas_fallback("kernel_error")
            return None
        if dest is not None:
            MK.attach_epilogue(
                page, dest, epi_spec[0], epi_spec[1],
                keys=(self.repartition_hint or ((),))[0],
            )
        # probe-major expansion preserves the probe side's order (the serial
        # join's out_sorted rule for non-FULL kinds)
        return Relation(page, out_symbols, probe.sorted_by)

    def _try_fused_join_aggregate(self, node: AggregationNode) -> Optional[Relation]:
        """join -> [project] -> partial-agg fusion: when a (non-distinct,
        grouped) aggregation sits on a fused-eligible join — possibly with
        one elementwise ProjectNode in between (the shape the optimizer
        emits for every sum(expr)-over-join fragment) — build, probe,
        expansion, the projected expressions, and the group stage all run
        inside megakernel launches; the join output never materializes
        between operators, and the whole fragment books ONE device program
        where the serial walk books two or three.

        Group strategy mirrors aggregate_relation exactly: direct-indexed
        (small static dictionary/boolean domains) runs entirely inside the
        expand kernel; every other shape takes the sort path — group-sort +
        boundary detection inside the expand kernel, one host sync for the
        group count (the sync the serial path performs too), then the
        reduction stage as the aggregate kernel. Returns the aggregated
        Relation, or None for the standard walk."""
        if not self._fusion_enabled():
            return None
        proj = None
        src = node.source
        if isinstance(src, ProjectNode) and isinstance(src.source, JoinNode):
            proj, src = src, src.source
        if not isinstance(src, JoinNode) or not node.group_keys:
            return None
        if self._spill_threshold():
            return None  # the spill paths host-sync sizes — serial only
        if self._pallas_mode() != "off":
            return None  # the limb kernels cannot nest inside the fused kernel
        if any(
            a.distinct or a.ordering or a.function in _LANE_AGGS
            for _, a in node.aggregations
        ):
            # lane-valued aggregates host-sync their static lane width;
            # aggregate ORDER BY pre-sorts the whole relation — serial only
            return None
        from ..ops import megakernels as MK

        pre = self._join_inputs(src)
        if isinstance(pre, Relation):
            # the operator-state spill path ran the whole join (it cannot
            # trigger with spill_operator_threshold_bytes unset, but stay
            # safe against future gates): finish serially
            return self._serial_agg_finish(node, proj, pre)
        left, right = pre
        kind, src_n, probe, build, pkeys, bkeys, luts = self._join_sides(
            src, left, right
        )

        def serial_finish() -> Relation:
            # ONE spelling of the fallback: serial join (fusion already
            # declined — don't re-attempt), booked like _eval_node would
            return self._serial_agg_finish(
                node, proj,
                self._join_relations(src, left, right, allow_fusion=False),
                book_join=True,
            )

        spec = self._fused_join_spec(kind, src_n, probe, build, pkeys, bkeys)
        if spec is None:
            return serial_finish()
        base_symbols = probe.symbols + build.symbols
        view = Relation(
            Page(
                tuple(probe.page.columns) + tuple(build.page.columns),
                probe.page.active,
            ),
            base_symbols,
            probe.sorted_by,
        )
        interp = self._fusion_interpret()
        try:
            pr = MK.probe_phase(
                pkeys, bkeys, luts, probe.page.active, build.page.active,
                spec.left_outer, interp,
            )
            if pr is None:
                return serial_finish()
            out_capacity, _ = self._choose_join_capacity(
                pr["emit"], probe.capacity, build.capacity
            )
            # fold the intermediate projection into the kernel: the same
            # compiled expression closures the serial _project_impl runs
            # (compile_expression caches on (expr, layout, capacity), so the
            # jit static key is stable across executions)
            proj_spec = None
            post_symbols = base_symbols
            post_sorted = view.sorted_by
            key_sources: Dict[str, Column] = {}
            if proj is not None:
                layout = view.layout()
                compiled = []
                symbols = []
                alias_of = {}
                for sym, expr in proj.assignments:
                    fn, out_dict = compile_expression(expr, layout, out_capacity)
                    type_ = self.types.get(sym) or expr.type
                    compiled.append((fn, type_, out_dict))
                    symbols.append(sym)
                    if isinstance(expr, Reference):
                        alias_of[expr.symbol] = sym
                        key_sources[sym] = view.column_for(expr.symbol)
                proj_spec = (tuple(compiled), tuple(symbols))
                post_symbols = tuple(symbols)
                post_sorted = []
                for s in view.sorted_by:
                    out = alias_of.get(s)
                    if out is None:
                        break
                    post_sorted.append(out)
                post_sorted = tuple(post_sorted)
            else:
                key_sources = {s: view.column_for(s) for s in node.group_keys
                               if s in base_symbols}
            agg_symbols = node.group_keys + tuple(s for s, _ in node.aggregations)
            epi_spec = self._epilogue_spec_for(agg_symbols)
            domains = None
            if all(k in key_sources for k in node.group_keys) and not any(
                a.function not in _DIRECT_AGG_FUNCS for _, a in node.aggregations
            ):
                domains = _direct_agg_domains(_KeyView(key_sources), node)
            if domains is not None:
                agg_spec = ("direct", (
                    tuple(node.group_keys), tuple(node.aggregations),
                    tuple(domains), tuple(post_symbols),
                ))
                page, dest = MK.expand_phase(
                    pr, pkeys, bkeys, luts, probe.page, build.page,
                    out_capacity, base_symbols, proj_spec, agg_spec,
                    epi_spec, interp,
                )
            else:
                needed = _needed_agg_symbols(node)
                presorted = bool(post_sorted) and (
                    post_sorted[0] == node.group_keys[0]
                )
                if presorted and any(
                    a.function in _RESORT_AGGS for _, a in node.aggregations
                ):
                    # serial would _force_dense here — a no-op for joined
                    # pages (the expansion emits a dense active prefix),
                    # so the presorted grouping is safe to take as-is
                    pass
                mode = "presorted" if presorted else "sort"
                agg_spec = (mode, (
                    tuple(node.group_keys), tuple(needed), tuple(post_symbols),
                ))
                if presorted:
                    # the serial presorted fast path, fused: the expand
                    # kernel verifies sortedness in-program; a violation
                    # re-groups through one extra kernel — the exact
                    # decision (and cost) of the serial path
                    joined, p, ng, n_grp, viol = MK.expand_phase(
                        pr, pkeys, bkeys, luts, probe.page, build.page,
                        out_capacity, base_symbols, proj_spec, agg_spec,
                        None, interp,
                    )
                    if bool(viol):
                        sorted_page, new_group, num_groups = MK.group_sort_phase(
                            tuple(node.group_keys), tuple(needed),
                            tuple(post_symbols), joined, interp,
                        )
                    else:
                        sorted_page, new_group, num_groups = p, ng, n_grp
                else:
                    sorted_page, new_group, num_groups = MK.expand_phase(
                        pr, pkeys, bkeys, luts, probe.page, build.page,
                        out_capacity, base_symbols, proj_spec, agg_spec,
                        None, interp,
                    )
                # the group-count host sync the serial sort path performs
                out_cap = min(
                    _round_capacity(max(_sync_int(num_groups, "num_groups"), 1), base=16),
                    max(out_capacity, 16),
                )
                page, dest = MK.aggregate_phase(
                    tuple(node.group_keys), tuple(node.aggregations),
                    tuple(needed), out_cap, sorted_page, new_group,
                    num_groups, epi_spec, interp,
                )
        except Exception:
            if not interp:
                raise  # as in _try_fused_join
            MK.on_pallas_fallback("kernel_error")
            return serial_finish()
        if dest is not None:
            MK.attach_epilogue(
                page, dest, epi_spec[0], epi_spec[1],
                keys=(self.repartition_hint or ((),))[0],
            )
        return Relation(page, agg_symbols)

    def _serial_agg_finish(self, node: AggregationNode, proj,
                           join_rel: Relation, book_join: bool = False) -> Relation:
        """Finish an attempted fused join+agg fragment on the serial path
        WITHOUT re-evaluating the join inputs, booking the intermediate
        nodes the way _eval_node would have."""
        if book_join:
            on_program_launch()
            if self.collect_actuals:
                self._stash_actual(node.source if proj is None else proj.source,
                                   join_rel)
            self._account(node.source if proj is None else proj.source, join_rel)
        rel = join_rel
        if proj is not None:
            on_program_launch()
            rel = self._project_relation(proj, rel)
            if self.collect_actuals:
                self._stash_actual(proj, rel)
            self._account(proj, rel)
        return aggregate_relation(rel, node, self.types, self._pallas_mode())

    def _exec_distinct_aggregation(self, node: AggregationNode) -> Relation:
        """x(DISTINCT col): dedup on (group keys, col) first, then aggregate.
        (Trino: MarkDistinct + masked accumulators; same two-phase idea.)
        A mix of DISTINCT and plain aggregates evaluates as two aggregations
        over the same input — both paths group by the same keys through the
        same machinery, so their group rows align 1:1 (asserted) and the
        outputs merge columnwise (the MarkDistinct-masked-accumulator effect
        without per-aggregate masks)."""
        distinct_cols = {a.args[0] for _, a in node.aggregations if a.distinct}
        if len(distinct_cols) > 1:
            raise ExecutionError(
                "multiple DISTINCT aggregates over different columns not supported yet"
            )
        rel = self.eval(node.source)
        dcol = next(iter(distinct_cols))
        dedup_node = AggregationNode(
            source=node.source,
            group_keys=tuple(node.group_keys) + (dcol,),
            aggregations=(),
            step=AggregationStep.SINGLE,
        )
        deduped = aggregate_relation(rel, dedup_node, self.types, self._pallas_mode())
        # the dedup's own numbers, before the aggregations after it note theirs
        _note(distinct=dcol, distinct_rows_in=_rows_or_capacity(rel), distinct_groups=deduped.rows)
        REGISTRY.counter(DISTINCT_COUNTER, help=DISTINCT_HELP).inc()
        dist_part = AggregationNode(
            source=node.source,  # unused
            group_keys=node.group_keys,
            aggregations=tuple(
                (s, Aggregation(a.function, a.args, False, a.filter, a.output_type))
                for s, a in node.aggregations
                if a.distinct
            ),
            step=node.step,
        )
        dist_rel = aggregate_relation(
            deduped, dist_part, self.types, self._pallas_mode()
        )
        plain_aggs = tuple(
            (s, a) for s, a in node.aggregations if not a.distinct
        )
        if not plain_aggs:
            return dist_rel
        plain_part = AggregationNode(
            source=node.source,  # unused
            group_keys=node.group_keys,
            aggregations=plain_aggs,
            step=node.step,
        )
        plain_rel = aggregate_relation(
            rel, plain_part, self.types, self._pallas_mode()
        )
        # both outputs order groups identically (same keys, same machinery —
        # group rows sit compacted at the front) but their CAPACITIES differ
        # (the distinct side aggregated the smaller deduped relation): verify
        # the active group rows match, then slice both to a common capacity
        act_a = np.asarray(dist_rel.page.active)
        act_b = np.asarray(plain_rel.page.active)
        ga, gb = int(act_a.sum()), int(act_b.sum())
        same = ga == gb
        if same and node.group_keys:
            # EVERY key column must align — a single-key check would accept
            # mismatched group orders whose first key happens to collide —
            # and NULL keys align on the valid mask with data compared only
            # where valid (invalid slots hold unspecified storage values)
            for k in node.group_keys:
                a, b = dist_rel.column_for(k), plain_rel.column_for(k)
                va = np.asarray(a.valid)[act_a]
                vb = np.asarray(b.valid)[act_b]
                da = np.asarray(a.data)[act_a]
                db = np.asarray(b.data)[act_b]
                # NaN is a valid non-NULL float group key and groups with
                # itself — it must compare equal here, not abort the query
                eq_nan = da.dtype.kind == "f"
                same = np.array_equal(va, vb) and np.array_equal(
                    da[va], db[vb], equal_nan=eq_nan
                )
                if not same:
                    break
        if not same:
            raise ExecutionError(
                "distinct/plain aggregation group alignment failed"
            )
        target = min(dist_rel.capacity, plain_rel.capacity)
        cols = {}
        for s in node.group_keys:
            cols[s] = _slice_column(dist_rel.column_for(s), target)
        for s, a in node.aggregations:
            src = dist_rel if a.distinct else plain_rel
            cols[s] = _slice_column(src.column_for(s), target)
        symbols = tuple(node.group_keys) + tuple(s for s, _ in node.aggregations)
        page = Page(tuple(cols[s] for s in symbols), dist_rel.page.active[:target])
        return Relation(page, symbols)

    # ----------------------------------------------------------------- joins

    def _exec_JoinNode(self, node: JoinNode) -> Relation:
        pre = self._join_inputs(node)
        if isinstance(pre, Relation):
            return pre  # the operator-state spill path ran the whole join
        left, right = pre
        return self._join_relations(node, left, right)

    def _join_inputs(self, node: JoinNode):
        """Shared join preamble — dynamic filtering, input compaction, the
        operator-state spill gate — factored out so the megakernel plane
        (join -> partial-agg fusion) evaluates inputs exactly the way the
        serial path does. Returns ``(left, right)`` Relations, or a finished
        Relation when the spill-partitioned path executed the join itself."""
        # dynamic filtering (ref: server/DynamicFilterService.java:101 +
        # DynamicFilterSourceOperator): evaluate the build side first, collect
        # its key ranges, and AND them into the probe subtree as a filter so
        # the probe is pruned before the join. Inner joins only (an outer
        # probe must keep unmatched rows).
        dynamic_filter = None
        if (
            node.kind == JoinKind.INNER
            and node.criteria
            and self.allow_host_sync
            and self.session.get("enable_dynamic_filtering")
        ):
            right = self.eval(node.right)
            dynamic_filter = self._dynamic_filter_predicate(node, right)
            if dynamic_filter is not None:
                fnode = FilterNode(source=node.left, predicate=dynamic_filter)
                left = self.eval(fnode)
                if self.collect_actuals:
                    # probe rows before vs after the build-derived range
                    # filter = the dynamic-filter hit rate statstore reports.
                    # fnode must stay referenced: actuals are keyed by id(),
                    # and a collected synthetic node's id could be reused
                    self._pinned.append(fnode)
                    self.dyn_filters[id(node)] = (id(fnode), id(node.left))
            else:
                left = self.eval(node.left)
        else:
            left = self.eval(node.left)
            right = self.eval(node.right)
        if self.allow_host_sync:
            left = _maybe_compact(left)
            right = _maybe_compact(right)
        # operator-state spill (ref: spilling HashBuilderOperator.java:68 +
        # MemoryRevokingScheduler.java:48): a build side larger than the
        # budget revokes to host as hash partitions, joined one at a time
        thresh = self._spill_threshold()
        if (
            thresh
            and self.allow_host_sync
            and node.criteria
            and node.kind != JoinKind.CROSS
        ):
            from .memory import page_bytes

            total = page_bytes(left.page) + page_bytes(right.page)
            if total > thresh:
                return self._spill_partitioned_join(node, left, right, total, thresh)
        return left, right

    def _join_sides(self, node: JoinNode, left: Relation, right: Relation):
        """RIGHT-swap + key/LUT extraction shared by the serial join and the
        fused megakernel path: returns (kind, node, probe, build, pkeys,
        bkeys, luts) with RIGHT normalized to LEFT (sides swapped; output
        symbols reorder by symbol lookup, so the swap is free)."""
        kind = node.kind
        if kind == JoinKind.RIGHT:
            node = JoinNode(
                left=node.right,
                right=node.left,
                kind=JoinKind.LEFT,
                criteria=tuple((r, l) for l, r in node.criteria),
                filter=node.filter,
                distribution=node.distribution,
            )
            left, right = right, left
            kind = JoinKind.LEFT
        probe, build = left, right
        if kind == JoinKind.CROSS:
            pkeys, bkeys, luts = (), (), ()
        else:
            pkeys = tuple(
                (probe.column_for(l).data, probe.column_for(l).valid)
                for l, _ in node.criteria
            )
            bkeys = tuple(
                (build.column_for(r).data, build.column_for(r).valid)
                for _, r in node.criteria
            )
            # cross-dictionary key translation for string join keys
            luts = _string_key_luts(node, probe, build)
        return kind, node, probe, build, pkeys, bkeys, luts

    def _join_relations(
        self, node: JoinNode, left: Relation, right: Relation,
        allow_fusion: bool = True,
    ) -> Relation:
        kind, node, probe, build, pkeys, bkeys, luts = self._join_sides(
            node, left, right
        )
        left_outer = kind in (JoinKind.LEFT, JoinKind.FULL)
        if allow_fusion and self._fusion_enabled():
            rel = self._try_fused_join(
                kind, node, probe, build, pkeys, bkeys, luts
            )
            if rel is not None:
                return rel

        key_bits, key_bases = self._join_key_widths(node, probe, build)
        # where the executor reads the join's totals, the match stops at the
        # merge and the expansion takes the ranks back to the probe's order
        # (RanksWay); a traced executor reads nothing and keeps the match's
        # own way back
        merged = (True,) if self.allow_host_sync else ()
        emit, count, lo, perm_b, totals, *qid = _jit_join_match(
            left_outer, pkeys, bkeys, luts, probe.page.active, build.page.active,
            key_bits, key_bases, *merged,
        )
        out_capacity, read = self._choose_join_capacity(emit, probe.capacity, build.capacity, totals)
        if read is not None and left_outer:
            # the probe rows no build row matched: rows emitted beyond the matches
            _note(unmatched_rows=read[0] - read[1])
        # no probe row emits more than one row (a key unique on the build
        # side, or a LEFT join's rows matching one build row or none): the
        # expansion's slots are the emitting rows in order
        unique = read is not None and read[2] <= 1
        ranks = ()
        if qid:
            ranks = (qid[0], _ranks_way(left_outer, probe.capacity, build.capacity, unique, read))
        page = _jit_join_expand(
            out_capacity, unique, emit, count, lo, perm_b, probe.page, build.page, *ranks
        )
        rows_out = self._note_join(
            node, probe, build, out_capacity, key_bits, unique,
            ranks[1].form if ranks else "merged", None if read is None else read[3],
        )

        if kind == JoinKind.FULL:
            # append unmatched build rows with a null probe side (the join is
            # symmetric: a LEFT expansion plus the build side's anti set)
            extra = _jit_full_join_tail(
                pkeys, bkeys, luts, probe.page, build.page
            )
            page = _concat_pages([page, extra])
        # match expansion emits probe-major output (expand_matches: slot ->
        # last probe row with start <= slot), so the probe side's sort order
        # survives INNER/LEFT joins; the FULL tail breaks it
        out_sorted = probe.sorted_by if kind != JoinKind.FULL else ()
        out = Relation(page, probe.symbols + build.symbols, out_sorted, rows_out)

        if node.filter is not None:
            if kind == JoinKind.FULL:
                raise ExecutionError(
                    "FULL JOIN with non-equi residual not supported yet"
                )
            fn, _ = compile_expression(node.filter, out.layout(), out.capacity)
            if not left_outer:
                page = _jit_filter(fn, out.env(), out.page)
                out = Relation(page, out.symbols, out.sorted_by)
            else:
                # LEFT semantics: the residual is part of the ON clause — rows
                # failing it drop, and probe rows left without any surviving
                # match re-emit one null-padded row
                page = _jit_left_join_residual(
                    fn,
                    out.symbols,
                    out_capacity,
                    unique,
                    emit,
                    count,
                    lo,
                    perm_b,
                    probe.page,
                    build.page,
                    *ranks,
                )
                out = Relation(page, out.symbols, out.sorted_by)
        self._tag_vector_broadcast(build, out)
        return out

    def _join_key_widths(self, node: JoinNode, probe: Relation, build: Relation):
        """(bits, least values) of the join's key columns where the dynamic
        filter has read the build side's range to the host already: a key
        that spans 2**bits values is matched as ``key - least`` in that many
        bits, so a bigint key whose values span 18 million is one 32-bit word
        of the match's sort and two keys of 25 and 30,000 values share one.
        The width is static (rounded up to a multiple of four, so that
        statements whose ranges differ a little share a program); the least
        value is an argument. (None, None) where no range is known: the keys
        take their types' widths."""
        bounds = self._join_key_bounds.get(id(node))
        if not bounds:
            return None, None
        bits, bases = [], []
        for probe_sym, build_sym in node.criteria:
            span = bounds.get(build_sym)
            integral = all(
                jnp.issubdtype(rel.column_for(sym).data.dtype, jnp.signedinteger)
                and rel.column_for(sym).dictionary is None
                for rel, sym in ((probe, probe_sym), (build, build_sym))
            )
            width = None
            if span is not None and integral:
                width = -(-max(1, (int(span[1]) - int(span[0])).bit_length()) // 4) * 4
            if width is None or width > 60:
                bits.append(None)
                bases.append(np.int64(0))
            else:
                bits.append(width)
                bases.append(np.int64(int(span[0])))  # an argument, not a program of its own
        if all(b is None for b in bits):
            return None, None
        return tuple(bits), tuple(bases)

    def _note_join(self, node, probe: Relation, build: Relation, out_capacity: int,
                   key_bits=None, unique: bool = False, ranks: str = "merged",
                   emitting_rows: Optional[int] = None):
        """What the join moved, on its span and in the counters; every value
        is on the host already. ``unique``: the expansion took the form in
        which each probe row emits one row at most. ``ranks``: the way its
        ranks came back to the probe's order (``RanksWay``), and
        ``emitting_rows`` the probe rows that emit, where read. Returns the
        rows emitted where ``_choose_join_capacity`` counted them
        (``sync:join_capacity``), else None; a FULL join's tail and a residual
        filter come after it."""
        keys = [probe.column_for(l) for l, _ in node.criteria]
        form = "unique" if unique else "general"
        _note(
            kind=node.kind.name, key_words=_join_key_words(node, probe, build, key_bits),
            probe_rows=_rows_or_capacity(probe), build_rows=_rows_or_capacity(build),
            probe_capacity=probe.capacity, build_capacity=build.capacity,
            capacity_out=out_capacity, expand=form, ranks=ranks, emitting_rows=emitting_rows,
            key_types=[c.type.display() for c in keys],
            key_bits=None if key_bits is None else list(key_bits),
            probe_types=_type_counts(probe.page.columns),
            build_types=_type_counts(build.page.columns), sort_passes=_sort_passes(1),
            merged_rows=probe.capacity + build.capacity, rank_words=K.rank_words(build.capacity),
        )
        _count_join_rows(probe=_rows_or_capacity(probe), build=_rows_or_capacity(build))
        REGISTRY.counter(JOINS_COUNTER, {"kind": node.kind.name}, help=JOINS_HELP).inc()
        REGISTRY.counter(JOIN_EXPANSIONS_COUNTER, {"form": form}, help=JOIN_EXPANSIONS_HELP).inc()
        REGISTRY.counter(JOIN_RANK_FORMS_COUNTER, {"form": ranks}, help=JOIN_RANK_FORMS_HELP).inc()
        if node.kind == JoinKind.FULL or node.filter is not None:
            return None
        span = TRACER.current()
        return None if span is None else span.attributes.get("rows_out")

    def _tag_vector_broadcast(self, build: Relation, out: Relation) -> None:
        """Embedding-JOIN detection (vector serving plane): a build side
        that is exactly ONE active row carrying vector columns makes
        ``sim(probe.v, build.v)`` above this join a constant-query scoring —
        tag the joined page with the broadcast vector symbols so a
        VectorTopN root routes through the vector serving tier's stacked
        path (runtime/device_scheduler.py). The lane body stays this query's
        own compiled einsum closures, so bit-identity vs the serial einsum
        is structural; the tag only affects routing."""
        if not self.allow_host_sync:
            return
        from ..spi.types import is_vector

        bsyms = frozenset(
            s for s in build.symbols
            if is_vector(build.column_for(s).type)
        )
        if not bsyms:
            return
        if _live_rows(build.page.active, "vector_broadcast") != 1:
            return
        out.page._vector_broadcast = bsyms

    # ------------------------------------------------- operator-state spill

    def _spill_threshold(self) -> int:
        try:
            return int(self.session.get("spill_operator_threshold_bytes") or 0)
        except KeyError:
            return 0

    def _hash_partition_spill(
        self, rel: Relation, key_symbols: Tuple[str, ...], nparts: int
    ) -> List[bytes]:
        """Revoke a relation to host as LZ4 hash partitions by key value.

        The partition id is a deterministic function of the key VALUE
        (dictionary columns hash through content-stable value keys), so the
        same key lands in the same partition on both join sides and a group
        never spans partitions — the invariant Trino's partitioned spill
        relies on (GenericPartitioningSpiller, SpillableHashAggregationBuilder).

        Runs as the compiled repartition epilogue (ops/repartition.py): one
        hash + stable cosort + one D2H yields a partition-contiguous buffer
        that serde slices into nparts frames — the old path ran one masked
        compaction program + serialization per partition (nparts device
        round-trips). Nested layouts keep the legacy per-partition path.
        """
        from ..ops.repartition import (
            device_repartition_enabled,
            hash_key_columns,
            partition_ids,
            repartition_frames,
            supports_device_repartition,
        )
        from .serde import serialize_page

        blobs: List[bytes] = []
        if device_repartition_enabled() and supports_device_repartition(rel.page):
            key_idx = [rel.symbols.index(s) for s in key_symbols]
            # pool=None: spill can run inside OOC pool jobs — fanning out
            # from a pool thread deadlocks a saturated executor
            blobs, _ = repartition_frames(rel.page, key_idx, nparts, compress=True)
        else:
            cols = [rel.column_for(s) for s in key_symbols]
            pid = partition_ids(hash_key_columns(cols), nparts)
            for p in range(nparts):
                mask = rel.page.active & (pid == p)
                n = _live_rows(mask, "spill_partition")
                part = _compact(Page(rel.page.columns, mask), n)
                blobs.append(serialize_page(part, compress=True))
        for b in blobs:
            self.spill_count += 1
            self.spilled_bytes += len(b)
            on_spill_write(len(b))
        return blobs

    def _unspill(self, blob: bytes, template: Relation) -> Relation:
        """Host bytes -> device Relation, re-attaching the parent's dictionary
        OBJECTS (same content): dictionaries are identity-hashed in the jit
        cache, so fresh objects per partition would force a recompile each.
        v2 frames land on a canonical capacity class (v1 frames carry their
        own rounded capacity) — varying partition sizes share compiled
        programs downstream."""
        from .serde import LazyPageFrame

        on_spill_read(len(blob))
        frame = LazyPageFrame(blob)
        page = frame.to_page(capacity=_round_capacity(max(frame.nrows, 1)))
        cols = tuple(
            Column(c.type, c.data, c.valid, t.dictionary, c.lengths,
                   c.elem_valid, c.children)
            if t.dictionary is not None
            else c
            for c, t in zip(page.columns, template.page.columns)
        )
        return Relation(Page(cols, page.active), template.symbols)

    @staticmethod
    def _spill_parts(total_bytes: int, thresh: int) -> int:
        nparts = 2
        while nparts * thresh < total_bytes and nparts < 64:
            nparts *= 2
        return nparts

    def _spill_partitioned_join(
        self, node: JoinNode, left: Relation, right: Relation,
        total_bytes: int, thresh: int,
    ) -> Relation:
        nparts = self._spill_parts(total_bytes, thresh)
        lkeys = tuple(l for l, _ in node.criteria)
        rkeys = tuple(r for _, r in node.criteria)
        lparts = self._hash_partition_spill(left, lkeys, nparts)
        rparts = self._hash_partition_spill(right, rkeys, nparts)
        outs: List[Relation] = []
        for lb, rb in zip(lparts, rparts):
            outs.append(
                self._join_relations(node, self._unspill(lb, left), self._unspill(rb, right))
            )
        page = _concat_pages([o.page for o in outs])
        return Relation(page, outs[0].symbols)

    def _spill_partitioned_aggregate(
        self, rel: Relation, node: AggregationNode, total_bytes: int, thresh: int
    ) -> Relation:
        """Partitioned aggregation under memory pressure (ref:
        SpillableHashAggregationBuilder.java): groups are disjoint across hash
        partitions, so per-partition aggregation outputs concatenate."""
        nparts = self._spill_parts(total_bytes, thresh)
        parts = self._hash_partition_spill(rel, node.group_keys, nparts)
        outs: List[Relation] = []
        for blob in parts:
            outs.append(
                aggregate_relation(
                    self._unspill(blob, rel), node, self.types, self._pallas_mode()
                )
            )
        page = _concat_pages([o.page for o in outs])
        return Relation(page, outs[0].symbols)

    def _dynamic_filter_predicate(self, node: JoinNode, build: Relation):
        """min/max range of the build keys as an IR predicate on probe symbols."""
        from ..sql.ir import Call as IrCall, Constant as IrConstant
        from ..spi.types import BOOLEAN as B, is_string as _is_str

        conjuncts = []
        bounds = self._join_key_bounds[id(node)] = {}
        for probe_sym, build_sym in node.criteria:
            bc = build.column_for(build_sym)
            if _is_str(bc.type):
                continue  # code spaces differ across dictionaries; skip strings
            w = build.page.active & bc.valid
            n = _live_rows(w, "dynamic_filter")
            if n == 0:
                continue
            info_min = jnp.where(w, bc.data, bc.data.max()).min()
            info_max = jnp.where(w, bc.data, bc.data.min()).max()
            lo, hi = bc.type.storage_dtype.type(info_min).item(), bc.type.storage_dtype.type(info_max).item()
            bounds[build_sym] = (lo, hi)  # the join's match packs its keys by them
            ptype = self.types[probe_sym]
            ref = Reference(probe_sym, ptype)
            conjuncts.append(
                IrCall(
                    "$and",
                    (
                        IrCall("$gte", (ref, IrConstant(bc.type, lo)), B),
                        IrCall("$lte", (ref, IrConstant(bc.type, hi)), B),
                    ),
                    B,
                )
            )
        if not conjuncts:
            return None
        pred = conjuncts[0]
        for c in conjuncts[1:]:
            pred = IrCall("$and", (pred, c), B)
        return pred

    def _exec_SemiJoinNode(self, node: SemiJoinNode) -> Relation:
        source = self.eval(node.source)
        filtering = self.eval(node.filtering_source)
        skey = source.column_for(node.source_key)
        fkey = filtering.column_for(node.filtering_key)
        lut = _translate_lut(skey.dictionary, fkey.dictionary)
        page = _jit_semijoin(
            skey, fkey, lut, source.page, filtering.page.active, node.null_aware
        )
        _note(
            probe_rows=_rows_or_capacity(source), build_rows=_rows_or_capacity(filtering),
            probe_capacity=source.capacity, build_capacity=filtering.capacity,
            rows_out=_rows_or_capacity(source), capacity_out=source.capacity,
            key_types=[skey.type.display()], probe_types=_type_counts(source.page.columns),
            build_types=_type_counts([fkey]), sort_passes=_sort_passes(1), negated=node.negated,
            merged_rows=source.capacity + filtering.capacity, rank_words=1,
        )
        _count_join_rows(
            probe=_rows_or_capacity(source), build=_rows_or_capacity(filtering),
            out=_rows_or_capacity(source),
        )
        return Relation(page, source.symbols + (node.output,), rows=source.rows)

    # ------------------------------------------------------------- sort/limit

    def _exec_SortNode(self, node: SortNode) -> Relation:
        rel = self.eval(node.source)
        if self.allow_host_sync:
            rel = _maybe_compact(rel)
        _note_sort(node.orderings, rel, None)
        page = _jit_sort(node.orderings, rel.symbols, None, rel.page)
        return Relation(page, rel.symbols, rows=rel.rows)

    def _exec_TopNNode(self, node: TopNNode) -> Relation:
        rel = self.eval(node.source)
        if self.allow_host_sync:
            rel = _maybe_compact(rel)
        _note_sort(node.orderings, rel, node.count)
        page = _jit_sort(node.orderings, rel.symbols, node.count, rel.page)
        return Relation(page, rel.symbols)

    def _exec_VectorTopNNode(self, node) -> Relation:
        rel = self.eval(node.source)
        if self.allow_host_sync:
            rel = _maybe_compact(rel)
        return self.run_vector_topn(node, rel)

    def run_vector_topn(self, node, rel: Relation) -> Relation:
        """Tensor plane: the fused scores->top-k program over an already
        evaluated (and compacted) source — the scoring projection's closures
        and the stable top-k permutation dispatch as ONE device program (one
        launch where the serial pair books two). Shared by the serial walk
        and the vector serving tier's per-lane fallback
        (runtime/device_scheduler.py), so both paths compute the same bytes.
        Off the chip a runtime failure falls back to the serial Project +
        TopN pair with a labeled counter tick; on the chip it is raised."""
        from ..ops import tensor as T
        from ..planner.plan import ProjectNode as _PN

        symbols = tuple(s for s, _ in node.assignments)
        try:
            compiled = self._compile_assignments(node.assignments, rel)
            info = T.assignments_vector_info(node.assignments) or (0, 0)
            with T.topk_fusion_span(rel.capacity, info[1], node.count):
                page = _jit_vector_topn(
                    compiled, symbols, node.orderings, node.count,
                    rel.env(), rel.page,
                )
            T.on_vector_kernel()
            out = Relation(page, symbols)
            self._maybe_sample_ann_recall(node, out)
            return out
        except Exception:
            if jax.default_backend() == "tpu":
                raise  # on the chip a fused program that fails is an error
            T.on_topk_fallback("kernel_error")
            proj = self._project_relation(
                _PN(source=node.source, assignments=node.assignments), rel
            )
            page = _jit_sort(
                node.orderings, proj.symbols, node.count, proj.page
            )
            return Relation(page, proj.symbols)

    def _maybe_sample_ann_recall(self, node, approx: Relation) -> None:
        """ANN recall monitoring: re-run a deterministic sample of pruned
        vector top-k executions against the unpruned exact oracle (the SAME
        fused program over ALL cluster splits) and record measured recall@k
        to the system.runtime.ann_recall ring. Measurement only — the
        sampled query's result is untouched, and a failed oracle run never
        fails the query."""
        from ..ops import tensor as T

        if not self.allow_host_sync:
            return
        stats = self.ann_probe_stats.get(id(node.source))
        if stats is None or stats["probed"] >= stats["total"]:
            return
        try:
            rate = float(self.session.get("ann_recall_sample_rate") or 0.0)
        except KeyError:
            rate = 0.0
        if rate <= 0.0 or not T.ann_sample_due(rate):
            return
        try:
            import dataclasses as _dc

            scan = node.source
            handle = scan.table
            exact_handle = _dc.replace(
                handle,
                connector_handle={
                    k: v for k, v in handle.connector_handle.items()
                    if k != "ann_probe"
                } or None,
            )
            oracle_rel = self._exec_TableScanNode(
                _dc.replace(scan, table=exact_handle)
            )
            oracle_rel = _maybe_compact(oracle_rel)
            symbols = tuple(s for s, _ in node.assignments)
            compiled = self._compile_assignments(node.assignments, oracle_rel)
            exact_page = _jit_vector_topn(
                compiled, symbols, node.orderings, node.count,
                oracle_rel.env(), oracle_rel.page,
            )
            from collections import Counter

            got = Counter(_result_row_keys(approx.page))
            want = Counter(_result_row_keys(exact_page))
            k_eff = sum(want.values())
            recall = (
                sum((got & want).values()) / k_eff if k_eff else 1.0
            )
            T.record_ann_recall(
                str(scan.table.schema_table), node.count, stats["nprobe"],
                recall, stats["probed"], stats["total"],
            )
        except Exception:
            T.on_ann_oracle_error()  # monitoring only, never a query failure

    def _exec_LimitNode(self, node: LimitNode) -> Relation:
        rel = self.eval(node.source)
        page = _jit_limit(node.count, node.offset, rel.page)
        return Relation(page, rel.symbols)

    # ------------------------------------------------------------------ misc

    def _exec_TableFunctionNode(self, node: TableFunctionNode) -> Relation:
        if node.function == "sequence":
            start, stop, step = node.args
            n = max((stop - start) // step + 1, 0)
            cap = _round_capacity(max(n, 1), base=16)
            data = jnp.int64(start) + jnp.arange(cap, dtype=jnp.int64) * jnp.int64(step)
            active = jnp.arange(cap) < n
            col = Column(BIGINT, data, active)
            return Relation(Page((col,), active), node.symbols)
        raise ExecutionError(f"table function {node.function} not implemented")

    def _exec_ValuesNode(self, node: ValuesNode) -> Relation:
        n = len(node.rows)
        cols = []
        for i, sym in enumerate(node.symbols):
            type_ = self.types[sym]
            vals = [row[i] for row in node.rows]
            from ..spi.types import VectorType as _VecT

            if is_string(type_):
                col = Column.from_strings(vals, type_)
            elif isinstance(type_, _VecT):
                # vector literals (folded CAST(ARRAY[...] AS vector(n))):
                # host tuples -> the dense (rows, n) lane buffer
                dim = type_.dimension
                arr = np.zeros((len(vals), dim), dtype=np.float64)
                valid = np.zeros(len(vals), dtype=np.bool_)
                for j, v in enumerate(vals):
                    if v is None:
                        continue
                    if len(v) != dim:
                        raise ExecutionError(
                            f"vector literal of length {len(v)} for "
                            f"{type_.display()}"
                        )
                    arr[j] = np.asarray(v, dtype=np.float64)
                    valid[j] = True
                col = Column.from_numpy(type_, arr, valid)
            elif getattr(type_, "storage_lanes", None) == 2:
                # long decimals: python ints -> two int64 limbs
                from ..ops.int128 import np_from_ints

                arr = np_from_ints([0 if v is None else int(v) for v in vals])
                valid = np.array([v is not None for v in vals], dtype=np.bool_)
                col = Column.from_numpy(type_, arr, valid)
            else:
                arr = np.array(
                    [0 if v is None else v for v in vals], dtype=type_.storage_dtype
                )
                valid = np.array([v is not None for v in vals], dtype=np.bool_)
                col = Column.from_numpy(type_, arr, valid)
            cols.append(col)
        active = jnp.ones((max(n, 1),), dtype=jnp.bool_)
        if n == 0:
            active = jnp.zeros((1,), dtype=jnp.bool_)
            cols = [
                Column(
                    self.types[s],
                    jnp.zeros((1,), dtype=self.types[s].storage_dtype),
                    jnp.zeros((1,), dtype=jnp.bool_),
                )
                for s in node.symbols
            ]
        return Relation(Page(tuple(cols), active), node.symbols)

    def _exec_UnionNode(self, node: UnionNode) -> Relation:
        pages = []
        for inp, in_syms in zip(node.inputs, node.symbol_mapping):
            rel = self.eval(inp)
            cols = tuple(rel.column_for(s) for s in in_syms)
            pages.append(Page(cols, rel.page.active))
        merged = _concat_union_pages(pages, [self.types[s] for s in node.symbols])
        return Relation(merged, node.symbols)

    def _exec_EnforceSingleRowNode(self, node: EnforceSingleRowNode) -> Relation:
        rel = self.eval(node.source)
        n = _live_rows(rel.page.active, "single_row")
        if n > 1:
            raise ExecutionError("scalar subquery returned more than one row")
        if n == 1:
            return rel
        # empty -> single null row (SQL scalar subquery semantics)
        cols = tuple(
            _null_column(c, 1) for c in rel.page.columns
        )
        return Relation(Page(cols, jnp.ones((1,), dtype=jnp.bool_)), rel.symbols)

    def _exec_ExchangeNode(self, node: ExchangeNode) -> Relation:
        # single-process local execution: exchanges are pass-through;
        # the distributed engine (parallel/) overrides this.
        return self.eval(node.source)

    def _exec_WindowNode(self, node: WindowNode) -> Relation:
        from .window import execute_window

        rel = self.eval(node.source)
        return execute_window(self, rel, node)

    def _exec_PatternRecognitionNode(self, node) -> Relation:
        from .match_recognize import execute_match_recognize

        rel = self.eval(node.source)
        return execute_match_recognize(self, rel, node)


# --------------------------------------------------------------------------- #
# aggregation core (shared with distinct path)
# --------------------------------------------------------------------------- #


def _load_splits(provider, splits, col_indexes, session) -> List[Page]:
    """Intra-node source parallelism (the LocalExchange.java:66 /
    AddLocalExchanges analogue for this engine): the device is ONE driver, so
    local parallelism lives at the source boundary — `task_concurrency` host
    threads decode/generate splits concurrently, overlapping host work with
    each other and with device uploads (numpy releases the GIL; jnp.asarray
    dispatch is async). Split order is preserved, so connector-declared sort
    order survives exactly as in the serial path."""
    try:
        workers = int(session.get("task_concurrency") or 1)
    except KeyError:
        workers = 1
    if workers <= 1 or len(splits) <= 1:
        return [provider.create_page_source(sp, col_indexes) for sp in splits]
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=min(workers, len(splits))) as pool:
        return list(
            pool.map(lambda sp: provider.create_page_source(sp, col_indexes), splits)
        )


def _note_sort(orderings, rel: Relation, count: Optional[int]) -> None:
    """An ORDER BY's span attributes: rows in and out, keys, passes."""
    rows_in = _rows_or_capacity(rel)
    # `K.encode_sort_columns`: an int64 order key a column, two for Int128 limbs
    bits = 1 + sum(128 if rel.column_for(o.symbol).data.ndim == 2 else 64 for o in orderings)
    _note(
        rows_in=rows_in, capacity_in=rel.capacity,
        rows_out=rows_in if count is None else min(count, rows_in),
        keys=len(orderings),
        key_types=[rel.column_for(o.symbol).type.display() for o in orderings],
        carried_types=_type_counts(rel.page.columns), sort_passes=_sort_passes(bits),
    )


def _maybe_compact(rel: Relation, density: int = 4, min_cap: int = 8192) -> Relation:
    """Drop inactive rows when fewer than 1/``density`` of capacity is live.

    One compaction (``_compact``: its cost follows the rows and columns kept)
    in place of the many full-capacity sort passes a sparse group-by, join or
    sort would otherwise pay. Host-syncs the active count: callers are
    pipeline breakers that already host-sync their output capacity."""
    cap = rel.capacity
    if cap <= min_cap:
        return rel
    n = _live_rows(rel.page.active, "compact")
    if n * density > cap:
        rel.rows = n
        return rel
    # compaction is a stable partition by activity — order preserved
    return Relation(_compact(rel.page, n), rel.symbols, rel.sorted_by, rows=n)


COMPACTIONS_COUNTER = "trino_tpu_compactions_total"
COMPACTIONS_HELP = (
    "pages made dense, by how the rows kept are found: index (a walk over the "
    "mask) or sort (one sort of the positions); the columns are gathered"
)


def _compact(page: Page, live_rows: int) -> Page:
    """The page's ``live_rows`` active rows, in row order, at the front of a
    page of the next capacity class. One `compact` span under the operator
    that asked (``gather``: the form its columns follow in) and one tick of
    ``trino_tpu_compactions_total{path}``."""
    new_cap = min(_round_capacity(max(live_rows, 1)), page.capacity)
    path = _compact_path(new_cap, page)
    # what `_jit_compact`'s one `K.gather_rows` is traced to, by the same call
    gathers, words = K.gather_shape(_flat_arrays(page.columns)[1])
    form = K.gather_form(page.capacity, new_cap, gathers, words)
    # what was scanned (capacity_in rows of `columns`) against what is kept
    with TRACER.span(
        "compact", capacity_in=page.capacity, live_rows=live_rows,
        capacity_out=new_cap, columns=len(page.columns), path=path,
        gather=form, words=words,
    ):
        REGISTRY.counter(
            COMPACTIONS_COUNTER, {"path": path}, help=COMPACTIONS_HELP
        ).inc()
        return _jit_compact(new_cap, page)


def _compact_path(new_cap: int, page: Page) -> str:
    """How ``K.live_indices`` finds the rows kept, from the static shapes
    alone, as the span and the counter name it: ``index`` (the walk over the
    mask, work per row KEPT) under a sixteenth kept, ``sort`` (one sort of
    the positions, the same at any share kept) above it. Either way the
    columns follow by one ``K.gather_rows`` of the rows kept: no column rides
    a sort (PERF.md section 6, PR 34: the eleven-operand sort that carried
    them compiled for 206 s). Nested and multi-lane pages are named
    ``index`` at any density, as before."""
    flat = not any(c.children or c.data.ndim > 1 for c in page.columns)
    if flat and new_cap * K.LIVE_INDEX_SHARE > page.capacity:
        return "sort"
    return "index"


@partial(kernelcost.jit, static_argnums=(0,))
def _jit_compact(new_cap: int, page: Page) -> Page:
    idx = K.live_indices(page.active, new_cap)
    rows = jnp.minimum(idx, page.capacity - 1)  # padding slots: any row
    cols, _ = _permute_columns(page.columns, rows)
    return Page(cols, idx < page.capacity)


def _needed_agg_symbols(node: AggregationNode) -> Tuple[str, ...]:
    needed: List[str] = []
    for k in node.group_keys:
        if k not in needed:
            needed.append(k)
    for _, a in node.aggregations:
        for s in a.args:
            if s not in needed:
                needed.append(s)
        if a.filter and a.filter not in needed:
            needed.append(a.filter)
    return tuple(needed)


# Functions the direct-indexed path supports (approx_distinct and DISTINCT
# need per-group value sorting and stay on the sort path).
_DIRECT_AGG_FUNCS = frozenset(
    {
        "count", "count_if", "sum", "avg", "min", "max", "bool_and", "every",
        "bool_or", "arbitrary", "any_value", "stddev", "stddev_samp",
        "stddev_pop", "variance", "var_samp", "var_pop", "$fsum", "$fsumsq",
    }
)
# What a global aggregation computes as one masked reduction over the page
# (`K.segment_reduce` at capacity 1): the others scatter, sort or gather per
# row and want few rows.
_MASKED_REDUCE_AGGS = _DIRECT_AGG_FUNCS - {"arbitrary", "any_value"}
# Above this many candidate groups the [G, n] broadcast reduction loses to the
# sort path (each extra group re-reads the data lane-parallel).
DIRECT_GROUP_LIMIT = 256


def _direct_agg_domains(rel: Relation, node: AggregationNode):
    """Static per-key domain sizes when every group key has a small, statically
    known domain (dictionary-coded strings, booleans) — the condition for the
    sort-free direct-indexed aggregation (BigintGroupByHash fast-path analogue,
    GroupByHash.java:82-98). Returns None when the sort path must be used."""
    if not node.group_keys:
        return None
    if any(
        a.function not in _DIRECT_AGG_FUNCS or a.distinct
        for _, a in node.aggregations
    ):
        return None
    domains = []
    for k in node.group_keys:
        c = rel.column_for(k)
        if c.dictionary is not None:
            domains.append(len(c.dictionary) + 1)  # +1: null slot
        elif c.type == BOOLEAN:
            domains.append(3)
        else:
            return None
    total = 1
    for d in domains:
        total *= d
    if not 1 <= total <= DIRECT_GROUP_LIMIT:
        return None
    return tuple(domains)


def aggregate_relation(
    rel: Relation,
    node: AggregationNode,
    types: Dict[str, Type],
    pallas_mode: str = "off",
) -> Relation:
    """Grouped aggregation, two strategies (ref GroupByHash.java:82-98 — the
    engine picks a hash strategy per key shape; here per domain knowledge):

    - direct-indexed (small static key domains): gid computed elementwise from
      dictionary codes, one fused bandwidth-bound pass — no sort, no host sync.
    - sort-based: (1) order the needed columns by the group keys (one sort of
      the keys' packed words, the columns gathered by its permutation:
      ``_group_sort_impl``), host-sync the group count, (2) reduction program
      with a bucketed static output capacity, segment sums via
      cumsum-at-boundaries."""
    domains = _direct_agg_domains(rel, node)
    if domains is not None:
        page = _jit_direct_aggregate(
            node.group_keys, node.aggregations, domains, rel.symbols, rel.page,
            pallas_mode,
        )
        _note_aggregation(node, rel, "direct", None)
        return Relation(page, node.group_keys + tuple(s for s, _ in node.aggregations))
    # sparse inputs (a selective filter upstream) would drag dead rows through
    # every multi-pass sort — compact first (this path host-syncs anyway).
    # ref: Trino pages are always dense (PageProcessor compacts per batch);
    # our mask design defers compaction to exactly these pipeline breakers.
    # A global aggregation of plain reductions is no such breaker: it reads
    # each column once under the mask, which costs less than the compaction.
    if node.group_keys or not all(
        a.function in _MASKED_REDUCE_AGGS and not a.ordering
        for _, a in node.aggregations
    ):
        rel = _maybe_compact(rel)
    # aggregate ORDER BY (array_agg(x ORDER BY y), listagg WITHIN GROUP): the
    # group sort is stable, so pre-sorting the whole relation by the aggregate
    # ordering fixes each group's element order (ref: AggregationNode
    # orderingScheme -> operator/aggregation ordered accumulators)
    orderings: Tuple = ()
    for _, a in node.aggregations:
        if a.ordering:
            if orderings and a.ordering != orderings:
                raise ExecutionError(
                    "multiple distinct aggregate ORDER BY clauses in one "
                    "aggregation are not supported"
                )
            orderings = a.ordering
    if orderings:
        rel = Relation(_jit_sort(orderings, rel.symbols, None, rel.page), rel.symbols)
    needed = _needed_agg_symbols(node)
    if node.group_keys:
        # pre-sorted fast path: input ordered on the first group key skips
        # the multi-pass group sort entirely (self-verifying, see
        # _jit_presorted_group)
        sorted_page = None
        if rel.sorted_by and rel.sorted_by[0] == node.group_keys[0]:
            if any(a.function in _RESORT_AGGS for _, a in node.aggregations):
                # these aggregates re-sort internally and rely on group
                # segments staying at fixed positions — that needs a dense
                # active prefix, so compact any interleaved inactive rows
                rel = _force_dense(rel)
            p, ng, n_grp, viol = _jit_presorted_group(
                node.group_keys, needed, rel.symbols, rel.page
            )
            if not _sync_int(viol, "presorted_check"):
                sorted_page, new_group, num_groups = p, ng, n_grp
        path = "presorted"
        if sorted_page is None:
            path = "sort"
            sorted_page, new_group, num_groups = _jit_group_sort(
                node.group_keys, needed, rel.symbols, rel.page
            )
        groups = _sync_int(num_groups, "num_groups")
        _note_aggregation(node, rel, path, groups)
        # a power of two up to 2**20 groups, a stored page's class above it
        # (4.5M groups take 5,242,880 slots, not 8,388,608: every aggregate is
        # a gather of the slots, and the join above sorts them)
        out_cap = min(
            _round_capacity(groups, base=16) if groups <= 1 << 20 else capacity_class(groups),
            max(rel.capacity, 16),
        )
    else:
        # global aggregation: no sort at all — select the needed columns
        cols = tuple(rel.column_for(s) for s in needed)
        sorted_page = Page(cols, rel.page.active)
        new_group, num_groups, out_cap = None, 1, 1
        groups = 1
        _note_aggregation(node, rel, "global", 1)
    # lane-valued aggregates (array_agg, map_agg, histogram, multimap_agg,
    # listagg) need a static lane width = the largest group's row count
    # (host-synced like num_groups; ref operator/aggregation/ArrayAggregation)
    agg_w = 0
    if any(a.function in _LANE_AGGS for _, a in node.aggregations):
        if node.group_keys:
            agg_w = _sync_int(_jit_max_run(new_group, sorted_page.active), "lane_width")
        else:
            agg_w = _live_rows(sorted_page.active, "lane_width")
        agg_w = _round_capacity(max(agg_w, 1), base=8)
    page = _jit_aggregate(
        node.group_keys,
        node.aggregations,
        needed,
        out_cap,
        agg_w,
        sorted_page,
        new_group,
        num_groups if node.group_keys else jnp.int32(1),
    )
    # host finalization for string/nested-valued aggregates: listagg joins the
    # gathered lanes into new dictionary strings; multimap_agg regroups the
    # (key, value) lanes into map<K, array(V)> (strings/nested construction is
    # a host concern in this engine — same as dictionary LUT transforms)
    fin = [
        i
        for i, (_, a) in enumerate(node.aggregations)
        if a.function in ("listagg", "multimap_agg")
    ]
    if fin:
        cols = list(page.columns)
        nk = len(node.group_keys)
        for i in fin:
            _, agg = node.aggregations[i]
            if agg.function == "listagg":
                sep = ""
                if len(agg.args) > 1:
                    sepcol = rel.column_for(agg.args[1])
                    vals = sepcol.decode(np.asarray(rel.page.active))
                    nonnull = [v for v in vals if v is not None]
                    sep = nonnull[0] if nonnull else ""
                cols[nk + i] = _finalize_listagg(cols[nk + i], sep)
            else:
                cols[nk + i] = _finalize_multimap(cols[nk + i], agg.output_type)
        page = Page(tuple(cols), page.active)
    out_symbols = node.group_keys + tuple(s for s, _ in node.aggregations)
    return Relation(page, out_symbols, rows=groups)


def _note_aggregation(node: AggregationNode, rel: Relation, path: str, groups) -> None:
    """An aggregation's span attributes and ``trino_tpu_group_rows_total``.
    ``groups`` is the synced count (None on the direct path, which reads
    none); a group sort's passes follow from its keys' widths."""
    keys = [rel.column_for(k) for k in node.group_keys]
    rows_in = _rows_or_capacity(rel)
    attributes = dict(
        path=path, rows_in=rows_in, capacity_in=rel.capacity, groups=groups,
        functions=[a.function for _, a in node.aggregations],
        keys=len(keys), key_types=[c.type.display() for c in keys],
        agg_types=_type_counts(
            rel.column_for(s) for s in _needed_agg_symbols(node) if s not in node.group_keys
        ),
    )
    if path == "sort":
        attributes["sort_passes"] = _sort_passes(1 + sum(_key_bits(c) + 1 for c in keys))
    _note(**attributes)
    REGISTRY.counter(GROUP_ROWS_COUNTER, {"path": path}, help=GROUP_ROWS_HELP).inc(rows_in)


# aggregates whose per-group state is a padded lane grid [out_cap, agg_w]
_LANE_AGGS = frozenset(
    {"array_agg", "map_agg", "multimap_agg", "histogram", "listagg"}
)

# aggregates whose evaluation re-sorts rows by gid and reuses the group
# bounds positionally (distinct-count cosorts, percentile rank gathers,
# map-lane scatters) — the presorted fast path must hand them a dense
# active prefix
_RESORT_AGGS = frozenset(
    {
        "approx_distinct", "approx_percentile", "tdigest_agg", "qdigest_agg",
        "map_agg", "histogram", "multimap_agg", "listagg",
    }
)


def _force_dense(rel: Relation) -> Relation:
    """Compact unless active rows already form a dense prefix."""
    n = _live_rows(rel.page.active, "force_dense")
    if n == rel.capacity or _sync_int(jnp.all(rel.page.active[:n]), "force_dense"):
        return rel
    return Relation(_compact(rel.page, n), rel.symbols, rel.sorted_by)


def _finalize_listagg(col: Column, sep: str) -> Column:
    """listagg lanes -> joined strings with a fresh dictionary (host).

    Rows outside the produced group count decode with padded lanes (None
    elements) — skip those elements; the page's active mask hides the rows."""
    lists = col.children[0].decode(None)
    strings = [
        None if x is None else sep.join(e for e in x if e is not None)
        for x in lists
    ]
    return Column.from_strings(strings, col.type)


def _finalize_multimap(col: Column, out_type) -> Column:
    """multimap_agg (key, value) lanes -> map<K, array(V)> (host regroup)."""
    karr, varr = col.children
    klists = karr.decode(None)
    vlists = varr.decode(None)
    dicts: List[Optional[dict]] = []
    for ks, vs in zip(klists, vlists):
        if ks is None:
            dicts.append(None)
            continue
        d: dict = {}
        for k, v in zip(ks, vs):
            if k is not None:
                d.setdefault(k, []).append(v)
        dicts.append(d)
    return Column.from_nested(out_type, dicts)


def _presorted_group_impl(group_keys, needed, symbols, page: Page):
    """Grouping WITHOUT sorting for inputs already ordered on the first group
    key (ref: the reference's streaming aggregation over pre-sorted local
    properties — AddExchanges keeps grouped/sorted data properties so
    HashAggregationOperator can stream). Rows stay in place; inactive rows may
    be interleaved (last-active-prev scans bridge the gaps).

    Returns (page over ``needed``, new_group, num_groups, violation) where
    ``violation`` is True when the data is NOT actually sorted on key1 (any
    active row's key1 decreases) or secondary keys vary within a key1 run —
    the caller falls back to the sorting path, so a wrong or stale sortedness
    declaration can never produce wrong results."""
    rel = Relation(page, symbols)
    active = page.active
    k1 = rel.column_for(group_keys[0])
    k1n = jnp.where(k1.valid, K.order_key(k1.data), jnp.int64(K.INT64_MAX))
    prev_k1, has_prev = K.last_active_prev(k1n, active)
    first_active = active & ~has_prev
    new_group = active & (first_active | (k1n != prev_k1))
    violation = jnp.any(active & has_prev & (k1n < prev_k1))
    for k in group_keys[1:]:
        c = rel.column_for(k)
        kn = jnp.where(c.valid, K.order_key(c.data), jnp.int64(K.INT64_MAX))
        prev_k, _ = K.last_active_prev(kn, active)
        # a secondary key changing inside a key1 run means the run holds
        # multiple groups interleaved — only a sort can separate them
        violation = violation | jnp.any(
            active & has_prev & ~new_group & (kn != prev_k)
        )
    num_groups = jnp.sum(new_group.astype(jnp.int32))
    cols = tuple(rel.column_for(s) for s in needed)
    return Page(cols, active), new_group, num_groups, violation


_jit_presorted_group = partial(kernelcost.jit, static_argnums=(0, 1, 2))(
    _presorted_group_impl
)


def _group_sort_impl(group_keys, needed, symbols, page: Page):
    """Phase 1: order the needed columns by the group keys; detect group
    boundaries. Returns (sorted Page over ``needed`` symbols, new_group mask,
    num_groups). Active rows first; then, key by key, nulls before values and
    values ascending. The order is one ``K.sort_perm`` over the keys' packed
    order fields (a key's width is its type's or its dictionary's, so seven
    keys are a handful of words and the program holds one three-operand sort
    whatever the keys and the columns); the columns follow in one gather.
    Plain body — ops/megakernels.py re-traces it inside the fused join
    kernel's sort-path aggregation stage (bit-identity by construction)."""
    rel = Relation(page, symbols)
    fields = []
    for i, k in enumerate(group_keys):
        c = rel.column_for(k)
        flag = c.valid.astype(jnp.uint64)  # nulls (0) before values (1)
        if i == 0:  # and inactive rows (2) after both
            flag = jnp.where(page.active, flag, jnp.uint64(2))
        fields.append((flag, 2 if i == 0 else 1))
        fields.extend(K.order_field(x, bits) for x, bits in _group_key_parts(c))
    perm = K.sort_perm(fields)
    cols, (active_s,) = _permute_columns(
        [rel.column_for(s) for s in needed], perm, extra=[page.active]
    )
    cap = page.capacity
    by_symbol = dict(zip(needed, cols))
    diff = jnp.zeros(cap, dtype=bool)
    for k in group_keys:
        c = by_symbol[k]
        diff = diff | (c.valid != jnp.roll(c.valid, 1))
        for x, _ in _group_key_parts(c):
            diff = diff | (x != jnp.roll(x, 1))
    first = jnp.zeros(cap, dtype=bool).at[0].set(True)
    prev_active = jnp.roll(active_s, 1).at[0].set(False)
    new_group = active_s & (first | diff | ~prev_active)
    num_groups = jnp.sum(new_group.astype(jnp.int32))
    return Page(cols, active_s), new_group, num_groups


def _group_key_parts(c: Column):
    """A group key as integers that are equal where the key is and ordered
    as it is, with the width each needs where it is known: [(values, bits or
    None)]. Nulls read zero (the validity bit is compared apart), a double
    its order key (so -0.0 and 0.0 stay two groups and NaNs one, as the sort
    sees them), Int128 limbs two parts, a dictionary's codes as many bits as
    the dictionary needs."""
    if c.data.ndim == 2:
        from ..ops import int128 as i128

        return [(jnp.where(c.valid, x, 0), None) for x in i128.order_key_pair(c.data)]
    if c.dictionary is not None:
        return [(jnp.where(c.valid, c.data, 0), _key_bits(c))]
    data = c.data
    if jnp.issubdtype(data.dtype, jnp.floating):
        data = K.float_order_key(data)
    return [(jnp.where(c.valid, data, jnp.zeros((), data.dtype)), None)]


_jit_group_sort = partial(kernelcost.jit, static_argnums=(0, 1, 2))(_group_sort_impl)


@kernelcost.jit
def _jit_max_run(new_group, active):
    """Largest group's row count (group-sorted input): distance from each row
    to its group's first row, maxed over active rows."""
    n = new_group.shape[0]
    idx = jnp.arange(n)
    start_pos = jax.lax.associative_scan(jnp.maximum, jnp.where(new_group, idx, -1))
    return jnp.max(jnp.where(active, idx - start_pos + 1, 0))


def _aggregate_impl(
    group_keys: Tuple[str, ...],
    aggregations: Tuple[Tuple[str, Aggregation], ...],
    symbols: Tuple[str, ...],
    out_cap: int,
    agg_w: int,  # static array_agg lane width (0 when unused)
    page: Page,  # already sorted by group keys (or unsorted for global)
    new_group,
    num_groups,
) -> Page:
    rel = Relation(page, symbols)
    global_agg = len(group_keys) == 0
    active_s = page.active
    n = page.capacity

    bounds = None
    gid = None
    if not global_agg:
        starts = K.live_indices(new_group, out_cap)  # n-padded
        ends = jnp.concatenate([starts[1:], jnp.array([n])]) - 1
        bounds = (starts, ends)
        safe_starts = jnp.clip(starts, 0, n - 1)
        # arbitrary/approx_*/... need dense gids (scatter/sort paths); min and
        # max read the segments' bounds, and only an Int128's two passes
        # broadcast the first pass's extreme back by gid
        if any(
            a.function
            in (
                "arbitrary", "any_value", "approx_distinct",
                "approx_percentile", "tdigest_agg", "qdigest_agg", "array_agg",
                "map_agg", "histogram", "multimap_agg", "listagg", "min_by",
                "max_by", "bitwise_and_agg", "bitwise_or_agg",
                "bitwise_xor_agg",
            )
            or (a.function in ("min", "max") and rel.column_for(a.args[0]).data.ndim == 2)
            for _, a in aggregations
        ):
            # max(…, 0): presorted (unsorted-layout) inputs may have inactive
            # rows before the first group start; they never participate but
            # their gid must stay a valid segment id
            gid = jnp.maximum(
                K.cumsum(new_group.astype(jnp.int32)) - 1, 0
            ).astype(jnp.int32)

    if global_agg:
        # exactly one output row even over empty input
        group_exists = jnp.ones((1,), dtype=jnp.bool_)
    else:
        group_exists = jnp.arange(out_cap) < num_groups

    # group key outputs: the first row of each group, every key's values and
    # validity in ONE gather of out_cap rows
    keys = [rel.column_for(k) for k in group_keys]
    first = K.gather_rows([a for c in keys for a in (c.data, c.valid)], safe_starts) if keys else []
    out_cols: List[Column] = [
        Column(c.type, first[2 * i], first[2 * i + 1] & group_exists, c.dictionary)
        for i, c in enumerate(keys)
    ]

    def reduce_fn(vals, w, kind):
        if kind in ("sum", "count", "min", "max"):  # read off the sorted segments' bounds
            return K.segment_reduce(vals, w, gid, out_cap, kind, new_group, bounds)
        g = gid if gid is not None else jnp.zeros(active_s.shape, dtype=jnp.int32)
        return K.segment_reduce(vals, w, g, out_cap, kind)

    def reduce_many(asked):
        """One round of the aggregates' reductions: those that are read at the
        sorted segments' ends (counts, exact sums, extremes) travel in one
        gather, whichever aggregates asked for them."""
        results = [None] * len(asked)
        at_ends = [] if global_agg else [i for i, a in enumerate(asked) if K.reads_at_ends(a[0], a[2])]
        if at_ends:
            read = K.segment_reduce_at_ends([asked[i] for i in at_ends], new_group, bounds[1])
            for i, r in zip(at_ends, read):
                results[i] = r
        return [reduce_fn(*a) if r is None else r for a, r in zip(asked, results)]

    def first_fn(vals, w):
        g = gid if gid is not None else jnp.zeros(active_s.shape, dtype=jnp.int32)
        return K.scatter_first(vals, w, g, out_cap)

    def distinct_count_fn(vals_s, w):
        # count distinct via sorted adjacency within each group; rows are
        # group-sorted so re-sorting by (gid primary, value) keeps each group's
        # segment at the same positions (stable sort) — bounds stay valid
        g = gid if gid is not None else jnp.zeros(active_s.shape, dtype=jnp.int32)
        keys2, payloads2 = K.cosort([K.order_key(vals_s), g.astype(jnp.int64)], [w])
        v2 = keys2[0]
        g2 = keys2[1].astype(jnp.int32)
        w2 = payloads2[0]
        prev_same = (v2 == jnp.roll(v2, 1)) & (g2 == jnp.roll(g2, 1))
        prev_same = prev_same.at[0].set(False)
        ws = w2 & ~prev_same
        return K.segment_reduce(
            ws.astype(jnp.int64), ws, g2, out_cap, "count", new_group, bounds
        )

    # HLL replaces the exact cosort when the register state fits; with MANY
    # groups each group has few rows, so the exact path is the cheap one anyway
    # (ref operator/aggregation/ApproximateCountDistinctAggregations)
    hll_fn = None
    if out_cap * (1 << K.HLL_BITS) <= (1 << 23):

        def hll_fn(vals_s, w):  # noqa: F811
            g = gid if gid is not None else jnp.zeros(active_s.shape, dtype=jnp.int32)
            return K.hll_estimate(K.hll_registers(vals_s, w, g, out_cap))

    def percentile_fn(vals_s, w, q_g, nonempty):
        # exact per-group quantile: re-sort by (gid primary, participates,
        # value); stable sort keeps each group's segment at the same positions
        # so ``bounds`` starts stay valid, then one gather at the rank offset
        g = gid if gid is not None else jnp.zeros(active_s.shape, dtype=jnp.int32)
        _, payloads2 = K.cosort(
            [K.order_key(vals_s), (~w).astype(jnp.int8), g.astype(jnp.int64)],
            [vals_s],
        )
        v2 = payloads2[0]
        cap_n = active_s.shape[0]
        starts = bounds[0] if bounds is not None else jnp.zeros((1,), dtype=jnp.int64)
        # clamp the rank to the group's participant prefix: an out-of-range q
        # must never gather across the group boundary
        idx = jnp.floor(
            q_g * jnp.maximum(nonempty - 1, 0).astype(jnp.float64)
        ).astype(jnp.int64)
        idx = jnp.clip(idx, 0, jnp.maximum(nonempty - 1, 0))
        pos = jnp.clip(starts.astype(jnp.int64) + idx, 0, cap_n - 1)
        return v2[pos]

    def tdigest_fn(vals_s, w, nonempty):
        # fixed-K t-digest (TDigestAggregationFunction.java:33, TPU-native):
        # participants sort to each group's segment front; the within-group
        # rank maps through the k1 (arcsine) scale so centroid resolution
        # biases toward the tails, then ONE segment-sum per lane builds all
        # groups' centroids at once
        from ..spi.types import TDIGEST_CENTROIDS as KC

        g = gid if gid is not None else jnp.zeros(active_s.shape, dtype=jnp.int32)
        _, payloads2 = K.cosort(
            [K.order_key(vals_s), (~w).astype(jnp.int8), g.astype(jnp.int64)],
            [vals_s, w],
        )
        v2, w2 = payloads2
        cap_n = active_s.shape[0]
        starts = bounds[0] if bounds is not None else jnp.zeros((1,), dtype=jnp.int64)
        rank = jnp.arange(cap_n, dtype=jnp.int64) - starts[g].astype(jnp.int64)
        n_g = jnp.maximum(nonempty[g], 1).astype(jnp.float64)
        q = (rank.astype(jnp.float64) + 0.5) / n_g
        scale = 0.5 + jnp.arcsin(jnp.clip(2.0 * q - 1.0, -1.0, 1.0)) / jnp.pi
        bucket = jnp.clip((scale * KC).astype(jnp.int32), 0, KC - 1)
        seg = jnp.where(w2, g * KC + bucket, out_cap * KC).astype(jnp.int32)
        sums = jax.ops.segment_sum(
            jnp.where(w2, v2.astype(jnp.float64), 0.0), seg,
            num_segments=out_cap * KC + 1,
        )[: out_cap * KC].reshape(out_cap, KC)
        cnts = jax.ops.segment_sum(
            w2.astype(jnp.float64), seg, num_segments=out_cap * KC + 1
        )[: out_cap * KC].reshape(out_cap, KC)
        means = jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1.0), 0.0)
        return jnp.concatenate([means, cnts], axis=-1)

    def array_agg_fn(vals_s, part, elem_ok, dictionary):
        # scatter each participating row into its group's lane grid
        # [out_cap, agg_w]; lane index = rank among the group's participants
        n = active_s.shape[0]
        g = gid if gid is not None else jnp.zeros((n,), dtype=jnp.int32)
        starts = (
            jnp.clip(bounds[0], 0, n - 1)
            if bounds is not None
            else jnp.zeros((1,), dtype=jnp.int64)
        )
        c = K.cumsum(part.astype(jnp.int32))
        spg = starts[g]
        rank = c - (c[spg] - part[spg].astype(jnp.int32)) - 1
        flat = jnp.where(
            part & (rank < agg_w), g.astype(jnp.int64) * agg_w + rank, out_cap * agg_w
        ).astype(jnp.int32)
        zeros = jnp.zeros((out_cap * agg_w + 1,), dtype=vals_s.dtype)
        data = zeros.at[flat].set(vals_s, mode="drop")[:-1].reshape(out_cap, agg_w)
        evf = jnp.zeros((out_cap * agg_w + 1,), dtype=jnp.bool_)
        ev = evf.at[flat].set(elem_ok, mode="drop")[:-1].reshape(out_cap, agg_w)
        lengths = jnp.minimum(
            reduce_fn(part.astype(jnp.int64), part, "count"), agg_w
        ).astype(jnp.int32)
        return data, ev, lengths

    def map_lanes_fn(kvals, part, vvals, vok, kind):
        """Distinct-key lane grids for map_agg/histogram: re-sort each group's
        participants by key (stable — group segments stay at the same
        positions, so ``bounds`` stays valid), mark the first row of each
        (group, key) run, and scatter keys/values/counts into [out_cap, agg_w]
        (ref operator/aggregation/MapAggAggregation, histogram/Histogram)."""
        n = active_s.shape[0]
        g = gid if gid is not None else jnp.zeros((n,), dtype=jnp.int32)
        starts = (
            jnp.clip(bounds[0], 0, n - 1)
            if bounds is not None
            else jnp.zeros((1,), dtype=jnp.int64)
        )
        payloads = [kvals, part] + ([vvals, vok] if vvals is not None else [])
        keys2, payloads2 = K.cosort(
            [K.order_key(kvals), (~part).astype(jnp.int8), g.astype(jnp.int64)],
            payloads,
        )
        k2, part2 = payloads2[0], payloads2[1]
        knorm2 = keys2[0]
        g2 = keys2[2].astype(jnp.int32)
        prev_same = (
            (knorm2 == jnp.roll(knorm2, 1))
            & (g2 == jnp.roll(g2, 1))
            & jnp.roll(part2, 1)
        )
        prev_same = prev_same.at[0].set(False)
        first = part2 & ~prev_same
        c = K.cumsum(first.astype(jnp.int32))
        spg = starts[g2]
        rank = c - (c[spg] - first[spg].astype(jnp.int32)) - 1
        in_lane = rank < agg_w
        oob = out_cap * agg_w
        flat_first = jnp.where(
            first & in_lane, g2.astype(jnp.int64) * agg_w + rank, oob
        ).astype(jnp.int32)
        kdata = (
            jnp.zeros((oob + 1,), dtype=kvals.dtype)
            .at[flat_first].set(k2, mode="drop")[:-1]
            .reshape(out_cap, agg_w)
        )
        kev = (
            jnp.zeros((oob + 1,), dtype=jnp.bool_)
            .at[flat_first].set(True, mode="drop")[:-1]
            .reshape(out_cap, agg_w)
        )
        lengths = (
            jnp.zeros((out_cap,), dtype=jnp.int32)
            .at[g2].add((first & in_lane).astype(jnp.int32), mode="drop")
        )
        if kind == "histogram":
            flat_all = jnp.where(
                part2 & in_lane, g2.astype(jnp.int64) * agg_w + rank, oob
            ).astype(jnp.int32)
            counts = (
                jnp.zeros((oob + 1,), dtype=jnp.int64)
                .at[flat_all].add(1, mode="drop")[:-1]
                .reshape(out_cap, agg_w)
            )
            return kdata, kev, counts, kev, lengths
        v2, vok2 = payloads2[2], payloads2[3]
        vdata = (
            jnp.zeros((oob + 1,), dtype=v2.dtype)
            .at[flat_first].set(v2, mode="drop")[:-1]
            .reshape(out_cap, agg_w)
        )
        vev = (
            jnp.zeros((oob + 1,), dtype=jnp.bool_)
            .at[flat_first].set(vok2, mode="drop")[:-1]
            .reshape(out_cap, agg_w)
        )
        return kdata, kev, vdata, vev, lengths

    steps = [
        _aggregate_steps(
            rel, agg, agg.output_type, active_s, out_cap, first_fn,
            distinct_count_fn, hll_fn, percentile_fn, tdigest_fn,
            array_agg_fn if agg_w else None,
            map_lanes_fn if agg_w else None,
            broadcast_fn=lambda g: g[
                gid if gid is not None
                else jnp.zeros(active_s.shape, dtype=jnp.int32)
            ],
        )
        for _, agg in aggregations
    ]
    out_cols.extend(_run_aggregates(steps, reduce_many))

    return Page(tuple(out_cols), group_exists)


_jit_aggregate = partial(kernelcost.jit, static_argnums=(0, 1, 2, 3, 4))(
    _aggregate_impl
)


def _direct_aggregate_impl(
    group_keys: Tuple[str, ...],
    aggregations: Tuple[Tuple[str, Aggregation], ...],
    domains: Tuple[int, ...],
    symbols: Tuple[str, ...],
    page: Page,
    pallas_mode: str = "off",
) -> Page:
    """Direct-indexed aggregation for small-domain group keys: gid computed
    elementwise from dictionary codes / bools — NO sort, NO scatter, no host
    sync; every aggregate is one fused [G, n] masked reduction. NULL keys take
    each domain's last slot. Empty key combinations stay inactive rows.
    (ref: BigintGroupByHash small-domain fast path, GroupByHash.java:82-98)"""
    rel = Relation(page, symbols)
    active = page.active
    G = 1
    for d in domains:
        G *= d
    gid = jnp.zeros(page.capacity, dtype=jnp.int32)
    for k, D in zip(group_keys, domains):
        c = rel.column_for(k)
        size = D - 1
        code = jnp.where(
            c.valid, jnp.clip(c.data.astype(jnp.int32), 0, max(size - 1, 0)), size
        )
        gid = gid * D + code

    out_cols: List[Column] = []
    # reconstruct key values from the flat group index (code order)
    codes_rev = []
    rem = jnp.arange(G, dtype=jnp.int32)
    for D in reversed(domains):
        codes_rev.append(rem % D)
        rem = rem // D
    for k, D, code_g in zip(group_keys, domains, codes_rev[::-1]):
        c = rel.column_for(k)
        out_cols.append(
            Column(c.type, code_g.astype(c.data.dtype), code_g < D - 1, c.dictionary)
        )

    # Pallas kernel tier (ops/pallas_kernels.py grouped sums): exact int64
    # sums/counts via 16-bit limb accumulation in native int32 — ONE data pass
    # per reduction instead of int64-emulated [G, n] reductions. min/max and
    # float sums stay on the XLA formulation.
    from ..ops import pallas_kernels as PK

    use_pallas = pallas_mode != "off" and G <= PK.PALLAS_GROUP_LIMIT
    interp = pallas_mode == "interpret"
    if pallas_mode == "tpu" and page.capacity < 32768:
        use_pallas = False  # launch overhead beats the win on tiny pages

    def reduce_fn(vals, w, kind):
        if use_pallas and kind == "count":
            return PK.grouped_sum_i32(w.astype(jnp.int32), w, gid, G, interpret=interp)
        if (
            use_pallas
            and kind == "sum"
            and not jnp.issubdtype(vals.dtype, jnp.floating)
        ):
            return PK.grouped_sum_i64(
                vals.astype(jnp.int64), w, gid, G, interpret=interp
            )
        return K.direct_group_reduce(vals, w, gid, G, kind)

    group_exists = reduce_fn(active.astype(jnp.int64), active, "count") > 0

    def first_fn(vals, w):
        return K.direct_group_first(vals, w, gid, G)

    for sym, agg in aggregations:
        out_cols.append(
            _eval_aggregate(
                rel, agg, agg.output_type, active, G, reduce_fn, first_fn,
                broadcast_fn=lambda g: g[gid],
            )
        )
    return Page(tuple(out_cols), group_exists)


# the plain body stays importable: ops/megakernels.py re-traces it INSIDE the
# fused join kernel (join -> partial-agg fusion), which is what makes the
# fused aggregation bit-identical to this serial formulation by construction
_jit_direct_aggregate = partial(kernelcost.jit, static_argnums=(0, 1, 2, 3, 5))(
    _direct_aggregate_impl
)


def _run_aggregates(steps, reduce_many) -> List[Column]:
    """Run ``_aggregate_steps`` generators round by round: the reductions the
    aggregates are waiting for go to ``reduce_many`` together, as a list of
    ``(vals, weight, kind)``, and each aggregate is sent its own result, until
    every one has returned its Column. What one round asks for depends on no
    result of the same round, so a strategy may compute it in one pass."""
    columns: List[Optional[Column]] = [None] * len(steps)
    asked = {}

    def resume(i, result=None):  # send(None) starts a generator
        try:
            asked[i] = steps[i].send(result)
        except StopIteration as done:
            asked.pop(i, None)
            columns[i] = done.value

    for i in range(len(steps)):
        resume(i)
    while asked:
        order = sorted(asked)
        for i, result in zip(order, reduce_many([asked[i] for i in order])):
            resume(i, result)
    return columns


def _eval_aggregate(
    rel: Relation,
    agg: Aggregation,
    out_type: Type,
    active_s: jnp.ndarray,
    out_cap: int,
    reduce_fn,
    first_fn,
    *strategies,
    **more,
) -> Column:
    """One aggregate by itself: ``_aggregate_steps`` with each reduction it
    asks for answered at once by ``reduce_fn(vals, weight, kind)``."""
    steps = _aggregate_steps(rel, agg, out_type, active_s, out_cap, first_fn, *strategies, **more)
    return _run_aggregates([steps], lambda asked: [reduce_fn(*a) for a in asked])[0]


def _aggregate_steps(
    rel: Relation,
    agg: Aggregation,
    out_type: Type,
    active_s: jnp.ndarray,
    out_cap: int,
    first_fn,
    distinct_count_fn=None,
    hll_fn=None,
    percentile_fn=None,
    tdigest_fn=None,
    array_agg_fn=None,
    map_lanes_fn=None,
    broadcast_fn=None,
):
    """One aggregate, strategy-agnostic, as a generator: it yields each
    per-group reduction it needs as ``(vals, weight, kind)``, is sent the
    result (sort path: read off the sorted segments' ends / gid scatter;
    direct path: [G, n] masked reduce), and returns its Column. ``first_fn``
    gives an arbitrary participating row (ref: operator/aggregation/*, the
    Accumulator bodies)."""
    name = agg.function
    fmask = active_s
    if agg.filter is not None:
        fcol = rel.column_for(agg.filter)
        fmask = fmask & (fcol.data.astype(jnp.bool_) & fcol.valid)

    if name == "count" and not agg.args:
        data = (yield (fmask.astype(jnp.int64), fmask, "count"))
        return Column(BIGINT, data, jnp.ones((out_cap,), dtype=jnp.bool_))

    arg = rel.column_for(agg.args[0])
    vals_s = arg.data
    valid_s = arg.valid
    w = fmask & valid_s
    nonempty = (yield (w.astype(jnp.int64), w, "count"))

    if name == "count":
        return Column(BIGINT, nonempty, jnp.ones((out_cap,), dtype=jnp.bool_))
    if name == "count_if":
        ws = w & vals_s.astype(jnp.bool_)
        data = (yield (ws.astype(jnp.int64), ws, "count"))
        return Column(BIGINT, data, jnp.ones((out_cap,), dtype=jnp.bool_))
    if name in ("$fsum", "$fsumsq"):
        # float64 partial states for distributed stddev/variance (fragmenter)
        x = vals_s.astype(jnp.float64)
        if isinstance(arg.type, DecimalType):
            x = x / float(10**arg.type.scale)
        if name == "$fsumsq":
            x = x * x
        data = (yield (x, w, "sum"))
        return Column(DOUBLE, data, jnp.ones((out_cap,), dtype=jnp.bool_))
    if name in ("sum", "avg"):
        acc_dtype = jnp.float64 if is_floating(arg.type) else jnp.int64
        data = (yield (vals_s.astype(acc_dtype), w, "sum"))
        if name == "avg":
            if isinstance(out_type, DecimalType):
                # decimal avg keeps scale: round-half-up division
                half = nonempty // 2
                denom = jnp.maximum(nonempty, 1)
                data = jnp.where(
                    data >= 0, (data + half) // denom, -((-data + half) // denom)
                )
            else:
                data = data.astype(jnp.float64) / jnp.maximum(nonempty, 1)
                if isinstance(arg.type, DecimalType):
                    data = data / float(10**arg.type.scale)
        return Column(out_type, data.astype(out_type.storage_dtype), nonempty > 0)
    if name in ("min", "max") and vals_s.ndim == 2:
        # Int128 limbs (DECIMAL p>18): per-group extreme of the hi key, then
        # the lo extreme among rows TIED on hi — the min_by broadcast trick
        # (Int128.compareTo semantics, two int64 reduction passes)
        if broadcast_fn is None:
            raise ExecutionError(
                f"{name} over DECIMAL(p>18) needs a group-broadcast strategy"
            )
        from ..ops import int128 as i128

        h, ulo = i128.order_key_pair(vals_s)
        if name == "max":  # order-reversing complement: one code path
            h, ulo = ~h, ~ulo
        sent = jnp.iinfo(jnp.int64).max
        h_ext = (yield (jnp.where(w, h, sent), jnp.ones_like(w), "min"))
        tied = w & (h == broadcast_fn(h_ext))
        l_ext = (yield (jnp.where(tied, ulo, sent), jnp.ones_like(w), "min"))
        if name == "max":
            h_ext, l_ext = ~h_ext, ~l_ext
        data = i128.make(h_ext, l_ext ^ jnp.int64(jnp.iinfo(jnp.int64).min))
        return Column(out_type, data, nonempty > 0)
    if name in ("min", "max"):
        sent = (
            jnp.iinfo(jnp.int64).max if name == "min" else jnp.iinfo(jnp.int64).min
        )
        if jnp.issubdtype(vals_s.dtype, jnp.floating):
            sentf = jnp.inf if name == "min" else -jnp.inf
            masked = jnp.where(w, vals_s, sentf)
        elif vals_s.dtype == jnp.bool_:
            masked = jnp.where(w, vals_s, name == "min")
        else:
            masked = jnp.where(w, vals_s.astype(jnp.int64), sent)
        data = (yield (masked, jnp.ones_like(w), name))
        return Column(
            out_type, data.astype(out_type.storage_dtype), nonempty > 0, arg.dictionary
        )
    if name in ("bool_and", "every"):
        ws = w & ~vals_s.astype(jnp.bool_)
        anyfalse = (yield (ws.astype(jnp.int64), ws, "count"))
        return Column(BOOLEAN, anyfalse == 0, nonempty > 0)
    if name == "bool_or":
        ws = w & vals_s.astype(jnp.bool_)
        anytrue = (yield (ws.astype(jnp.int64), ws, "count"))
        return Column(BOOLEAN, anytrue > 0, nonempty > 0)
    if name in ("arbitrary", "any_value"):
        # any participating row of each group
        data = first_fn(vals_s, w)
        return Column(out_type, data, nonempty > 0, arg.dictionary)
    if name in ("stddev", "stddev_samp", "stddev_pop", "variance", "var_samp", "var_pop"):
        x = vals_s.astype(jnp.float64)
        if isinstance(arg.type, DecimalType):
            x = x / float(10**arg.type.scale)
        s1 = (yield (x, w, "sum"))
        s2 = (yield (x * x, w, "sum"))
        n = jnp.maximum(nonempty, 1).astype(jnp.float64)
        mean = s1 / n
        var_pop = jnp.maximum(s2 / n - mean * mean, 0.0)
        if name in ("var_pop", "stddev_pop"):
            var = var_pop
            valid = nonempty > 0
        else:
            var = var_pop * n / jnp.maximum(n - 1, 1)
            valid = nonempty > 1
        data = jnp.sqrt(var) if name.startswith("stddev") else var
        return Column(DOUBLE, data, valid)
    if name == "approx_distinct" and (hll_fn or distinct_count_fn):
        # HyperLogLog sketch (bounded [G, m] state, one scatter-max) when the
        # register state fits; exact sorted-adjacency count otherwise
        fn = hll_fn if hll_fn is not None else distinct_count_fn
        data = fn(vals_s, w)
        return Column(BIGINT, data, jnp.ones((out_cap,), dtype=jnp.bool_))
    if name in ("tdigest_agg", "qdigest_agg") and tdigest_fn is not None:
        if vals_s.ndim == 2:
            raise ExecutionError(
                "tdigest_agg over DECIMAL(p>18) not supported yet "
                "(cast to DOUBLE or a short decimal)"
            )
        x = vals_s.astype(jnp.float64)
        if isinstance(arg.type, DecimalType):
            x = x / float(10**arg.type.scale)
        data = tdigest_fn(x, w, nonempty)
        return Column(out_type, data, nonempty > 0)
    if name == "approx_percentile" and percentile_fn is not None:
        qcol = rel.column_for(agg.args[1])
        q = qcol.data.astype(jnp.float64)
        if isinstance(qcol.type, DecimalType):
            q = q / float(10**qcol.type.scale)
        # a row participates only if BOTH value and percentile are non-null —
        # the rank count must match the sort's participant mask exactly
        wq = w & qcol.valid
        nq = (yield (wq.astype(jnp.int64), wq, "count"))
        q_g = first_fn(q, wq)
        data = percentile_fn(vals_s, wq, q_g, nq)
        return Column(
            out_type, data.astype(out_type.storage_dtype), nq > 0, arg.dictionary
        )
    if name == "array_agg" and array_agg_fn is not None:
        # NULL elements are kept (Trino default); empty groups yield NULL
        data, ev, lengths = array_agg_fn(vals_s, fmask, fmask & valid_s, arg.dictionary)
        return Column(
            out_type, data, lengths > 0, arg.dictionary,
            lengths=lengths, elem_valid=ev,
        )
    if name in ("map_agg", "histogram") and map_lanes_fn is not None:
        from ..spi.types import ArrayType as _At

        # NULL keys are skipped (Trino map_agg/histogram); groups with no
        # non-null key yield NULL (same convention as array_agg above)
        part = w  # fmask & key validity
        if name == "map_agg":
            varg = rel.column_for(agg.args[1])
            kdata, kev, vdata, vev, lengths = map_lanes_fn(
                vals_s, part, varg.data, varg.valid & part, "map_agg"
            )
            vtype, vdict = varg.type, varg.dictionary
        else:
            kdata, kev, vdata, vev, lengths = map_lanes_fn(
                vals_s, part, None, None, "histogram"
            )
            vtype, vdict = BIGINT, None
        karr = Column(
            _At(element=arg.type), kdata, lengths > 0, arg.dictionary,
            lengths=lengths, elem_valid=kev,
        )
        varr = Column(
            _At(element=vtype), vdata, lengths > 0, vdict,
            lengths=lengths, elem_valid=vev,
        )
        return Column(
            out_type, jnp.zeros((out_cap,), dtype=jnp.int8), lengths > 0,
            lengths=lengths, children=(karr, varr),
        )
    if name == "multimap_agg" and array_agg_fn is not None:
        from ..spi.types import ArrayType as _At

        varg = rel.column_for(agg.args[1])
        kdata, kev, lengths = array_agg_fn(vals_s, w, w, arg.dictionary)
        vdata, vev, _ = array_agg_fn(varg.data, w, w & varg.valid, varg.dictionary)
        karr = Column(
            _At(element=arg.type), kdata, lengths > 0, arg.dictionary,
            lengths=lengths, elem_valid=kev,
        )
        varr = Column(
            _At(element=varg.type), vdata, lengths > 0, varg.dictionary,
            lengths=lengths, elem_valid=vev,
        )
        # placeholder carrying raw lanes; aggregate_relation regroups on host
        return Column(
            out_type, jnp.zeros((out_cap,), dtype=jnp.int8), lengths > 0,
            lengths=lengths, children=(karr, varr),
        )
    if name == "listagg" and array_agg_fn is not None:
        from ..spi.types import ArrayType as _At

        # NULL values are skipped (Trino listagg default ON OVERFLOW ERROR
        # semantics aside); host pass joins lanes with the separator
        data, ev, lengths = array_agg_fn(vals_s, w, w, arg.dictionary)
        lanes = Column(
            _At(element=arg.type), data, lengths > 0, arg.dictionary,
            lengths=lengths, elem_valid=ev,
        )
        return Column(
            out_type, jnp.zeros((out_cap,), dtype=jnp.int32), lengths > 0,
            children=(lanes,),
        )
    def _f64(col, weight):
        x = col.data.astype(jnp.float64)
        if isinstance(col.type, DecimalType):
            x = x / float(10**col.type.scale)
        return jnp.where(weight, x, 0.0)

    if name in ("min_by", "max_by") and broadcast_fn is not None:
        # value of arg0 at the row where arg1 is extremal (ref:
        # operator/aggregation/minmaxby/) — reduce the key's order-key, then
        # pick any row matching the group extreme
        kcol = rel.column_for(agg.args[1])
        wk = fmask & kcol.valid
        key = K.encode_sort_column(kcol.data, kcol.valid, True, False)
        key = jnp.where(wk, key, K.INT64_MAX if name == "min_by" else K.INT64_MIN)
        extreme = (yield (key, wk, "min" if name == "min_by" else "max"))
        at = wk & (key == broadcast_fn(extreme))
        data = first_fn(vals_s, at)
        valid_out = ((yield (wk.astype(jnp.int64), wk, "count")) > 0) & first_fn(
            valid_s, at
        )
        return Column(out_type, data, valid_out, arg.dictionary)
    if name in (
        "corr", "covar_samp", "covar_pop", "regr_slope", "regr_intercept",
        "regr_count", "regr_avgx", "regr_avgy", "regr_sxx", "regr_syy",
        "regr_sxy", "regr_r2",
    ):
        # two-column moments (ref: operator/aggregation/ CorrelationAggregation,
        # CovarianceAggregation, RegressionAggregation): trino argument order
        # is (y, x) with x the independent variable
        xcol = rel.column_for(agg.args[1])
        w2 = fmask & valid_s & xcol.valid
        y = _f64(arg, w2)
        x = _f64(xcol, w2)
        n2 = (yield (w2.astype(jnp.int64), w2, "count"))
        n = jnp.maximum(n2, 1).astype(jnp.float64)
        sx = (yield (x, w2, "sum"))
        sy = (yield (y, w2, "sum"))
        sxy = (yield (x * y, w2, "sum"))
        sxx = (yield (x * x, w2, "sum"))
        syy = (yield (y * y, w2, "sum"))
        cov_pop = sxy / n - (sx / n) * (sy / n)
        varx = jnp.maximum(sxx / n - (sx / n) ** 2, 0.0)
        vary = jnp.maximum(syy / n - (sy / n) ** 2, 0.0)
        if name == "covar_pop":
            data, valid_out = cov_pop, n2 > 0
        elif name == "covar_samp":
            data = cov_pop * n / jnp.maximum(n - 1, 1.0)
            valid_out = n2 > 1
        elif name == "corr":
            denom = jnp.sqrt(varx * vary)
            data = cov_pop / jnp.where(denom > 0, denom, 1.0)
            valid_out = (n2 > 1) & (denom > 0)
        elif name == "regr_slope":
            data = cov_pop / jnp.where(varx > 0, varx, 1.0)
            valid_out = (n2 > 1) & (varx > 0)
        elif name == "regr_intercept":
            slope = cov_pop / jnp.where(varx > 0, varx, 1.0)
            data = sy / n - slope * (sx / n)
            valid_out = (n2 > 1) & (varx > 0)
        elif name == "regr_count":
            return Column(BIGINT, n2, jnp.ones_like(n2, dtype=jnp.bool_))
        elif name == "regr_avgx":
            data, valid_out = sx / n, n2 > 0
        elif name == "regr_avgy":
            data, valid_out = sy / n, n2 > 0
        elif name == "regr_sxx":
            data, valid_out = varx * n, n2 > 0
        elif name == "regr_syy":
            data, valid_out = vary * n, n2 > 0
        elif name == "regr_sxy":
            data, valid_out = cov_pop * n, n2 > 0
        else:  # regr_r2: corr^2; 1.0 when y is constant, NULL when x is
            r2 = jnp.where(
                vary > 0,
                (cov_pop * cov_pop) / jnp.where(
                    varx * vary > 0, varx * vary, 1.0
                ),
                1.0,
            )
            data = r2
            valid_out = (n2 > 0) & (varx > 0)
        return Column(DOUBLE, data, valid_out)
    if name == "entropy":
        # log2 entropy of per-row counts (ref: operator/aggregation/
        # EntropyAggregation): E = log2(S) - sum(c*log2(c)) / S
        c = jnp.maximum(_f64(arg, w), 0.0)
        s = (yield (c, w, "sum"))
        clogc = jnp.where(c > 0, c * jnp.log2(jnp.where(c > 0, c, 1.0)), 0.0)
        sl = (yield (clogc, w, "sum"))
        pos = s > 0
        data = jnp.where(
            pos, jnp.log2(jnp.where(pos, s, 1.0)) - sl / jnp.where(pos, s, 1.0), 0.0
        )
        return Column(DOUBLE, jnp.maximum(data, 0.0), nonempty > 0)
    if name in ("bitwise_and_agg", "bitwise_or_agg", "bitwise_xor_agg"):
        kind = {"bitwise_and_agg": "band", "bitwise_or_agg": "bor",
                "bitwise_xor_agg": "bxor"}[name]
        data = (yield (vals_s.astype(jnp.int64), w, kind))
        return Column(BIGINT, data, nonempty > 0)
    if name in ("skewness", "kurtosis"):
        # central moments from raw power sums (CentralMomentsAggregation)
        x = _f64(arg, w)
        n2 = nonempty
        n = jnp.maximum(n2, 1).astype(jnp.float64)
        s1 = (yield (x, w, "sum"))
        s2 = (yield (x * x, w, "sum"))
        s3 = (yield (x * x * x, w, "sum"))
        m = s1 / n
        M2 = s2 - s1 * m
        M3 = s3 - 3 * s2 * m + 2 * s1 * m * m
        if name == "skewness":
            denom = jnp.power(jnp.maximum(M2, 1e-300), 1.5)
            data = jnp.sqrt(n) * M3 / denom
            valid_out = (n2 > 2) & (M2 > 0)
        else:
            s4 = (yield (x * x * x * x, w, "sum"))
            M4 = s4 - 4 * s3 * m + 6 * s2 * m * m - 3 * s1 * m * m * m
            m2sq = jnp.maximum(M2 * M2, 1e-300)
            data = (n * (n + 1) / jnp.maximum((n - 1) * (n - 2) * (n - 3), 1.0)) * (
                n * M4 / m2sq
            ) - 3 * (n - 1) * (n - 1) / jnp.maximum((n - 2) * (n - 3), 1.0)
            valid_out = (n2 > 3) & (M2 > 0)
        return Column(DOUBLE, data, valid_out)
    if name == "geometric_mean":
        x = _f64(arg, w)
        logs = jnp.where(w, jnp.log(jnp.where(w, x, 1.0)), 0.0)
        s = (yield (logs, w, "sum"))
        n = jnp.maximum(nonempty, 1).astype(jnp.float64)
        return Column(DOUBLE, jnp.exp(s / n), nonempty > 0)
    if name == "checksum":
        # order-insensitive content hash: wrapping sum of mixed value bits
        # (ref ChecksumAggregationFunction; BIGINT here, varbinary there)
        v = vals_s
        if arg.dictionary is not None:
            lut = jnp.asarray(arg.dictionary.value_keys())
            v = lut[jnp.clip(v, 0, lut.shape[0] - 1)]
        hashed = K.splitmix64(K.order_key(v))
        hashed = jnp.where(w, hashed, jnp.int64(0x9E3779B9))
        data = (yield (jnp.where(fmask, hashed, 0), fmask, "sum"))
        # zero-ROW groups return NULL (ref ChecksumAggregationFunction) —
        # but NULL input rows still update the state (the 0x9E3779B9 term
        # above), so the mask counts fmask rows, not non-null ones
        any_rows = (yield (fmask.astype(jnp.int64), fmask, "count"))
        return Column(BIGINT, data, any_rows > 0)
    raise ExecutionError(f"aggregate {name} not implemented")


# --------------------------------------------------------------------------- #
# jitted operator programs (cached per (static plan piece, page layout))
# --------------------------------------------------------------------------- #


def _repeat_column(c: Column, w: int) -> Column:
    return Column(
        c.type,
        jnp.repeat(c.data, w, axis=0),
        jnp.repeat(c.valid, w, axis=0),
        c.dictionary,
        lengths=None if c.lengths is None else jnp.repeat(c.lengths, w, axis=0),
        elem_valid=None if c.elem_valid is None else jnp.repeat(c.elem_valid, w, axis=0),
        children=tuple(_repeat_column(k, w) for k in c.children),
    )


def _flatten_array_col(c: Column, w: int, parent_valid) -> Column:
    """[cap, Wc] array lanes -> [cap*w] element column (pad lanes to w)."""
    wc = c.data.shape[1]
    data = c.data if wc == w else jnp.pad(c.data, ((0, 0), (0, w - wc)))
    ev = c.elem_valid if wc == w else jnp.pad(c.elem_valid, ((0, 0), (0, w - wc)))
    el_t = c.type.element
    return Column(
        el_t,
        data.reshape(-1),
        ev.reshape(-1) & jnp.repeat(parent_valid & c.valid, w),
        c.dictionary,
    )


@partial(kernelcost.jit, static_argnums=(0, 1, 2, 3))
def _jit_unnest(rep_idx, un_idx, w: int, with_ord: bool, page: Page) -> Page:
    from ..spi.types import ArrayType as _At

    cap = page.capacity
    maxlen = jnp.zeros(cap, dtype=jnp.int32)
    for i in un_idx:
        c = page.columns[i]
        lengths = c.lengths if isinstance(c.type, _At) else c.children[0].lengths
        maxlen = jnp.maximum(maxlen, jnp.where(c.valid, lengths, 0))
    lane = jnp.tile(jnp.arange(w, dtype=jnp.int64), cap)
    active = jnp.repeat(page.active, w) & (lane < jnp.repeat(maxlen, w))

    cols: List[Column] = []
    for i in rep_idx:
        cols.append(_repeat_column(page.columns[i], w))
    for i in un_idx:
        c = page.columns[i]
        if isinstance(c.type, _At):
            cols.append(_flatten_array_col(c, w, jnp.ones_like(c.valid)))
        else:  # map -> key, value columns
            keys, vals = c.children
            kc = Column(_At(element=c.type.key), keys.data, c.valid,
                        keys.dictionary, keys.lengths, keys.elem_valid)
            vc = Column(_At(element=c.type.value), vals.data, c.valid,
                        vals.dictionary, vals.lengths, vals.elem_valid)
            cols.append(_flatten_array_col(kc, w, c.valid))
            cols.append(_flatten_array_col(vc, w, c.valid))
    if with_ord:
        cols.append(Column(BIGINT, lane + 1, jnp.ones_like(active)))
    return Page(tuple(cols), active)


@partial(kernelcost.jit, static_argnums=(0,))
def _jit_filter(fn, env: Dict[str, CVal], page: Page) -> Page:
    v = fn(env)
    keep = v.valid & v.data.astype(jnp.bool_)
    return page.mask(keep)


def _project_impl(compiled, env: Dict[str, CVal], page: Page) -> Page:
    cols = []
    for fn, type_, out_dict in compiled:
        v = fn(env)
        dt = type_.storage_dtype
        data = v.data if v.data.dtype == dt else v.data.astype(dt)
        v = CVal(data, v.valid, v.dictionary, v.lengths, v.elem_valid, v.children)
        cols.append(_column_of(type_, v, out_dict))
    return Page(tuple(cols), page.active)


_jit_project = partial(kernelcost.jit, static_argnums=(0,))(_project_impl)


@partial(kernelcost.jit, static_argnums=(0, 6, 8))
def _jit_join_match(
    left_outer: bool, pkeys, bkeys, luts, probe_active, build_active,
    key_bits=None, key_bases=None, merged: bool = False,
):
    """Join phase 1: key normalization + sorted-build matching + emit counts.
    ``key_bits`` / ``key_bases`` (``PlanExecutor._join_key_widths``): where a
    key column's entry is a width, the column is matched as ``value - base``
    in that many bits; a probe value outside that range matches nothing, as
    no live build value lies there. ``merged``: the match stops at the merge
    (``K.join_merge``): ``emit``, ``count`` and ``lo`` come in the merged
    order, with a sixth output, the probe row number of each merged row, and
    the way back is the expansion's (``RanksWay``); the totals carry a fourth
    number, the probe rows that emit."""
    if not pkeys:  # cross join: all-equal keys
        probe_key = [jnp.zeros(probe_active.shape, dtype=jnp.int32)]
        build_key = [jnp.zeros(build_active.shape, dtype=jnp.int32)]
        probe_valid = jnp.ones(probe_active.shape, dtype=jnp.bool_)
        build_valid = jnp.ones(build_active.shape, dtype=jnp.bool_)
        key_bits = None
    else:
        aligned = []
        for (pd, pv), lut in zip(pkeys, luts):
            if lut is not None:
                mapped = lut[jnp.clip(pd, 0, lut.shape[0] - 1)]
                pd, pv = mapped, pv & (mapped >= 0)
            aligned.append((pd, pv))
        probe_key, probe_valid, build_key, build_valid = K.join_keys(
            aligned, list(bkeys)
        )
    if key_bits is not None:
        for i, (bits, base) in enumerate(zip(key_bits, key_bases)):
            if bits is None:
                continue
            rebased = probe_key[i].astype(jnp.int64) - base
            inside = (rebased >= 0) & (rebased < (1 << bits))
            probe_valid = probe_valid & inside
            probe_key[i] = jnp.where(inside, rebased, 0)
            build_key[i] = build_key[i].astype(jnp.int64) - base
    pa = probe_active & probe_valid
    ba = build_active & build_valid
    if merged:
        perm_b, qid, lo, count, live = K.join_merge(build_key, ba, probe_key, pa, probe_active, key_bits)
        emit = jnp.where(live, jnp.maximum(count, 1), 0) if left_outer else count
        totals = jnp.stack([
            jnp.sum(emit.astype(jnp.int64)), jnp.sum(count.astype(jnp.int64)),
            jnp.max(emit).astype(jnp.int64), jnp.sum((emit > 0).astype(jnp.int64)),
        ])
        return emit, count, lo, perm_b, totals, qid
    perm_b, lo, hi, count = K.join_match(build_key, ba, probe_key, pa, key_bits)
    emit = jnp.where(probe_active, jnp.maximum(count, 1), 0) if left_outer else count
    # (rows emitted, matches among them, most rows one probe row emits): the
    # one read that sizes the output carries all three; an outer join's rows
    # emitted beyond its matches are the probe rows no build row matched
    totals = jnp.stack([
        jnp.sum(emit.astype(jnp.int64)), jnp.sum(count.astype(jnp.int64)),
        jnp.max(emit).astype(jnp.int64),
    ])
    return emit, count, lo, perm_b, totals


class RanksWay(NamedTuple):
    """How a join's ranks reach its expansion from the merged order
    (``_jit_join_match`` with ``merged``): ``form`` is ``K.ranks_form``'s,
    ``slots`` the emitting form's listed rows (``K.emitting_ranks``), and
    ``left_outer`` whether a live probe row that matches nothing emits."""

    form: str
    left_outer: bool
    slots: int = 0


def _ranks_way(left_outer: bool, probe_rows: int, build_rows: int, unique: bool, read) -> RanksWay:
    """The way back for a join whose ``sync:join_capacity`` read is ``read``
    (None: nothing was read, the merged form). The emitting form lists the
    probe rows that emit and the probe's last row: one slot more than they."""
    if read is None or probe_rows == 0:
        return RanksWay("merged", left_outer)
    slots = _round_capacity(read[3] + 1)
    form = K.ranks_form(probe_rows + build_rows, probe_rows, slots, K.rank_words(build_rows), unique)
    return RanksWay(form, left_outer, slots if form == "emitting" else 0)


def _expand_join(out_capacity: int, unique: bool, emit, count, lo, perm_b,
                 probe_page: Page, build_page: Page, qid=None, way: Optional[RanksWay] = None):
    """The expansion's columns, probe side then build side (a null-padded
    slot's build columns null), and (probe_idx, matched, out_active) of its
    slots (``K.expand_matches``). In the ``unique`` form ``lo`` and ``count``
    ride the probe columns' one gather to the slots. Where ``way`` is given,
    ``emit``, ``count`` and ``lo`` are in the merged order, ``qid`` the probe
    row of each merged row, and the expansion first brings the ranks back
    (``RanksWay``); either way the slots are the same."""
    if way is not None and way.form == "emitting":
        n, m = probe_page.capacity, perm_b.shape[0]
        e_qid, e_lo, e_count = K.emitting_ranks(qid, lo, count, emit, m, way.slots)
        probe_idx, build_pos, matched, out_active, _ = K.expand_listed(
            e_qid, e_lo, e_count, n, probe_page.active[n - 1], perm_b, out_capacity,
            unique=unique, left_outer=way.left_outer,
        )
        probe_cols, _ = _permute_columns(probe_page.columns, probe_idx)
        return _with_build_columns(probe_cols, build_page, build_pos, matched), probe_idx, matched, out_active
    if way is not None:
        lo, count = K.merged_ranks(qid, lo, count, perm_b.shape[0])
        emit = jnp.where(probe_page.active, jnp.maximum(count, 1), 0) if way.left_outer else count
    if unique:
        probe_idx, out_active = K.unique_slots(emit, out_capacity)
        probe_cols, (lo_at, count_at) = _permute_columns(
            probe_page.columns, probe_idx, extra=(lo, count)
        )
        build_pos, matched = K.unique_build_rows(lo_at, count_at, perm_b, out_active)
    else:
        probe_idx, build_pos, matched, out_active, _ = K.expand_matches(
            emit, count, lo, perm_b, out_capacity
        )
        probe_cols, _ = _permute_columns(probe_page.columns, probe_idx)
    return _with_build_columns(probe_cols, build_page, build_pos, matched), probe_idx, matched, out_active


def _with_build_columns(probe_cols, build_page: Page, build_pos, matched) -> list:
    """The probe's columns at the slots, then the build's at ``build_pos``,
    null where the slot matched nothing."""
    cols = list(probe_cols)
    for pc in _permute_columns(build_page.columns, build_pos)[0]:
        cols.append(replace(pc, valid=pc.valid & matched))
    return cols


@partial(kernelcost.jit, static_argnums=(0, 1, 9))
def _jit_join_expand(
    out_capacity: int, unique: bool, emit, count, lo, perm_b, probe_page: Page, build_page: Page,
    qid=None, way: Optional[RanksWay] = None,
) -> Page:
    cols, _, _, out_active = _expand_join(
        out_capacity, unique, emit, count, lo, perm_b, probe_page, build_page, qid, way
    )
    return Page(tuple(cols), out_active)


@partial(kernelcost.jit, static_argnums=(0, 1, 2, 3, 11))
def _jit_left_join_residual(
    residual_fn,
    symbols: Tuple[str, ...],
    out_capacity: int,
    unique: bool,
    emit,
    count,
    lo,
    perm_b,
    probe_page: Page,
    build_page: Page,
    qid=None,
    way: Optional[RanksWay] = None,
) -> Page:
    """LEFT JOIN with an ON residual: filter the expanded matches, then append
    one null-padded row for every probe row whose matches all failed (including
    rows that never matched — their placeholder also fails the residual)."""
    cols, probe_idx, matched, out_active = _expand_join(
        out_capacity, unique, emit, count, lo, perm_b, probe_page, build_page, qid, way
    )
    env = {s: _cval_of(c) for s, c in zip(symbols, cols)}
    v = residual_fn(env)
    keep = out_active & matched & v.valid & v.data.astype(jnp.bool_)
    expanded = Page(tuple(cols), keep)

    # surviving matches per probe row (probe capacity is small relative to the
    # expansion; scatter-add over probe_idx)
    pcap = probe_page.capacity
    ids = jnp.where(keep, probe_idx, pcap).astype(jnp.int32)
    survivors = (
        jnp.zeros((pcap + 1,), dtype=jnp.int32).at[ids].add(1, mode="drop")[:pcap]
    )
    tail_active = probe_page.active & (survivors == 0)
    tail_cols = list(probe_page.columns)
    for c in build_page.columns:
        tail_cols.append(_null_column(c, pcap))  # tree_map keeps type/dictionary
    tail = Page(tuple(tail_cols), tail_active)
    return _concat_pages([expanded, tail])


@kernelcost.jit
def _jit_full_join_tail(pkeys, bkeys, luts, probe_page: Page, build_page: Page) -> Page:
    """Unmatched-build-rows segment of a FULL OUTER JOIN: build rows whose key
    has no active probe match, with an all-null probe side."""
    aligned = []
    for (pd, pv), lut in zip(pkeys, luts):
        if lut is not None:
            mapped = lut[jnp.clip(pd, 0, lut.shape[0] - 1)]
            pd, pv = mapped, pv & (mapped >= 0)
        aligned.append((pd, pv))
    probe_key, probe_valid, build_key, build_valid = K.join_keys(
        aligned, list(bkeys)
    )
    matched_b = K.semijoin_mask(
        probe_key,
        probe_page.active & probe_valid,
        build_key,
        build_page.active & build_valid,
    )
    active = build_page.active & ~matched_b
    cap = build_page.capacity
    cols = []
    for c in probe_page.columns:  # null probe side, build-capacity shaped
        cols.append(_null_column(c, cap))
    cols.extend(build_page.columns)
    return Page(tuple(cols), active)


@partial(kernelcost.jit, static_argnums=(5,))
def _jit_semijoin(
    skey: Column, fkey: Column, lut, source_page: Page, filtering_active,
    null_aware: bool = False,
):
    sdata = skey.data
    # match_ok gates matching only; a probe string absent from the filtering
    # dictionary (lut -> -1) is a real value that is simply unmatched, not NULL
    match_ok = skey.valid
    if lut is not None:
        sdata = lut[jnp.clip(sdata, 0, lut.shape[0] - 1)]
        match_ok = match_ok & (sdata >= 0)
    mask = K.semijoin_mask(
        K.order_key(fkey.data),
        filtering_active & fkey.valid,
        K.order_key(sdata),
        source_page.active & match_ok,
    )
    if null_aware:
        # IN 3VL: unmatched is NULL when the probe key is NULL or the filtering
        # side contains NULL; x IN (empty) is FALSE even for NULL x.
        has_any = jnp.any(filtering_active)
        has_null = jnp.any(filtering_active & ~fkey.valid)
        valid = mask | ~has_any | (skey.valid & ~has_null)
    else:
        valid = jnp.ones(source_page.active.shape, dtype=jnp.bool_)
    match_col = Column(BOOLEAN, mask, valid)
    return source_page.append_column(match_col)


def _sort_impl(orderings, symbols, count, page: Page) -> Page:
    rel = Relation(page, symbols)
    keys = []
    for o in orderings:
        c = rel.column_for(o.symbol)
        keys.extend(K.encode_sort_columns(c.data, c.valid, o.ascending, o.nulls_first))
    perm, out_active = K.topn_perm(keys, page.active, count)
    if count is not None:
        # slice the permutation BEFORE gathering: TopN gathers `count` rows
        # per column, not full capacity (gathers cost ~60ns/element on TPU)
        n = min(count, page.capacity)
        perm, out_active = perm[:n], out_active[:n]
    cols, _ = _permute_columns(page.columns, perm)
    return Page(cols, out_active)


_jit_sort = partial(kernelcost.jit, static_argnums=(0, 1, 2))(_sort_impl)


@partial(kernelcost.jit, static_argnums=(0, 1, 2, 3))
def _jit_vector_topn(compiled, symbols, orderings, count, env, page: Page) -> Page:
    """The tensor plane's fused scores->top-k program: the scoring
    projection's compiled closures AND the stable top-k permutation in ONE
    device program (ref arXiv:2306.08367 — similarity matmul + selection in
    one launch). Composes the exact serial bodies (_project_impl +
    _sort_impl), so the unfused Project + TopN pair is the bit-identity
    oracle by construction."""
    proj = _project_impl(compiled, env, page)
    return _sort_impl(orderings, symbols, count, proj)


@partial(kernelcost.jit, static_argnums=(0,))
def _jit_vector_topn_lanes(specs, envs, pages):
    """Query-matrix batched vector serving (runtime/device_scheduler.py's
    vector lane tier): the statically-unrolled per-lane fused bodies of a
    whole lane group in ONE device program. Each lane's compiled closures
    close over that lane's OWN query constant — the same trace-time-constant
    environment the serial ``_jit_vector_topn`` folds — and compose the
    exact serial impls, so every lane's output is bit-identical to its own
    serial launch. A runtime ``(n, q)`` stacked query operand is deliberately
    NOT used: XLA constant-folds the constant-query normalization (cosine's
    query norm) differently from the runtime-operand arithmetic in the last
    ulp, which would break the bit-identity contract."""
    out = []
    for (compiled, symbols, orderings, count), env, page in zip(
        specs, envs, pages
    ):
        proj = _project_impl(compiled, env, page)
        out.append(_sort_impl(orderings, symbols, count, proj))
    return tuple(out)


def _result_row_keys(page: Page) -> list:
    """Active rows of a (small, drained) result page as hashable row keys —
    dictionary codes decode to their string values, so pages whose merged
    dictionaries differ (an ANN-pruned read sees fewer splits) still compare
    by content. Host-side; used only by the recall sampler."""
    act = np.asarray(page.active)
    idx = np.nonzero(act)[0]
    cols = []
    for c in page.columns:
        cols.append((np.asarray(c.data), np.asarray(c.valid), c.dictionary))
    keys = []
    for i in idx:
        parts = []
        for data, valid, dic in cols:
            if not valid[i]:
                parts.append(None)
            elif dic is not None:
                parts.append(dic.values[int(data[i])])
            else:
                parts.append(np.asarray(data[i]).tobytes())
        keys.append(tuple(parts))
    return keys


@partial(kernelcost.jit, static_argnums=(0, 1))
def _jit_limit(count: int, offset: int, page: Page) -> Page:
    keep = K.limit_mask(page.active, count, offset)
    return page.mask(keep)


# --------------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------------- #


def _round_capacity(n: int, base: int = 1024) -> int:
    """Bucket output capacities to limit recompilation (powers of two)."""
    cap = base
    while cap < n:
        cap *= 2
    return cap


def _translate_lut(from_dict, to_dict):
    """Host LUT translating codes of ``from_dict`` into ``to_dict`` code space
    (exact match; unmatched -> -1, which never equals a real code)."""
    if from_dict is None or to_dict is None or from_dict is to_dict:
        return None
    lut = np.array([to_dict.code_of(s) for s in from_dict.values], dtype=np.int64)
    return jnp.asarray(lut)


def _string_key_luts(node, probe: Relation, build: Relation):
    luts = []
    for l, r in node.criteria:
        pc = probe.column_for(l)
        bc = build.column_for(r)
        luts.append(_translate_lut(pc.dictionary, bc.dictionary))
    return tuple(luts)


def _unify_dictionaries(cols: List[Column]):
    """(dictionary, data of every chunk in its code space): string chunks
    whose dictionaries differ are re-encoded into one merged sorted
    dictionary (codes are only comparable within one dictionary)."""
    dicts = [c.dictionary for c in cols]
    real = [d for d in dicts if d is not None]
    if not real or not (
        len({id(d) for d in dicts}) > 1 and len({d.fingerprint() for d in real}) > 1
    ):
        return next(iter(real), None), [c.data for c in cols]
    merged_values = sorted(set().union(*[list(d.values) for d in real]))
    dictionary = Dictionary(np.asarray(merged_values, dtype=object))
    code_of = {s: c for c, s in enumerate(merged_values)}
    datas = []
    for c in cols:
        if c.dictionary is None:
            # dictionary-less string chunk (e.g. all-NULL branch of a
            # grouping-sets union): codes are meaningless, map to 0
            datas.append(jnp.zeros_like(c.data))
            continue
        lut = np.array([code_of[s] for s in c.dictionary.values], dtype=np.int32)
        datas.append(jnp.asarray(lut)[jnp.clip(c.data, 0, len(lut) - 1)])
    return dictionary, datas


def _concat_cols(cols: List[Column], type_: Type) -> Column:
    """Concatenate column chunks: merges differing string dictionaries, pads
    array lanes to the widest W, and recurses into map/row children."""
    from ..spi.types import ArrayType as _At, MapType as _Mt, RowType as _Rt

    dictionary, datas = _unify_dictionaries(cols)
    valids = [c.valid for c in cols]

    if isinstance(type_, _At):
        w = max(d.shape[1] for d in datas)
        datas = [
            d if d.shape[1] == w else jnp.pad(d, ((0, 0), (0, w - d.shape[1])))
            for d in datas
        ]
        evs = [
            c.elem_valid
            if c.elem_valid.shape[1] == w
            else jnp.pad(c.elem_valid, ((0, 0), (0, w - c.elem_valid.shape[1])))
            for c in cols
        ]
        return Column(
            type_, jnp.concatenate(datas), jnp.concatenate(valids), dictionary,
            lengths=jnp.concatenate([c.lengths for c in cols]),
            elem_valid=jnp.concatenate(evs),
        )
    if isinstance(type_, (_Mt, _Rt)):
        kid_types = type_.child_types()
        kids = tuple(
            _concat_cols([c.children[k] for c in cols], kt)
            for k, kt in enumerate(kid_types)
        )
        lengths = (
            None
            if cols[0].lengths is None
            else jnp.concatenate([c.lengths for c in cols])
        )
        return Column(
            type_, jnp.concatenate(datas), jnp.concatenate(valids), None,
            lengths=lengths, children=kids,
        )
    return Column(type_, jnp.concatenate(datas), jnp.concatenate(valids), dictionary)


def _concat_union_pages(pages: List[Page], types: List[Type]) -> Page:
    cols = [
        _concat_cols([p.columns[i] for p in pages], type_)
        for i, type_ in enumerate(types)
    ]
    active = jnp.concatenate([p.active for p in pages])
    return Page(tuple(cols), active)
