"""Estimate<->actual statistics feedback plane.

Reference blueprint: Presto's history-based optimization (HBO —
presto-main's HistoryBasedPlanStatisticsCalculator keyed on canonicalized
plan fragments) and Trino's anticipated `EXPLAIN ANALYZE` estimate/actual
rendering. The round-7 observability plane attributes *time*; this module
closes the loop on *cardinality*:

- **actuals collection**: executors stash each plan node's output ``active``
  mask (one dict store per operator per page — no device op, no host sync on
  the hot path); :func:`observe_query` folds them into the per-query
  ``QueryStatsCollector`` once the query has drained.
- **when the feedback runs** (:class:`Feedback`): on the served path, where
  the ``QueryManager`` offers a place (:class:`deferring_feedback`), after
  the statement is FINISHED and its root span has closed, on the manager's
  pool: between a statement's submission and its answer the plane costs
  the row-count scalars the executor launches and nothing else. A caller
  that offers no place (a direct ``LocalQueryRunner.execute``, ``EXPLAIN
  ANALYZE``, the distributed runner's fragments) gets it inline, after the
  drain and before ``execute`` returns. Whatever reads what the feedback
  writes (:func:`operator_stats_log`, :func:`load_history`, :func:`lookup`,
  a query's ``planNodes``) joins the pending feedback first
  (:func:`join_pending`), so no reader sees less than it did inline.
- **history store**: per-node estimate-vs-actual records persisted under the
  capstore structural plan fingerprint (``$TRINO_TPU_STATS_HISTORY`` file,
  atomic-rename merge-on-write; bounded in-process dict otherwise). Entries
  are content-addressed two ways so the next planning of a matching shape
  can find them:

  * ``s:<sha>`` — exact structural subtree fingerprint (plancodec encoding,
    the capstore contract), and
  * ``l:<sha>`` — a canonical *filtered-leaf* key (table + conjuncts over
    COLUMN names), robust against symbol renaming, column pruning, and
    constraint absorption — the key join reordering looks up mid-optimize,
    before the final plan shape exists.

- **mis-estimate detection**: every folded node computes a smoothed q-error
  ``max(est, act) / min(est, act)`` (floored at 1 row); nodes past the
  ``qerror_threshold`` session knob emit ``cardinality_misestimate`` flight
  events and Prometheus counters/histograms. Recent per-node rows land in a
  bounded process ring surfaced as ``system.runtime.operator_stats``; the
  history store itself is ``system.optimizer.stats_history``.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Sequence

from .. import knobs

ENV_VAR = "TRINO_TPU_STATS_HISTORY"

# ------------------------------------------------------------ query identity

_qid_tls = threading.local()


def current_query_id() -> Optional[str]:
    return getattr(_qid_tls, "qid", None)


class query_id_scope:
    """Install a query id on this thread (the QueryManager wraps execution
    in one) so operator-stats rows join against system.runtime.queries;
    embedded runs without a manager fall back to the trace id."""

    def __init__(self, query_id: str):
        self.query_id = query_id

    def __enter__(self):
        self._prev = getattr(_qid_tls, "qid", None)
        _qid_tls.qid = self.query_id
        return self

    def __exit__(self, *exc):
        _qid_tls.qid = self._prev
        return False

# ------------------------------------------------- when the feedback runs

_feedback_tls = threading.local()
# feedback handed to a caller and not yet run, in the order the statements
# finished: a run takes its elders first, so records land in that order
_PENDING: "Dict[Feedback, None]" = {}
_PENDING_LOCK = threading.Lock()


class deferring_feedback:
    """Offer this thread's statements a place to run their feedback later:
    inside the scope a runner hands each :class:`Feedback` over (the list
    ``with`` gives) and does not run it; the caller runs them once the
    statement's answer is out. Outside any scope the feedback runs inline."""

    def __enter__(self) -> "List[Feedback]":
        self._prev = getattr(_feedback_tls, "sink", None)
        sink = _feedback_tls.sink = []
        return sink

    def __exit__(self, *exc):
        _feedback_tls.sink = self._prev
        return False


def feedback_is_deferred() -> bool:
    """Whether this thread's caller has offered a place to run feedback."""
    return getattr(_feedback_tls, "sink", None) is not None


@contextmanager
def feedback_span(query_id: str, deferred: bool = False):
    """One statement's feedback on the statement timeline and in the metrics
    registry: the span ``stats_feedback`` (a root of its own tree when
    deferred, since its statement's root has closed; under the current span
    when inline) and ``trino_tpu_stats_feedback_total{path}`` /
    ``trino_tpu_stats_feedback_seconds``. A thread inside one joins no
    pending feedback: it is one, and would wait for itself."""
    from .tracing import STATS_FEEDBACK, TRACER

    scope = TRACER.root if deferred else TRACER.span
    prev = getattr(_feedback_tls, "running", False)
    _feedback_tls.running = True
    t0 = time.perf_counter()
    try:
        with scope(STATS_FEEDBACK, query_id=query_id, deferred=deferred) as span:
            yield span
    finally:
        _feedback_tls.running = prev
        _metric_feedback("deferred" if deferred else "inline").inc()
        _metric_feedback_seconds().observe(time.perf_counter() - t0)


class Feedback:
    """One executed statement's feedback, run once: the executor's actuals
    resolved to integers and :func:`observe_query`. It holds the plan, the
    collector and ``finalize`` (a closure over the executor's actuals: 4-byte
    device scalars and masks of at most ``_NULL_FRAC_CAP`` rows), not the
    executor or its pages. ``run()`` inline; ``defer()`` hands it to the
    place the caller offered, and then the first of the manager's pool and
    any reader to get to it runs it. Failures are swallowed: observability
    never fails a statement."""

    def __init__(self, plan, metadata, session, collector,
                 finalize: Callable[[], Dict[int, dict]], query_id: str = ""):
        self.plan = plan
        self.metadata = metadata
        self.session = session
        self.collector = collector
        self.finalize = finalize
        self.query_id = query_id
        # the query's stats snapshot, taken before a deferred run: the run
        # writes the collector's planNodes into it
        self.snapshot: Optional[dict] = None
        self.deferred = False
        self._holds_recorder = False
        self._claim = threading.Lock()
        self._done = threading.Event()

    def defer(self, snapshot: Optional[dict] = None,
              hold_recorder: bool = False) -> None:
        """Hands it to the place the caller offered. ``hold_recorder``: the
        statement recorded its flight (``flight_recorder``), so the recorder
        stays on until the feedback's events are in it too."""
        from .observability import RECORDER

        self.deferred = True
        self.snapshot = snapshot
        if hold_recorder:
            RECORDER.acquire()
            self._holds_recorder = True
        with _PENDING_LOCK:
            _PENDING[self] = None
        _feedback_tls.sink.append(self)

    def run(self) -> None:
        """Runs the feedback if nobody has, else waits for who did. A
        deferred one first takes those handed over before it."""
        if self.deferred and not self._done.is_set():
            _join(upto=self)
        self._run_once()

    def _run_once(self) -> None:
        if not self._claim.acquire(blocking=False):
            self._done.wait()
            return
        try:
            with feedback_span(self.query_id, self.deferred) as span:
                actuals = self.finalize()
                span.attributes["nodes"] = len(actuals)
                observe_query(
                    self.plan, self.metadata, self.session, self.collector,
                    actuals, query_id=self.query_id,
                )
            if self.snapshot is not None:
                self.snapshot["planNodes"] = (
                    self.collector.snapshot()["planNodes"]
                )
        except Exception:  # noqa: BLE001 — observability only
            pass
        finally:
            with _PENDING_LOCK:
                _PENDING.pop(self, None)
            if self._holds_recorder:
                from .observability import RECORDER

                RECORDER.release()
            # what it held of the statement goes with it
            self.plan = self.collector = self.finalize = None
            self._done.set()


def _join(upto: "Optional[Feedback]" = None) -> None:
    if getattr(_feedback_tls, "running", False):
        return
    with _PENDING_LOCK:
        pending = list(_PENDING)
    for fb in pending:
        if fb is upto:
            break
        fb._run_once()


def join_pending() -> None:
    """Readers of what the feedback writes call this first: every feedback
    handed over so far has run when it returns. One not yet started is run
    here, so nothing waits on a thread that may never come."""
    _join()


# in-process fallback store, bounded (oldest fingerprints evicted) so a
# long-lived coordinator recording every query shape cannot grow unbounded
_MAX_MEMORY_ENTRIES = 4096
_lock = threading.Lock()
_memory_store: "Dict[str, dict]" = {}

# bounded ring of recent per-node actuals: system.runtime.operator_stats
_OP_STATS: deque = deque(maxlen=4096)
_OP_STATS_LOCK = threading.Lock()


# --------------------------------------------------------------------------- #
# q-error
# --------------------------------------------------------------------------- #


def q_error(estimate: Optional[float], actual: Optional[float]) -> Optional[float]:
    """Smoothed multiplicative estimation error: max(e, a) / min(e, a) with
    both sides floored at one row — always finite, 1.0 = perfect."""
    if estimate is None or actual is None:
        return None
    e = max(float(estimate), 1.0)
    a = max(float(actual), 1.0)
    return max(e / a, a / e)


# --------------------------------------------------------------------------- #
# canonical keys
# --------------------------------------------------------------------------- #


class _Uncanonical(Exception):
    """Expression/subtree outside the canonical grammar — no leaf key."""


def _canon_expr(expr, sym_to_col: Dict[str, str]) -> str:
    """Render an IR expression with symbols replaced by COLUMN names — the
    symbol-allocation-independent form two plannings of the same SQL agree
    on. Raises :class:`_Uncanonical` for shapes we can't translate."""
    from ..sql.ir import Call, CastExpr, Constant, InLut, Reference

    if isinstance(expr, Reference):
        col = sym_to_col.get(expr.symbol)
        if col is None:
            raise _Uncanonical(expr.symbol)
        return f"@{col}"
    if isinstance(expr, Constant):
        return repr(expr.value)
    if isinstance(expr, Call):
        args = ",".join(_canon_expr(a, sym_to_col) for a in expr.args)
        return f"{expr.name}({args})"
    if isinstance(expr, CastExpr):
        t = expr.type.display() if expr.type is not None else "?"
        return f"cast({_canon_expr(expr.value, sym_to_col)} as {t})"
    if isinstance(expr, InLut):
        # the LUT is dictionary-local; the description carries the predicate
        return f"inlut({_canon_expr(expr.value, sym_to_col)},{expr.description!r})"
    raise _Uncanonical(type(expr).__name__)


def _peel_to_scan(node):
    """Walk Filter/identity-Project chains down to a TableScan, collecting
    filter conjuncts along the way. Returns (scan, conjuncts) or None."""
    from ..planner.logical_planner import split_conjuncts
    from ..planner.plan import FilterNode, ProjectNode, TableScanNode

    conjuncts: List[object] = []
    cur = node
    while True:
        if isinstance(cur, TableScanNode):
            return cur, conjuncts
        if isinstance(cur, FilterNode):
            conjuncts.extend(split_conjuncts(cur.predicate))
            cur = cur.source
            continue
        if isinstance(cur, ProjectNode) and cur.is_identity():
            cur = cur.source
            continue
        return None


def leaf_key_for(leaf, extra_conjuncts: Sequence[object] = ()) -> Optional[str]:
    """Canonical key of a filtered scan: table + sorted conjuncts rendered
    over column names. ``extra_conjuncts`` lets join reordering ask about a
    (bare leaf + pending WHERE conjuncts) combination before the filter node
    exists. Ignores absorbed scan constraints and pruned column lists — both
    are derived from the same conjuncts, so the key stays stable across the
    optimizer passes that introduce them."""
    peeled = _peel_to_scan(leaf)
    if peeled is None:
        return None
    scan, conjuncts = peeled
    conjuncts = list(conjuncts) + list(extra_conjuncts)
    sym_to_col = {s: c for s, c in scan.assignments}
    try:
        parts = sorted(_canon_expr(c, sym_to_col) for c in conjuncts)
    except _Uncanonical:
        return None
    h = scan.table
    text = f"{h.catalog}.{h.schema_table}"
    if scan.limit is not None:
        text += f"|limit={scan.limit}"
    # an ABSORBED constraint changes what the scan emits even when no
    # conjunct survives above it (connectors prune splits / render WHERE),
    # so it must key separately from a bare scan of the table — otherwise a
    # constrained scan's reduced actual would overlay unfiltered scans.
    # Frozen-dataclass reprs are deterministic, which is all a hash needs.
    domains = getattr(scan.constraint, "domains", ()) or ()
    if domains:
        text += "|" + ";".join(
            sorted(f"{col}={dom!r}" for col, dom in domains)
        )
    text += "|" + ";".join(parts)
    return "l:" + hashlib.sha256(text.encode()).hexdigest()[:16]


def node_fingerprint(node) -> str:
    """Exact structural subtree fingerprint (the capstore plan-fingerprint
    contract applied per node). Empty string when the subtree holds types
    outside the plancodec registry — no key, no persistence."""
    from .plancodec import fingerprint

    fp = fingerprint(node)
    return ("s:" + fp[:16]) if fp else ""


# --------------------------------------------------------------------------- #
# history store (capstore-modeled: env-pointed JSON file, atomic rename,
# merge-on-write; bounded in-process dict otherwise)
# --------------------------------------------------------------------------- #


def history_path() -> Optional[str]:
    return knobs.env_path(ENV_VAR)


# mtime-keyed read cache: make_estimator loads the history on every planned
# query (twice per optimize() — join reordering builds its own estimator);
# re-parsing the whole JSON file each time would scale planning cost with
# store size. Guarded by _lock.
_file_cache: "Dict[str, tuple]" = {}  # path -> (mtime_ns, data)


def _split_object(path: str):
    """(filesystem, key Location) for an ``object://`` history path."""
    from ..fs import Location
    from .objectstore import backend_for_root

    base, _, name = str(path).rstrip("/").rpartition("/")
    fs, _ = backend_for_root(base)
    return fs, Location("object", name)


def _read_object_locked(path: str) -> Dict[str, dict]:
    """Object-backend read: the etag plays the mtime's cache-key role (no
    stat on an object store — the GET returns content + etag together and
    per-key reads are strongly consistent)."""
    fs, loc = _split_object(path)
    try:
        raw, etag = fs.read_with_etag(loc)
    except OSError:
        return {}
    cached = _file_cache.get(path)
    if cached is not None and cached[0] == etag:
        return cached[1]
    try:
        data = json.loads(raw.decode())
    except ValueError:
        from .ha import note_torn_record

        note_torn_record()
        return {}
    if not isinstance(data, dict):
        return {}
    _file_cache.clear()
    _file_cache[path] = (etag, data)
    return data


def _read_file_locked(path: str) -> Dict[str, dict]:
    from .objectstore import is_object_uri

    if is_object_uri(path):
        return _read_object_locked(path)
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return {}
    cached = _file_cache.get(path)
    if cached is not None and cached[0] == mtime:
        return cached[1]
    try:
        with open(path, "r") as f:
            data = json.load(f)
    except OSError:
        return {}
    except ValueError:
        # a truncated store (killed mid-write before the atomic rename
        # landed, or external corruption): recover cold instead of crashing
        from .ha import note_torn_record

        note_torn_record()
        return {}
    if not isinstance(data, dict):
        return {}
    _file_cache.clear()  # one live path; a test switching files must not pin
    _file_cache[path] = (mtime, data)
    return data


def load_history() -> Dict[str, dict]:
    """Full key -> entry map (the overlay estimator and the system table
    both read it). A snapshot: mutations go through :func:`record_history`."""
    join_pending()
    path = history_path()
    with _lock:
        if path is None:
            return dict(_memory_store)
        return dict(_read_file_locked(path))


def lookup(key: str) -> Optional[dict]:
    if not key:
        return None
    join_pending()
    path = history_path()
    with _lock:
        if path is None:
            ent = _memory_store.get(key)
        else:
            ent = _read_file_locked(path).get(key)
        return dict(ent) if ent else None


def _evict_oldest(data: Dict[str, dict]) -> None:
    """Bound the store (memory AND file): beyond the cap, drop the
    least-recently-updated entries — unbounded growth in a long-lived
    coordinator recording every query shape is the failure mode."""
    if len(data) <= _MAX_MEMORY_ENTRIES:
        return
    by_age = sorted(data, key=lambda k: data[k].get("updated_at", 0.0))
    for key in by_age[: len(data) - _MAX_MEMORY_ENTRIES]:
        del data[key]


def record_history(entries: Dict[str, dict]) -> None:
    """Merge per-node records into the store. Existing entries keep their
    run counter; the latest actual wins (executions are deterministic, and
    the newest observation reflects the current catalog state)."""
    if not entries:
        return
    path = history_path()
    with _lock:
        if path is None:
            data = _memory_store
        else:
            data = dict(_read_file_locked(path))
        for key, ent in entries.items():
            prev = data.get(key)
            if prev:
                ent = dict(ent)
                ent["runs"] = int(prev.get("runs", 0)) + 1
            data[key] = ent
        _evict_oldest(data)
        if path is None:
            return
        from .objectstore import is_object_uri

        if is_object_uri(path):
            # CAS merge-on-write (mirrors capstore): a lost etag race
            # re-reads and re-merges, so concurrent recorders never drop
            # each other's keys on the rename-free substrate
            fs, loc = _split_object(path)
            for _ in range(16):
                body = json.dumps(data).encode()
                try:
                    _, etag = fs.read_with_etag(loc)
                except OSError:
                    etag = None
                if etag is None:
                    if fs.write_if_absent(loc, body):
                        break
                elif fs.write_if_match(loc, body, etag) is not None:
                    break
                merged = dict(_read_object_locked(path))
                merged.update(data)
                _evict_oldest(merged)
                data = merged
            _file_cache.clear()
            _file_cache[path] = (hashlib.md5(body).hexdigest(), data)
            return
        d = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".statstore-")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(data, f)
            os.replace(tmp, path)
            _file_cache.clear()
            try:
                _file_cache[path] = (os.stat(path).st_mtime_ns, data)
            except OSError:
                pass
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def clear_memory() -> None:
    """Test hook: drop the in-process store, read cache, and the
    operator-stats ring (what is pending writes into them first, and not
    later into the next test's)."""
    join_pending()
    with _lock:
        _memory_store.clear()
        _file_cache.clear()
    with _OP_STATS_LOCK:
        _OP_STATS.clear()


# --------------------------------------------------------------------------- #
# operator-stats ring (system.runtime.operator_stats)
# --------------------------------------------------------------------------- #


def operator_stats_log() -> List[dict]:
    join_pending()
    with _OP_STATS_LOCK:
        return list(_OP_STATS)


def _log_operator_stats(rows: List[dict]) -> None:
    with _OP_STATS_LOCK:
        _OP_STATS.extend(rows)


# --------------------------------------------------------------------------- #
# the feedback step
# --------------------------------------------------------------------------- #


def _session_float(session, name: str, default: float) -> float:
    try:
        return float(session.get(name))
    except (KeyError, TypeError, ValueError):
        return default


def _session_bool(session, name: str, default: bool) -> bool:
    try:
        return bool(session.get(name))
    except KeyError:
        return default


def merge_actuals(dst: Dict[int, dict], src: Dict[int, dict]) -> None:
    """Fold one executor's finalized actuals into a query-level rollup
    (fragment partitions sum; null fractions average weighted by rows)."""
    for key, ent in src.items():
        cur = dst.get(key)
        if cur is None:
            dst[key] = dict(ent)
            continue
        old_rows, new_rows = cur.get("rows", 0), ent.get("rows", 0)
        a, b = cur.get("null_frac"), ent.get("null_frac")
        if a is not None or b is not None:
            total = old_rows + new_rows
            cur["null_frac"] = (
                ((a or 0.0) * old_rows + (b or 0.0) * new_rows) / total
                if total else (a if a is not None else b)
            )
        cur["rows"] = old_rows + new_rows
        cur["capacity"] = cur.get("capacity", 0) + ent.get("capacity", 0)
        cur["bytes"] = cur.get("bytes", 0) + ent.get("bytes", 0)
        for k in ("dyn_pre", "dyn_post"):
            if k in cur or k in ent:
                cur[k] = cur.get(k, 0) + ent.get(k, 0)


def observe_query(
    plan,
    metadata,
    session,
    collector,
    actuals: Dict[int, dict],
    query_id: str = "",
    fragment: Optional[int] = None,
) -> None:
    """Fold executed per-node actuals into the collector, detect
    mis-estimates, and feed the history store.

    ``actuals``: id(plan node) -> {"rows", "capacity", "bytes",
    "null_frac", join-only "dyn_pre"/"dyn_post"} as produced by
    ``PlanExecutor.finalize_actuals`` (merged with :func:`merge_actuals`
    for multi-partition runs). ``fragment``: distributed callers observe
    once per fragment (actuals pre-aggregated across partitions and FTE
    attempts — only the winning attempt of a speculative pair was folded
    in). Runs once per query AFTER the result drained: on the served path
    after the statement is FINISHED (:class:`Feedback`, deferred), and
    inline, between the drain and the caller's return, everywhere else.
    """
    from ..planner.plan import JoinNode, visit_plan
    from ..planner.stats import make_estimator
    from .observability import RECORDER

    if not actuals:
        return
    estimator = make_estimator(metadata, plan.types, session)
    threshold = _session_float(session, "qerror_threshold", 2.0)
    record = _session_bool(session, "statistics_feedback", True)
    now = time.time()

    ordered: List[object] = []
    visit_plan(plan.root, ordered.append)

    history: Dict[str, dict] = {}
    ring_rows: List[dict] = []
    misestimates = 0
    plan_fp = node_fingerprint(plan.root)

    # `query_id` makes the span a window of the cluster trace's attribution
    # (clusterobs.filter_events_for_query): a deferred run is outside its
    # statement's `query_exec` window
    with RECORDER.span(
        "stats_feedback", "stats", query=query_id, query_id=query_id
    ):
        for idx, node in enumerate(ordered):
            ent = actuals.get(id(node))
            if ent is None:
                continue
            kind = type(node).__name__
            act = int(ent.get("rows", 0))
            try:
                est = estimator.rows(node)
            except Exception:  # noqa: BLE001 — estimation must never fail a query
                est = None
            q = q_error(est, act)
            input_rows = sum(
                int(actuals[id(s)].get("rows", 0))
                for s in node.sources
                if id(s) in actuals
            )
            build_rows = None
            dyn_sel = None
            if isinstance(node, JoinNode):
                build = actuals.get(id(node.right))
                if build is not None:
                    build_rows = int(build.get("rows", 0))
                if ent.get("dyn_pre"):
                    dyn_sel = float(ent.get("dyn_post", 0)) / float(ent["dyn_pre"])
            key = f"{idx}:{kind}" if fragment is None else f"f{fragment}.{idx}:{kind}"
            collector.add_node(
                key,
                kind=kind,
                actual_rows=act,
                estimated_rows=est,
                q_error=q,
                input_rows=input_rows,
                output_bytes=int(ent.get("bytes", 0)),
                null_fraction=ent.get("null_frac"),
                build_rows=build_rows,
                dynamic_filter_selectivity=dyn_sel,
            )
            ring_rows.append({
                "query_id": query_id,
                "node_id": idx,
                "fragment": fragment,
                "kind": kind,
                "estimate": est,
                "actual": act,
                "input_rows": input_rows,
                "bytes": int(ent.get("bytes", 0)),
                "null_frac": ent.get("null_frac"),
                "build_rows": build_rows,
                "dyn_filter_sel": dyn_sel,
                "qerror": q,
                "ts": now,
            })
            if q is not None:
                _metric_histogram().observe(q)
                if q > threshold:
                    misestimates += 1
                    _metric_counter().inc()
                    RECORDER.instant(
                        "cardinality_misestimate", "stats",
                        node=key, estimate=est, actual=act,
                        q=round(q, 3), query=query_id,
                    )
            if record:
                h = node.table if kind == "TableScanNode" else None
                base = {
                    "kind": kind,
                    "plan": plan_fp,
                    "table": f"{h.catalog}.{h.schema_table}" if h else None,
                    "estimate": est,
                    "actual": act,
                    "qerror": q,
                    "runs": 1,
                    "updated_at": now,
                }
                fp = node_fingerprint(node)
                if fp:
                    history[fp] = dict(base)
                lk = leaf_key_for(node)
                if lk:
                    history[lk] = dict(base)
    _log_operator_stats(ring_rows)
    if record:
        record_history(history)


_metric_cache: Dict[str, object] = {}


def _metric_counter():
    m = _metric_cache.get("counter")
    if m is None:
        from .metrics import REGISTRY

        m = _metric_cache["counter"] = REGISTRY.counter(
            "trino_tpu_cardinality_misestimates_total",
            help="plan nodes whose actual rows exceeded the q-error threshold",
        )
    return m


def _metric_histogram():
    m = _metric_cache.get("histogram")
    if m is None:
        from .metrics import REGISTRY

        m = _metric_cache["histogram"] = REGISTRY.histogram(
            "trino_tpu_cardinality_qerror",
            help="per-node cardinality q-error (estimate vs actual)",
            buckets=(1.0, 1.5, 2.0, 4.0, 8.0, 16.0, 64.0, 256.0, 1024.0),
        )
    return m


def _metric_feedback(path: str):
    m = _metric_cache.get("feedback_" + path)
    if m is None:
        from .metrics import REGISTRY

        m = _metric_cache["feedback_" + path] = REGISTRY.counter(
            "trino_tpu_stats_feedback_total", labels={"path": path},
            help="statements' statistics feedback run, by when: deferred "
                 "(after FINISHED, off the statement's path) or inline",
        )
    return m


def _metric_feedback_seconds():
    m = _metric_cache.get("feedback_seconds")
    if m is None:
        from .metrics import DEFAULT_BUCKETS, REGISTRY

        m = _metric_cache["feedback_seconds"] = REGISTRY.histogram(
            "trino_tpu_stats_feedback_seconds",
            help="seconds one statement's statistics feedback took",
            buckets=DEFAULT_BUCKETS,
        )
    return m
