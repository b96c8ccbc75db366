"""Query lifecycle management: state machine, tracking, async execution.

Reference blueprint: io.trino.execution.QueryStateMachine (QueryStateMachine.java:131
over StateMachine.java:43; states QUEUED...FINISHED), QueryTracker.java:51 (expiry),
DispatchManager.createQuery (DispatchManager.java:176). SURVEY.md §2.6.

Event plane: the full Trino EventListener lifecycle — ``query_created`` at
submit, ``query_state_change`` on every transition, ``split_completed`` from
the executor's split boundaries, ``query_completed`` on the terminal
transition — dispatched in state-machine order with per-listener exception
isolation (EventListenerManager semantics: a throwing listener is logged and
skipped, never wedges the state machine or starves later listeners).

History: terminal queries stay queryable (``system.runtime.queries``,
``GET /v1/query/{id}``) in a bounded completed-query ring —
``TRINO_TPU_QUERY_HISTORY`` env, default 100 — instead of vanishing at the
old expiry sweep.
"""

from __future__ import annotations

import threading
import time
import traceback
import uuid
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, Dict, List, Optional

from .. import knobs

DEFAULT_HISTORY = 100


class QueryState(Enum):
    QUEUED = "QUEUED"
    PLANNING = "PLANNING"
    RUNNING = "RUNNING"
    FINISHED = "FINISHED"
    FAILED = "FAILED"
    CANCELED = "CANCELED"

    @property
    def is_done(self) -> bool:
        return self in (QueryState.FINISHED, QueryState.FAILED, QueryState.CANCELED)


class QueryNotFound(KeyError):
    """cancel/kill of an unknown query id (-> HTTP 404 at the coordinator)."""

    def __init__(self, query_id: str):
        super().__init__(query_id)
        self.query_id = query_id

    def __str__(self):
        return f"query not found: {self.query_id}"


class CancelResult(Enum):
    """Outcome of cancel()/kill(): the query transitioned, or it was already
    in a terminal state (-> HTTP 409 on the admin API; unknown ids raise
    QueryNotFound instead of collapsing into the same bare False)."""

    CANCELED = "CANCELED"
    TERMINAL = "TERMINAL"


# the statement's spans between admission and its first page
_EXEC_SPANS = ("parse", "planner", "optimizer", "execution", "drain", "encode")


@dataclass
class QueryStats:
    create_time: float = field(default_factory=time.time)
    end_time: Optional[float] = None
    # CPU seconds of the pool thread that ran the statement (thread_time:
    # XLA's own threads are not in it)
    cpu_time: float = 0.0
    rows: int = 0
    # the statement's root span (runtime/tracing.py): the split of the
    # server's clock below is read off its children, on the tracer's clock
    root: Optional[Any] = field(default=None, repr=False)

    @property
    def elapsed(self) -> float:
        end = self.end_time or time.time()
        return end - self.create_time

    def _child_secs(self, *names: str) -> float:
        from .tracing import child_secs

        if self.root is None:
            return 0.0
        return child_secs(self.root._trace, self.root, *names)

    @property
    def queued_secs(self) -> float:
        """From creation to admission: the wait for a pool thread and for
        the resource group's slot (spans ``queue`` and ``admit``)."""
        return self._child_secs("queue", "admit")

    @property
    def planning_secs(self) -> float:
        return self._child_secs("parse", "planner", "optimizer")

    @property
    def exec_secs(self) -> float:
        """From admission to the rows in hand: parse to encode."""
        return self._child_secs(*_EXEC_SPANS)


@dataclass
class QueryExecution:
    """One tracked query (SqlQueryExecution + QueryInfo analogue)."""

    query_id: str
    sql: str
    user: str = "user"
    source: str = ""
    resource_group: str = ""
    # client-requested spooled result encoding ("json" / "json+lz4"); None =
    # inline protocol data (ref: protocol/spooling QueryDataEncoding)
    data_encoding: Optional[str] = None
    # protocol-level client session (ClientContext): carries prepared
    # statements + open transaction across pool threads; session-state
    # changes land in client_ctx.updates for the protocol layer
    client_ctx: Optional[Any] = None
    trace_id: Optional[str] = None
    # observability plane: QueryStatsCollector.snapshot() from the runner
    # (device/host/compile attribution + counters; /v1/query surfaces it)
    query_stats: Optional[dict] = None
    state: QueryState = QueryState.QUEUED
    stats: QueryStats = field(default_factory=QueryStats)
    column_names: Optional[List[str]] = None
    column_types: Optional[List[object]] = None
    rows: Optional[List[tuple]] = None
    error: Optional[str] = None
    error_type: Optional[str] = None
    # the statement's statistics feedback (runtime/statstore.Feedback), which
    # the runner handed over and did not run: run once the root has closed
    # (QueryManager.close_statement), or by a reader that joins it
    feedback: List[Any] = field(default_factory=list, repr=False)
    _feedback_sent: int = field(default=0, repr=False)  # how many went to the pool
    # the open span `client_turn`: a page with a nextUri is out, the next
    # request is not in (server/coordinator.py)
    _client_turn: Optional[Any] = field(default=None, repr=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)
    _done: threading.Event = field(default_factory=threading.Event, repr=False)
    _state_listeners: List[Callable] = field(default_factory=list, repr=False)
    # serializes event dispatch per query so listeners observe transitions
    # in state-machine order even when cancel() races the pool thread
    _event_lock: threading.Lock = field(default_factory=threading.Lock, repr=False)
    # guards single query_completed dispatch + history-ring entry: two state
    # hooks can both observe a terminal state when transitions race
    _completed_dispatched: bool = field(default=False, repr=False)

    def transition(self, new_state: QueryState, error: Optional[str] = None,
                   error_type: Optional[str] = None) -> bool:
        """Advance the state machine; no-op (False) once terminal. ``error``/
        ``error_type`` are applied atomically with a SUCCESSFUL transition so
        a kill() losing the race to a natural finish can't scribble failure
        text onto a FINISHED query."""
        with self._lock:
            if self.state.is_done:
                return False
            if error is not None:
                self.error = error
            if error_type is not None:
                self.error_type = error_type
            self.state = new_state
            if new_state.is_done:
                self.stats.end_time = time.time()
                self._done.set()
        for listener in list(self._state_listeners):
            listener(self)
        return True

    def wait_done(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)


class QueryManager:
    """Tracks queries and runs them on a worker pool behind hierarchical
    resource-group admission (DispatchManager + QueryTracker +
    InternalResourceGroup: queries QUEUE at the group's hard concurrency
    limit, are rejected when the queue is full, and dequeue weighted-fair)."""

    def __init__(self, executor_fn: Callable[[str], Any], max_workers: int = 4,
                 max_history: Optional[int] = None,
                 max_concurrent: Optional[int] = None,
                 resource_groups=None,
                 memory_pool=None, cluster_memory=None,
                 low_memory_killer=None):
        from .resource_groups import ResourceGroupManager

        import inspect

        self._executor_fn = executor_fn
        try:
            params = inspect.signature(executor_fn).parameters
            self._fn_accepts_user = "user" in params
            self._fn_accepts_client = "client" in params
        except (TypeError, ValueError):
            self._fn_accepts_user = False
            self._fn_accepts_client = False
        self._pool = ThreadPoolExecutor(max_workers=max_workers, thread_name_prefix="query")
        self._queries: Dict[str, QueryExecution] = {}
        self._lock = threading.Lock()
        if max_history is None:
            max_history = knobs.env_int("TRINO_TPU_QUERY_HISTORY", DEFAULT_HISTORY)
        self._max_history = max(max_history, 0)
        # completed-query ring: terminal query ids in completion order; when
        # it overflows, the oldest terminal query leaves _queries too
        self._done_ring: deque = deque()
        self._listeners: List[Callable] = []
        if resource_groups is not None:
            self._groups = resource_groups
        elif max_concurrent:
            self._groups = ResourceGroupManager.default(max_concurrent)
        else:
            self._groups = None
        # memory arbitration plane (runtime/memory.py): a pool makes every
        # query's reservations cluster-arbitrated — blocking backpressure,
        # revocable spill, and the low-memory killer wired to self.kill()
        # (AdministrativelyKilled). Default: the env-sized process pool;
        # None = accounting-only (exactly the pre-arbitration behavior).
        from .memory import ClusterMemoryManager, default_pool

        if cluster_memory is not None:
            self._cluster_memory = cluster_memory
            self._memory_pool = cluster_memory.pool
            if cluster_memory.kill_fn is None:
                cluster_memory.kill_fn = self._kill_for_memory
        else:
            pool = memory_pool if memory_pool is not None else default_pool()
            self._memory_pool = pool
            self._cluster_memory = (
                ClusterMemoryManager(
                    pool, kill_fn=self._kill_for_memory,
                    killer=low_memory_killer,
                )
                if pool is not None
                else None
            )
        if self._memory_pool is not None:
            # resource-group memory shares ride the pool's change feed
            self._memory_pool.add_listener(self._on_pool_change)
        # system catalog wiring: a manager built over LocalQueryRunner.execute
        # becomes that runner's `system.runtime.*` source (last one wins)
        owner = getattr(executor_fn, "__self__", None)
        ctx = getattr(getattr(owner, "metadata", None), "system_context", None)
        if ctx is not None:
            ctx.query_manager = self
            ctx.memory_pool = self._memory_pool
            ctx.cluster_memory = self._cluster_memory
        # cluster observability plane: profile persistence is gated on the
        # owning runner's session (cluster_obs) — None disables the hook
        self._obs_session = getattr(owner, "session", None)
        # pre-register the admission series so every coordinator's
        # announcement/heartbeat snapshot carries them from the first beat
        # (the fleet plane federates per-node queue depth + admission
        # counters; a node that has served nothing must still report 0)
        from .metrics import REGISTRY

        REGISTRY.gauge(
            "trino_tpu_protocol_queue_depth",
            help="queries waiting on a resource-group concurrency slot",
        )
        REGISTRY.counter(
            "trino_tpu_queries_submitted_total", help="queries submitted"
        )
        REGISTRY.counter(
            "trino_tpu_queries_finished_total", help="queries finished"
        )
        REGISTRY.counter(
            "trino_tpu_cache_admission_hits_total",
            help="result-cache hits served before the resource-group "
                 "queue gate",
        )

    @property
    def resource_groups(self):
        return self._groups

    @property
    def memory_pool(self):
        return self._memory_pool

    @property
    def cluster_memory(self):
        return self._cluster_memory

    def _kill_for_memory(self, query_id: str, reason: str) -> None:
        """ClusterMemoryManager kill hook -> AdministrativelyKilled. Lets
        QueryNotFound PROPAGATE: on a shared process pool the victim may be
        a worker task id, and maybe_kill must learn the owner is unkillable
        rather than doom an innocent reservation."""
        self.kill(query_id, message=reason)

    def _on_pool_change(self, owner: str, delta: int, revocable: bool) -> None:
        """Pool listener: charge reservation deltas to the owning query's
        resource group so soft_memory_limit gating sees live usage."""
        if self._groups is None:
            return
        q = self.get(owner)
        if q is None or not q.resource_group:
            return
        note = getattr(self._groups, "note_memory", None)
        if note is not None:
            note(q.resource_group, delta)

    def add_listener(self, listener: Callable) -> None:
        """EventListener SPI hook (spi/eventlistener/): an object with any of
        ``query_created`` / ``query_state_change`` / ``split_completed`` /
        ``query_completed`` methods (each takes the event dict), or a plain
        callable, which receives the QueryExecution on completion only
        (legacy listeners keep their exact pre-lifecycle behavior)."""
        self._listeners.append(listener)

    # ----------------------------------------------------------- event plane

    def _dispatch(self, kind: str, q: QueryExecution, event: Optional[dict] = None) -> None:
        """One event to every listener, isolation per listener: a raiser is
        logged and skipped; the remaining listeners still run and the state
        machine never observes the exception."""
        if not self._listeners:
            return
        if event is None:
            from .events import lifecycle_event

            event = lifecycle_event(q, kind)
        for listener in list(self._listeners):
            try:
                method = getattr(listener, kind, None)
                if callable(method):
                    method(event)
                elif kind == "query_completed" and callable(listener):
                    listener(q)
            except Exception:  # noqa: BLE001 — listener isolation
                traceback.print_exc()

    def _wants(self, kind: str) -> bool:
        """True only when some listener OVERRIDES the hook — the EventListener
        base class ships no-op defaults, and e.g. a history store attaching
        must not switch on the per-split event path."""
        from .events import EventListener

        base = getattr(EventListener, kind, None)
        for listener in self._listeners:
            method = getattr(listener, kind, None)
            if callable(method) and getattr(type(listener), kind, None) is not base:
                return True
        return False

    def _on_transition(self, q: QueryExecution) -> None:
        """State hook installed on every tracked query: lifecycle events in
        order + completed-ring bookkeeping on the terminal transition. The
        _completed_dispatched flag (under _event_lock) keeps the completion
        event and ring entry single-shot even when a delayed non-terminal
        hook observes a state that a racing cancel already made terminal."""
        with q._event_lock:
            if q._completed_dispatched:
                # a delayed non-terminal hook arriving after the completion
                # event must stay silent — nothing follows QueryCompleted
                return
            self._dispatch("query_state_change", q)
            if q.state.is_done:
                q._completed_dispatched = True
                self._note_done(q)
                self._maybe_persist_profile(q)
                self._dispatch("query_completed", q)

    def _note_done(self, q: QueryExecution) -> None:
        with self._lock:
            self._done_ring.append(q.query_id)
            expired = []
            while len(self._done_ring) > self._max_history:
                expired.append(self._queries.pop(self._done_ring.popleft(), None))
        for old in expired:
            if old is not None:  # its client never fetched the last page
                self.close_statement(old, expired=True)

    def _maybe_persist_profile(self, q: QueryExecution) -> None:
        """Cluster observability plane: persist the completed query's
        self-contained profile bundle ($TRINO_TPU_QUERY_PROFILE_DIR) when
        the owning session enables cluster_obs and the query ran at or
        above slow_query_threshold. Advisory: a store failure must never
        touch the state machine. Off path: one attribute check."""
        sess = self._obs_session
        if sess is None:
            return
        try:
            if not sess.get("cluster_obs"):
                return
        except Exception:  # noqa: BLE001 — sessions without the knob: off
            return
        try:
            from .clusterobs import maybe_persist_profile

            self.join_feedback(q)  # the bundle holds the plan's planNodes
            maybe_persist_profile(
                sess,
                query_id=q.query_id,
                sql=q.sql,
                state=q.state.value,
                user=q.user,
                wall_secs=q.stats.elapsed,
                query_stats=q.query_stats,
                created=q.stats.create_time,
                ended=q.stats.end_time,
            )
        except Exception:  # noqa: BLE001 — profile persistence is advisory
            traceback.print_exc()

    # ------------------------------------------------------------- lifecycle

    def submit(self, sql: str, user: str = "user", source: str = "",
               data_encoding: Optional[str] = None,
               client_ctx=None, warm_result=None) -> QueryExecution:
        from .metrics import REGISTRY

        from .tracing import STATEMENT, TRACER

        query_id = f"q_{uuid.uuid4().hex[:16]}"
        q = QueryExecution(
            query_id=query_id, sql=sql, user=user, source=source,
            data_encoding=data_encoding, client_ctx=client_ctx,
            trace_id=query_id,
        )
        # the statement's timeline starts here, on the caller's thread: the
        # trace id IS the query id; `queue` runs until a pool thread has the
        # resource group's slot; the root until the last page is sent
        # (server/coordinator.py) or the statement is canceled or expires
        q.stats.root = TRACER.open_span(
            STATEMENT, None, query_id, query_id=query_id, sql=sql[:200]
        )
        q._queue_span = TRACER.open_span(
            "queue", q.stats.root, cat="protocol", query_id=query_id
        )
        # fleet routing already peeked the warm tier to classify this
        # statement as follower-servable: carry that result into admission
        # so the serving path doesn't repeat the plan/key/lookup work
        q._warm_result = warm_result
        # hook + created event BEFORE the query becomes discoverable: a
        # cancel() can only reach a query via _queries, so no transition can
        # precede the hook, and the created dispatch holds _event_lock so no
        # state-change event can overtake it
        q._state_listeners.append(self._on_transition)
        with q._event_lock:
            self._dispatch("query_created", q)
        with self._lock:
            self._queries[query_id] = q
        REGISTRY.counter(
            "trino_tpu_queries_submitted_total", help="queries submitted"
        ).inc()
        self._pool.submit(self._run, q)
        return q

    def get(self, query_id: str) -> Optional[QueryExecution]:
        with self._lock:
            return self._queries.get(query_id)

    def list_queries(self) -> List[QueryExecution]:
        with self._lock:
            return list(self._queries.values())

    def cancel(self, query_id: str) -> CancelResult:
        """Cancel a tracked query. Raises :class:`QueryNotFound` for unknown
        ids; returns ``CancelResult.TERMINAL`` when the query had already
        reached a terminal state (the two used to collapse into one bare
        ``False``)."""
        q = self.get(query_id)
        if q is None:
            raise QueryNotFound(query_id)
        if q.transition(QueryState.CANCELED):
            self.close_statement(q, canceled=True)
            return CancelResult.CANCELED
        return CancelResult.TERMINAL  # already terminal (or lost the race)

    def close_statement(self, q: QueryExecution, **attributes) -> None:
        """Ends the statement's root span (once): its last page has been
        sent, or its client went away (cancel, expiry from the history).
        Nobody waits for this statement any more, so its statistics feedback
        goes to the pool from here: never before FINISHED, and not at
        FINISHED either, where its plain Python would take the interpreter
        from the HTTP thread that is about to send the answer."""
        from .tracing import TRACER

        self.end_client_turn(q)
        if q.stats.root is not None:
            TRACER.close_span(q.stats.root, **attributes)
        self._schedule_feedback(q)

    @staticmethod
    def end_client_turn(q: QueryExecution) -> None:
        """Ends the span `client_turn` the protocol front opened when it sent
        a page with a nextUri: the client's next request has arrived, or the
        statement is closed without one."""
        from .tracing import TRACER

        turn, q._client_turn = q._client_turn, None
        if turn is not None:
            TRACER.close_span(turn)

    def _schedule_feedback(self, q: QueryExecution) -> None:
        with q._lock:
            # sent once; `feedback` keeps them for readers to join
            pending, q._feedback_sent = q.feedback[q._feedback_sent:], len(q.feedback)
        for fb in pending:
            try:
                self._pool.submit(fb.run)
            except RuntimeError:  # the pool is shut down
                fb.run()

    @staticmethod
    def join_feedback(q: QueryExecution) -> None:
        """For readers of ``q.query_stats["planNodes"]``: the statement's
        feedback has run when this returns (by this thread, if by no other)."""
        for fb in list(q.feedback):
            fb.run()

    def kill(self, query_id: str, message: str = "") -> CancelResult:
        """system.runtime.kill_query semantics (KillQueryProcedure): fail the
        query with an administrative message rather than a plain cancel."""
        q = self.get(query_id)
        if q is None:
            raise QueryNotFound(query_id)
        if q.transition(
            QueryState.FAILED,
            error=message or "Query killed by user",
            error_type="AdministrativelyKilled",
        ):
            return CancelResult.CANCELED
        return CancelResult.TERMINAL

    def _serve_cached(self, q: QueryExecution) -> bool:
        """Cache-aware admission (ROADMAP item 5): a result-cache hit is
        served BEFORE the resource-group queue gate — a warm hit must never
        wait behind a saturated group's queued queries. Best-effort: the
        runner exposes ``peek_cached_result`` (pure lookup, never executes);
        any miss/failure falls through to the normal queued path. A hit the
        fleet route layer already peeked rides in on ``q._warm_result`` and
        is served directly — one plan/key/lookup per statement, not two."""
        result = getattr(q, "_warm_result", None)
        q._warm_result = None
        if result is None:
            fn = self._executor_fn
            peek = getattr(fn, "peek_cached_result", None)
            if peek is None:
                peek = getattr(
                    getattr(fn, "__self__", None), "peek_cached_result", None
                )
            if peek is None:
                return False
            try:
                result = peek(q.sql, user=q.user)
            except Exception:  # noqa: BLE001 — admission fast path is advisory
                return False
            if result is None:
                return False
        from .metrics import REGISTRY

        q.transition(QueryState.PLANNING)
        q.transition(QueryState.RUNNING)
        q.column_names = result.column_names
        q.column_types = getattr(result, "column_types", None)
        q.rows = result.rows
        q.stats.rows = len(result.rows)
        q.query_stats = getattr(result, "query_stats", None)
        q.transition(QueryState.FINISHED)
        REGISTRY.counter(
            "trino_tpu_cache_admission_hits_total",
            help="result-cache hits served before the resource-group "
                 "queue gate",
        ).inc()
        REGISTRY.counter(
            "trino_tpu_queries_finished_total", help="queries finished"
        ).inc()
        REGISTRY.counter(
            "trino_tpu_rows_produced_total", help="result rows produced"
        ).inc(len(result.rows))
        return True

    def _run(self, q: QueryExecution) -> None:
        from .tracing import TRACER

        with TRACER.attach(q.stats.root):
            try:
                self._run_queued(q)
            finally:
                # rejected, canceled while queued, or served from the cache
                TRACER.close_span(q._queue_span)

    def _run_queued(self, q: QueryExecution) -> None:
        from .tracing import TRACER

        if q.state.is_done:
            return
        if self._groups is None:
            # no queue gate to bypass, but a route-layer warm hit is still
            # served directly instead of re-running the statement
            if getattr(q, "_warm_result", None) is not None \
                    and self._serve_cached(q):
                return
            TRACER.close_span(q._queue_span)
            self._run_admitted(q)
            return
        if self._serve_cached(q):
            return
        from .resource_groups import QueryQueueFullError

        try:
            ticket = self._groups.submit(q.user, q.source)
        except QueryQueueFullError as e:
            q.transition(
                QueryState.FAILED,
                error=str(e), error_type="QueryQueueFullError",
            )
            return
        q.resource_group = ticket.group.path
        from .metrics import REGISTRY

        # protocol queue depth: queries parked behind the resource-group
        # gate right now (the host-path plane's saturation signal; rides
        # /v1/metrics and the announcement snapshot like every gauge)
        depth = REGISTRY.gauge(
            "trino_tpu_protocol_queue_depth",
            help="queries waiting on a resource-group concurrency slot",
        )
        try:
            # stays QUEUED until the group grants a concurrency slot: the
            # `queue` span, open since submit(), makes the wait attributable
            depth.inc()
            try:
                while not ticket.event.wait(timeout=0.5):
                    if q.state.is_done:  # canceled while queued
                        self._groups.cancel(ticket)
                        return
            finally:
                depth.dec()
            if ticket.canceled:
                return
            TRACER.close_span(q._queue_span, resource_group=q.resource_group)
            # the group's scheduling weight rides this thread into the
            # device scheduler: batch admission and launch-gate ordering
            # drain high-priority groups first (runtime/device_scheduler)
            from .device_scheduler import priority_scope

            with priority_scope(ticket.group.spec.scheduling_weight):
                self._run_admitted(q)
        finally:
            self._groups.finish(ticket)

    def _run_admitted(self, q: QueryExecution) -> None:
        from .metrics import REGISTRY

        if q.state.is_done:
            return
        from .hostprof import phase_span
        from .observability import RECORDER

        # the admission edge — slot granted to RUNNING (between the wait in
        # `queue` and the runner's own spans)
        with phase_span(
            RECORDER, "admit", query_id=q.query_id,
            resource_group=q.resource_group,
        ):
            q.transition(QueryState.PLANNING)
        running = REGISTRY.gauge(
            "trino_tpu_queries_running", help="queries currently executing"
        )
        running.inc()
        cpu0 = time.thread_time()
        from .memory import memory_scope

        try:
            q.transition(QueryState.RUNNING)
            # propagate the authenticated principal so access control checks
            # run against the submitting user, not the shared session default
            kwargs = {}
            if self._fn_accepts_user:
                kwargs["user"] = q.user
            if self._fn_accepts_client and q.client_ctx is not None:
                kwargs["client"] = q.client_ctx
            from .statstore import deferring_feedback, query_id_scope

            # memory scope: executor contexts built on this thread attach to
            # the pool under this query's id (blocking reservations; the
            # killer dooms by the same id). No pool -> no-op scope. The
            # statstore scope gives operator-stats rows this query's id.
            # The query_exec flight span is the cluster trace plane's
            # attribution WINDOW (clusterobs.filter_events_for_query):
            # everything nested on this thread belongs to this query (no-op
            # while the recorder is off). It times nothing: the runner's
            # spans under the statement's root do.
            # deferring_feedback: the place the runner's statistics
            # feedback is handed to, to be run after FINISHED.
            with query_id_scope(q.query_id), memory_scope(
                q.query_id, self._memory_pool
            ), RECORDER.span("query_exec", "query", query_id=q.query_id), \
                    deferring_feedback() as feedback:
                if self._wants("split_completed"):
                    from .events import split_events

                    with split_events(
                        lambda info: self._dispatch(
                            "split_completed", q,
                            {"eventType": "SplitCompleted",
                             "queryId": q.query_id, **info},
                        )
                    ):
                        result = self._executor_fn(q.sql, **kwargs)
                else:
                    result = self._executor_fn(q.sql, **kwargs)
            q.column_names = result.column_names
            q.column_types = getattr(result, "column_types", None)
            q.query_stats = getattr(result, "query_stats", None)
            # cluster trace assembly: a distributed runner's INTERNAL FTE
            # query id (task/attempt spans key on it) aliases this query
            q.fte_query_id = getattr(result, "fte_query_id", None)
            q.rows = result.rows
            q.stats.rows = len(result.rows)
            q.stats.cpu_time = time.thread_time() - cpu0
            # before FINISHED: from then on the last page can go out and
            # close the root, which is what sends the feedback to the pool
            q.feedback = feedback
            q.transition(QueryState.FINISHED)
            if q.stats.root is None or q.stats.root.end_ns is not None:
                # canceled while it ran: the root closed before the
                # feedback was here to be sent
                self._schedule_feedback(q)
            REGISTRY.counter(
                "trino_tpu_queries_finished_total", help="queries finished"
            ).inc()
            REGISTRY.counter(
                "trino_tpu_rows_produced_total", help="result rows produced"
            ).inc(len(result.rows))
        except Exception as e:  # noqa: BLE001 — error surface is the protocol
            q.stats.cpu_time = time.thread_time() - cpu0
            # error fields ride the transition so a query already FAILED by
            # kill() keeps its administrative message (transition no-ops)
            q.transition(
                QueryState.FAILED, error=str(e), error_type=type(e).__name__
            )
            REGISTRY.counter(
                "trino_tpu_queries_failed_total", help="queries failed"
            ).inc()
        finally:
            if self._memory_pool is not None:
                # the query-end sweep: whatever its contexts still hold comes
                # back to the pool (and wakes blocked peers) even when the
                # executor died mid-plan
                self._memory_pool.free_owner(q.query_id)
            running.dec()
            from .metrics import DEFAULT_BUCKETS

            REGISTRY.histogram(
                "trino_tpu_query_duration_secs",
                help="end-to-end query wall time",
                buckets=DEFAULT_BUCKETS,
            ).observe(q.stats.exec_secs)
