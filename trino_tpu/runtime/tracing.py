"""Statement tracing: the one span source of the served path.

Reference blueprint: the reference threads an io.opentelemetry Tracer through
the whole engine (Trino's TracingMetadata / planning spans: "planner",
"analyzer", "optimizer", per-stage execution spans) and exports via OTLP.
This module keeps the same span model (trace id, span id, parent, name,
start/end nanos, attributes) with an in-memory ring of finished trees the
coordinator serves as JSON.

One clock: ``start_ns``/``end_ns`` are ``time.perf_counter_ns()``, the clock
the benchmark's harness stamps a statement with; one wall-clock anchor per
process keeps ``startTimeUnixNano`` true. One timeline: every span also opens
a ``jax.profiler.TraceAnnotation("trino:<name>")``, so under a profiler
session the same span lies in ``/host:CPU`` on the clock of the device's
events (a TraceMe costs an atomic read while no session is on). One
identifier: under a ``QueryManager`` the trace id is the query id and the root
span ``statement`` lives from the statement's creation (HTTP
thread) to its last page sent (``open_span``/``close_span``); the pool
thread that runs the statement ``attach``es to it.

Readers: ``/v1/query/{id}`` (``operatorTree``, ``queryStats``), the protocol's
``stats``, the flight recorder (``sink``: each finished span as one X event,
installed by runtime/observability.py), benchmark/layer_metrics (``finished``).
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

# the span names of the served path (ISSUE 26's table; PERF.md section 3
# names the metric each is for)
STATEMENT = "statement"
STATS_FEEDBACK = "stats_feedback"
OP_PREFIX = "op:"
SYNC_PREFIX = "sync:"

# perf_counter has no epoch: one anchor per process maps it to the wall clock
_WALL_ANCHOR_NS = time.time_ns() - time.perf_counter_ns()
_PROCESS_PREFIX = os.urandom(8).hex()
_ids = itertools.count(1)

DROPPED_COUNTER = "trino_tpu_trace_statements_dropped_total"
DEFAULT_RING = 4096


class Span:
    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "start_ns", "end_ns",
        "attributes", "cat", "recorder", "_trace", "_annotation",
    )

    def __init__(self, trace_id: str, span_id: str, parent_id: Optional[str],
                 name: str, start_ns: int, end_ns: Optional[int] = None,
                 attributes: Optional[Dict[str, object]] = None,
                 cat: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.attributes = {} if attributes is None else attributes
        # flight-recorder category (the sink's); None = "trace"
        self.cat = cat
        self.recorder = None  # a flight recorder other than the process's
        self._trace: Optional[List["Span"]] = None  # the tree it is kept in
        self._annotation = None

    @property
    def duration_secs(self) -> float:
        """Seconds from start to end; to now while the span is open."""
        end = self.end_ns if self.end_ns is not None else time.perf_counter_ns()
        return (end - self.start_ns) / 1e9

    def to_dict(self) -> dict:
        return {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentSpanId": self.parent_id,
            "name": self.name,
            "startTimeUnixNano": self.start_ns + _WALL_ANCHOR_NS,
            "endTimeUnixNano": (
                self.end_ns + _WALL_ANCHOR_NS if self.end_ns is not None else None
            ),
            # the process's perf_counter: the harness's clock
            "startNs": self.start_ns,
            "endNs": self.end_ns,
            "attributes": self.attributes,
            "durationMs": (
                (self.end_ns - self.start_ns) / 1e6
                if self.end_ns is not None else None
            ),
        }


class TraceContext:
    """An immutable capture of the current span, for carrying trace
    parentage across thread boundaries (Context.makeCurrent() analogue)."""

    __slots__ = ("span",)

    def __init__(self, span: Optional[Span]):
        self.span = span


class _Scope:
    """``with TRACER.span(...)``: a class and not a generator, since a
    statement opens some thirty-five of these."""

    __slots__ = ("_tracer", "_span", "_stack")

    def __init__(self, tracer: "Tracer", span: Span, stack: list):
        self._tracer = tracer
        self._span = span
        self._stack = stack

    def __enter__(self) -> Span:
        span = self._span
        self._stack.append(span)
        span._annotation = TraceAnnotation("trino:" + span.name)
        span._annotation.__enter__()
        span.start_ns = time.perf_counter_ns()
        return span

    def __exit__(self, exc_type, exc, tb) -> bool:
        span = self._span
        span.end_ns = time.perf_counter_ns()
        span._annotation.__exit__(exc_type, exc, tb)
        span._annotation = None
        self._stack.pop()
        if exc_type is not None and issubclass(exc_type, Exception):
            span.attributes["error"] = f"{exc_type.__name__}: {exc}"
        if span.parent_id is None and span.name == STATEMENT:
            roll_up(span)
        self._tracer._finished(span)
        return False


class _Attached:
    __slots__ = ("_span", "_stack")

    def __init__(self, span: Optional[Span], stack: Optional[list]):
        self._span = span
        self._stack = stack

    def __enter__(self) -> Optional[Span]:
        if self._span is not None:
            self._stack.append(self._span)
        return self._span

    def __exit__(self, *exc) -> bool:
        if self._span is not None:
            self._stack.pop()
        return False


class Tracer:
    """Per-process tracer; spans are grouped by trace (one trace per
    statement). The ring keeps the newest ``max_traces`` trees in memory and
    counts what falls off; nothing is written out during a statement.
    ``sink`` (if set) receives each finished span."""

    def __init__(self, max_traces: int = DEFAULT_RING):
        self._lock = threading.Lock()
        self._traces: Dict[str, List[Span]] = {}  # oldest first
        self._max_traces = max_traces
        self._tls = threading.local()
        self.dropped = 0
        self.sink: Optional[Callable[[Span], None]] = None

    # ------------------------------------------------------------- internals

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _tree(self, trace_id: str) -> List[Span]:
        """The list a trace's spans are kept in, made (and the oldest tree
        dropped) on first use."""
        dropped = 0
        with self._lock:
            tree = self._traces.get(trace_id)
            if tree is None:
                tree = self._traces[trace_id] = []
                while len(self._traces) > self._max_traces:
                    del self._traces[next(iter(self._traces))]
                    dropped += 1
                self.dropped += dropped
        if dropped:
            from .metrics import REGISTRY

            REGISTRY.counter(
                DROPPED_COUNTER,
                help="statement span trees pushed off the tracer's ring",
            ).inc(dropped)
        return tree

    def _new(self, name: str, parent: Optional[Span], trace_id: Optional[str],
             root: bool, cat: Optional[str], attributes: dict) -> Span:
        span_id = format(next(_ids), "016x")
        if parent is not None:
            span = Span(parent.trace_id, span_id, parent.span_id, name, 0,
                        None, attributes, cat)
            span._trace = parent._trace
        else:
            if trace_id is None:
                trace_id = _PROCESS_PREFIX + span_id
            span = Span(trace_id, span_id, None, name, 0, None, attributes, cat)
            if root:
                span._trace = self._tree(trace_id)
        if span._trace is not None:
            span._trace.append(span)  # list.append: atomic under the GIL
        return span

    def _finished(self, span: Span) -> None:
        sink = self.sink
        if sink is not None:
            try:
                sink(span)
            except Exception:  # noqa: BLE001 — a reader never fails a statement
                pass

    # ----------------------------------------------------------------- spans

    def current(self) -> Optional[Span]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def span(self, name: str, trace_id: Optional[str] = None, *,
             root: bool = True, cat: Optional[str] = None, **attributes):
        """A span under this thread's current one. With none current it
        starts a tree of its own, unless ``root=False``: then it is timed,
        annotated and handed to the sink but kept in no tree (a protocol
        phase before its statement exists)."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        return _Scope(
            self, self._new(name, parent, trace_id, root, cat, attributes), stack
        )

    def root(self, name: str, trace_id: Optional[str] = None, **attributes):
        """A span that starts a tree of its own whatever this thread's
        current span is: work done on a statement's behalf once its own
        root has closed (the deferred statistics feedback), which a reader
        may run from inside another statement."""
        return _Scope(
            self, self._new(name, None, trace_id, True, None, attributes),
            self._stack(),
        )

    def statement(self, sql: str = ""):
        """The statement's root for a runner: under a QueryManager it is open
        already (the pool thread is attached to it), and a statement run from
        inside another (a CTAS's SELECT) stays in that one's tree, so the
        current span is handed back; with none current (tests, the harness's
        CTAS) this opens a root `statement` of the runner's own."""
        current = self.current()
        if current is not None:
            return contextlib.nullcontext(current)
        return self.span(STATEMENT, sql=sql[:200])

    def open_span(self, name: str, parent: Optional[Span] = None,
                  trace_id: Optional[str] = None, *,
                  cat: Optional[str] = None, **attributes) -> Span:
        """A span that is on no thread's stack and may end on another thread
        than it began on (``close_span``): the statement's root, opened where
        the statement is created and closed where its last page is sent, and
        its ``queue``, from creation to admission on a pool thread. Others
        work under it through ``attach``. With no ``parent`` it is the root
        of the tree ``trace_id``."""
        span = self._new(name, parent, trace_id, True, cat, attributes)
        # a TraceMe records where it is stopped: the thread may differ
        span._annotation = TraceAnnotation("trino:" + name)
        span._annotation.__enter__()
        span.start_ns = time.perf_counter_ns()
        return span

    def close_span(self, span: Span, **attributes) -> bool:
        """Ends a span of ``open_span`` once; False if it had ended."""
        with self._lock:
            if span.end_ns is not None:
                return False
            span.end_ns = time.perf_counter_ns()
        annotation, span._annotation = span._annotation, None
        if annotation is not None:
            annotation.__exit__(None, None, None)
        span.attributes.update(attributes)
        if span.name == STATEMENT:
            roll_up(span)
        self._finished(span)
        return True

    def count(self, key: str, n: int = 1) -> None:
        """Adds ``n`` to attribute ``key`` of this thread's current span."""
        stack = getattr(self._tls, "stack", None)
        if stack:
            attributes = stack[-1].attributes
            attributes[key] = attributes.get(key, 0) + n

    # -------------------------------------------------- context propagation

    def capture(self) -> "TraceContext":
        """Snapshot the calling thread's current span for cross-thread
        propagation. Spans opened on a pooled thread (runtime/spiller
        io_pool, worker task threads) get a FRESH thread-local stack and
        would otherwise orphan from the query trace — capture() on the
        submitting thread + attach() on the worker re-parents them."""
        return TraceContext(self.current())

    def attach(self, ctx):
        """Make ``ctx``'s span (a TraceContext, or the Span itself) the
        current parent on THIS thread for the duration. Only the stack entry
        is thread-local — the span object is shared, and attach never
        finishes it (its owner does); children opened under attach read
        parent ids only, so concurrent attaches of one context are safe."""
        span = ctx.span if isinstance(ctx, TraceContext) else ctx
        return _Attached(span, self._stack() if span is not None else None)

    def capture_ids(self) -> Optional[Dict[str, str]]:
        """Wire form of capture(): the current span's ids as a small dict
        (ship it in a task descriptor / header), or None outside any span."""
        s = self.current()
        if s is None:
            return None
        return {"trace_id": s.trace_id, "span_id": s.span_id}

    def attach_remote(self, ids: Optional[Dict[str, str]]):
        """Adopt a REMOTE parent (ids that crossed a process or wire
        boundary, from capture_ids()) as this thread's current parent.
        Spans opened underneath join that trace with the remote span as
        parent; the phantom parent itself is never recorded here."""
        if not ids or not ids.get("trace_id"):
            return _Attached(None, None)
        phantom = Span(
            str(ids["trace_id"]), str(ids.get("span_id") or ""), None,
            "<remote>", 0,
        )
        phantom._trace = self._tree(phantom.trace_id)
        return _Attached(phantom, self._stack())

    def wrap(self, fn: Callable) -> Callable:
        """capture() now, attach() around each later call — the convenience
        form for pool submission: ``pool.submit(TRACER.wrap(job), ...)``."""
        ctx = self.capture()

        def wrapped(*args, **kwargs):
            with self.attach(ctx):
                return fn(*args, **kwargs)

        return wrapped

    # --------------------------------------------------------------- readers

    def spans(self, trace_id: str) -> List[Span]:
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def trace(self, trace_id: str) -> List[dict]:
        return [s.to_dict() for s in self.spans(trace_id)]

    def traces(self) -> List[str]:
        with self._lock:
            return list(self._traces)

    def finished(self, name: str = STATEMENT) -> List[List[Span]]:
        """The ring's trees whose root is a closed span called ``name``,
        oldest first; each tree root first, in the order its spans opened."""
        with self._lock:
            trees = [list(tree) for tree in self._traces.values()]
        return [
            tree for tree in trees
            if tree and tree[0].parent_id is None and tree[0].name == name
            and tree[0].end_ns is not None
        ]


def roll_up(root: Span) -> None:
    """The statement's counts, from its tree: ``launches`` (device programs
    the operators launched), ``host_syncs`` (device-to-host reads on the
    operator path), ``pages`` and ``rows`` (sent to the client)."""
    launches = syncs = pages = rows = 0
    for s in root._trace or ():
        if s is root:
            continue
        launches += s.attributes.get("launches", 0)
        if s.name.startswith(SYNC_PREFIX):
            syncs += 1
        elif s.name == "result_stream":
            sent = s.attributes.get("rows", 0)
            rows += sent
            pages += 1 if sent else 0
    root.attributes["launches"] = launches
    root.attributes["host_syncs"] = syncs
    root.attributes["pages"] = pages
    root.attributes.setdefault("rows", rows)


def children(tree: List[Span], parent: Span) -> Iterator[Span]:
    return (s for s in tree if s.parent_id == parent.span_id)


def child_secs(tree: List[Span], parent: Span, *names: str) -> float:
    """Seconds of ``parent``'s finished children called one of ``names``."""
    return sum(
        (c.end_ns - c.start_ns) / 1e9
        for c in children(tree, parent)
        if c.name in names and c.end_ns is not None
    )


TRACER = Tracer()
