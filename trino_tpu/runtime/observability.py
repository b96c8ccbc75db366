"""Query observability plane: flight recorder + per-query stats collector.

Reference blueprint: the reference's operator/OperatorStats.java +
QueryStats.java rollups (the numbers EXPLAIN ANALYZE and /v1/query render),
its OpenTelemetry spans, and the JFR-style always-on flight recording the
ecosystem leans on for production triage. Three pieces:

- ``FlightRecorder``: a bounded ring buffer of pipeline events (bucket
  start/end, prefetch issue/complete, host->device transfer, XLA compile,
  spill write/read, exchange push/pull) exportable as Chrome/Perfetto
  trace-event JSON (``chrome_trace``). Off by default — hot paths guard on
  ``RECORDER.enabled`` (one attribute read) so the disabled plane costs
  nothing measurable.
- ``QueryStatsCollector``: per-query attribution of device-busy vs
  host-wait vs compile time, per fragment and per operator, plus the
  counters every perf PR cites (compile-cache, capstore, spill bytes,
  prefetch hits, exchange bytes). JAX dispatch is asynchronous, so exact
  per-operator numbers need explicit ``block_until_ready`` fencing — the
  opt-in sync mode (``query_stats_sync`` session property / EXPLAIN ANALYZE
  VERBOSE); async mode keeps today's behavior and reports dispatch/drain
  deltas only.
- Compile attribution: one process-wide ``jax.monitoring`` duration
  listener routes ``backend_compile`` durations into every compile window
  open on the compiling thread (operator windows nest inside query
  windows), the Prometheus registry, and the flight recorder.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

from .tracing import TRACER

# logical pid for all engine events (one process; workers override)
_PID = 1
_PROCESS_NAME = "trino-tpu"


def _now_us() -> int:
    # monotonic: Perfetto sorts on ts, and the smoke check asserts per-track
    # monotonicity — wall clock can step backwards under NTP
    return time.monotonic_ns() // 1000


def _ring_capacity_from_env() -> int:
    """Ring capacity: $TRINO_TPU_FLIGHT_RING (events), default 65536.
    Floored at 16 — a sub-page ring records nothing useful."""

    from .. import knobs

    return max(knobs.env_int("TRINO_TPU_FLIGHT_RING", 65536), 16)


class FlightRecorder:
    """Bounded ring buffer of trace events in Chrome trace-event form.

    Spans emit paired B/E duration events (same thread by construction —
    ``span`` is a context manager), point events emit "i" instants. The
    buffer is a deque(maxlen): recording never blocks and never grows; old
    events fall off the front (a B whose E survived the wrap is reported by
    the validator, so exports from a live ring are explicit about loss).
    """

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = _ring_capacity_from_env()
        self.enabled = False  # plain attribute: ONE read guards hot paths
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=capacity)
        self._tids: Dict[int, int] = {}  # thread ident -> small stable tid
        self._tid_names: Dict[int, str] = {}
        # ring overflow is data loss — count it so truncated exports are
        # explicit instead of silently short (dropped_events rides the
        # chrome_trace export and a Prometheus counter)
        self.dropped_events = 0
        # recording is on while manually enabled OR any scoped user holds a
        # reference (concurrent flight_recorder=true queries: the first to
        # finish must not truncate the others' recording)
        self._manual = False
        self._refs = 0

    # ------------------------------------------------------------- control

    def _recompute(self) -> None:
        self.enabled = self._manual or self._refs > 0

    def enable(self) -> None:
        with self._lock:
            self._manual = True
            self._recompute()

    def disable(self) -> None:
        with self._lock:
            self._manual = False
            self._recompute()

    def acquire(self) -> None:
        """Scoped enable (refcounted): pair with release()."""
        with self._lock:
            self._refs += 1
            self._recompute()

    def release(self) -> None:
        with self._lock:
            self._refs = max(0, self._refs - 1)
            self._recompute()

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped_events = 0

    # ------------------------------------------------------------ recording

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = len(self._tids) + 1
                self._tids[ident] = tid
                self._tid_names[tid] = threading.current_thread().name
            return tid

    def _emit(self, ev: dict) -> None:
        dropped = False
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped_events += 1
                dropped = True
            self._buf.append(ev)
        if dropped:
            _counter(
                "trino_tpu_flight_dropped_events_total",
                "flight-recorder events pushed off the ring by overflow",
            ).inc()

    @contextmanager
    def span(self, name: str, cat: str, **args):
        """Paired B/E duration event on the current thread's track. Yields a
        mutable dict: keys written into it while the span is open land on
        the E event's args — outcome labels only known at span end (e.g.
        ``task_attempt`` ok/failed) ride the close event."""
        if not self.enabled:
            yield {}
            return
        tid = self._tid()
        self._emit(
            {"name": name, "cat": cat, "ph": "B", "ts": _now_us(),
             "pid": _PID, "tid": tid, "args": dict(args)}
        )
        end_args: Dict[str, object] = {}
        try:
            yield end_args
        finally:
            ev = {"name": name, "cat": cat, "ph": "E", "ts": _now_us(),
                  "pid": _PID, "tid": tid}
            if end_args:
                ev["args"] = dict(end_args)
            self._emit(ev)

    def instant(self, name: str, cat: str, **args) -> None:
        if not self.enabled:
            return
        self._emit(
            {"name": name, "cat": cat, "ph": "i", "ts": _now_us(), "s": "t",
             "pid": _PID, "tid": self._tid(), "args": dict(args)}
        )

    def counter_event(self, name: str, cat: str, **values) -> None:
        """A Perfetto counter-track sample (ph "C"): ``values`` are the
        series on the track named ``name`` for this thread's lane. Used for
        the live HBM-watermark track — one sample per kernel-cost
        attribution, rendered by Perfetto as a stepped counter under the
        lane's span track."""
        if not self.enabled:
            return
        self._emit(
            {"name": name, "cat": cat, "ph": "C", "ts": _now_us(),
             "pid": _PID, "tid": self._tid(),
             "args": {k: float(v) for k, v in values.items()}}
        )

    def complete(self, name: str, cat: str, dur_secs: float, **args) -> None:
        """An "X" event for a duration only known at its end (e.g. an XLA
        compile reported by the jax.monitoring listener)."""
        if not self.enabled:
            return
        dur_us = max(int(dur_secs * 1e6), 0)
        self._emit(
            {"name": name, "cat": cat, "ph": "X", "ts": _now_us() - dur_us,
             "dur": dur_us, "pid": _PID, "tid": self._tid(),
             "args": dict(args)}
        )

    # -------------------------------------------------------------- export

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._buf)

    def thread_names(self) -> Dict[int, str]:
        """tid -> thread name (the cluster observability plane's per-node
        segment export rebuilds thread_name metadata from this)."""
        with self._lock:
            return dict(self._tid_names)

    def chrome_trace(self) -> dict:
        """Chrome/Perfetto trace-event JSON (load in ui.perfetto.dev or
        chrome://tracing)."""
        with self._lock:
            events = list(self._buf)
            tid_names = dict(self._tid_names)
        meta: List[dict] = [
            {"name": "process_name", "ph": "M", "pid": _PID, "tid": 0,
             "args": {"name": _PROCESS_NAME}}
        ]
        for tid, tname in sorted(tid_names.items()):
            meta.append(
                {"name": "thread_name", "ph": "M", "pid": _PID, "tid": tid,
                 "args": {"name": tname}}
            )
        return {
            "traceEvents": meta + sorted(events, key=lambda e: e["ts"]),
            "displayTimeUnit": "ms",
            # ring-overflow visibility: events lost since the last clear()
            "droppedEvents": self.dropped_events,
        }


RECORDER = FlightRecorder()


def flight_sink(span) -> None:
    """``TRACER.sink``: a finished span of runtime/tracing.py as one X event
    of the flight recorder, while it records. A span still open when an
    export is taken is simply not there yet. Protocol phases keep the
    ``proto_`` prefix and the ``protocol`` category the recorder's readers
    select by; ``execution`` keeps ``query``."""
    recorder = RECORDER if span.recorder is None else span.recorder
    if not recorder.enabled:
        return
    cat = span.cat or "trace"
    name = f"proto_{span.name}" if cat == "protocol" else span.name
    recorder.complete(
        name, cat, (span.end_ns - span.start_ns) / 1e9, **span.attributes
    )


TRACER.sink = flight_sink


def validate_chrome_trace(trace: dict) -> List[str]:
    """Minimal schema validation for an exported trace: required fields,
    known pids/tids (declared via metadata events), per-track monotonic
    timestamps, paired B/E events, non-negative X durations, and numeric
    non-empty args on counter ("C") events. Returns a list of problems
    ([] = valid) — the observability smoke check's contract."""
    problems: List[str] = []
    events = trace.get("traceEvents")
    if not isinstance(events, list) or not events:
        return ["traceEvents missing or empty"]
    known_pids = set()
    known_tids = set()
    for ev in events:
        if ev.get("ph") == "M":
            known_pids.add(ev.get("pid"))
            if ev.get("name") == "thread_name":
                known_tids.add((ev.get("pid"), ev.get("tid")))
    stacks: Dict[tuple, List[str]] = {}
    last_ts: Dict[tuple, int] = {}
    for i, ev in enumerate(events):
        for field in ("name", "ph", "pid", "tid"):
            if field not in ev:
                problems.append(f"event {i} missing {field!r}")
        if ev.get("ph") == "M":
            continue
        if "ts" not in ev:
            problems.append(f"event {i} missing 'ts'")
            continue
        key = (ev["pid"], ev["tid"])
        if ev["pid"] not in known_pids:
            problems.append(f"event {i} has undeclared pid {ev['pid']}")
        if key not in known_tids:
            problems.append(f"event {i} has undeclared tid {ev['tid']}")
        if ev["ts"] < last_ts.get(key, 0):
            problems.append(
                f"event {i} ({ev['name']!r}) ts not monotonic on tid {ev['tid']}"
            )
        last_ts[key] = ev["ts"]
        ph = ev["ph"]
        if ph == "B":
            stacks.setdefault(key, []).append(ev["name"])
        elif ph == "E":
            stack = stacks.setdefault(key, [])
            if not stack:
                problems.append(
                    f"event {i} ({ev['name']!r}) E without matching B on "
                    f"tid {ev['tid']}"
                )
            else:
                stack.pop()
        elif ph == "X":
            if ev.get("dur", 0) < 0:
                problems.append(f"event {i} ({ev['name']!r}) negative dur")
        elif ph == "C":
            # counter-track sample: args IS the sample — every value must
            # be numeric or Perfetto drops the series silently
            cargs = ev.get("args")
            if not isinstance(cargs, dict) or not cargs:
                problems.append(
                    f"event {i} ({ev['name']!r}) counter event without args"
                )
            else:
                for k, v in cargs.items():
                    if isinstance(v, bool) or not isinstance(v, (int, float)):
                        problems.append(
                            f"event {i} ({ev['name']!r}) counter series "
                            f"{k!r} non-numeric value {v!r}"
                        )
        elif ph not in ("i", "I"):
            problems.append(f"event {i} unknown ph {ph!r}")
    for (pid, tid), stack in stacks.items():
        for name in stack:
            problems.append(f"unclosed B event {name!r} on tid {tid}")
    return problems


# --------------------------------------------------------------------------- #
# per-query stats collection
# --------------------------------------------------------------------------- #


class QueryStatsCollector:
    """Thread-safe per-query accumulator for the observability plane.

    Time attribution (seconds): ``device_busy`` (fenced operators' device
    time: sync mode only), ``host_wait`` (blocked on host I/O / prefetch
    results), ``compile`` (XLA compiles, attributed by the jax.monitoring
    listener); ``dispatch``, ``drain``, ``analysis`` and ``planning`` are
    read off the statement's spans (runtime/tracing.py).
    Exact per-operator splits need sync mode (block_until_ready fencing —
    see PlanExecutor.collect_stats); async callers still get honest query-
    level dispatch/drain deltas plus every counter.
    """

    _TIME_KEYS = (
        "device_busy_secs", "host_wait_secs", "compile_secs", "emit_secs",
        "fallback_secs", "dispatch_secs", "drain_secs",
        "analysis_secs", "planning_secs",
    )
    _COUNT_KEYS = (
        "compile_count", "compile_cache_hits", "caps_from_store",
        "spill_write_bytes", "spill_read_bytes", "spill_count",
        "prefetch_hits", "prefetch_misses",
        "exchange_push_bytes", "exchange_pull_bytes",
        "h2d_bytes", "input_rows", "overflow_retries",
    )

    def __init__(self):
        self._lock = threading.Lock()
        self.times: Dict[str, float] = {k: 0.0 for k in self._TIME_KEYS}
        self.counts: Dict[str, int] = {k: 0 for k in self._COUNT_KEYS}
        # fragment id -> {"device_busy_secs": ..., "compile_secs": ..., ...}
        self.fragments: Dict[int, Dict[str, float]] = {}
        # operator label -> {"device_secs", "host_secs", "compile_secs",
        #                    "rows", "invocations"}
        self.operators: Dict[str, Dict[str, float]] = {}
        # plan-node key ("<preorder idx>:<kind>") -> cardinality actuals
        # (the statistics feedback plane's estimate-vs-actual rows; only the
        # WINNING attempt of a speculative FTE pair folds in here)
        self.nodes: Dict[str, Dict[str, object]] = {}
        # plan-node label -> aggregated XLA cost-model attribution
        # (runtime/kernelcost.py sink; flops/bytes sum over the node's
        # distinct programs, peak HBM is a max — programs launch serially
        # per operator so the watermark is the largest single launch)
        self.kernel_costs: Dict[str, Dict[str, object]] = {}
        self.sync_mode = False

    def add_time(self, key: str, secs: float, fragment: Optional[int] = None) -> None:
        with self._lock:
            self.times[key] = self.times.get(key, 0.0) + secs
            if fragment is not None:
                frag = self.fragments.setdefault(fragment, {})
                frag[key] = frag.get(key, 0.0) + secs

    def add_fragment_time(self, fragment: int, key: str, secs: float) -> None:
        """Fragment-level time whose QUERY total was already credited by
        another path (e.g. the jax compile listener books query-level
        compile_secs; the fragment share lands here without re-counting)."""
        with self._lock:
            frag = self.fragments.setdefault(fragment, {})
            frag[key] = frag.get(key, 0.0) + secs

    def add_count(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.counts[key] = self.counts.get(key, 0) + n

    def add_operator(
        self, label: str, device_secs: float = 0.0, host_secs: float = 0.0,
        compile_secs: float = 0.0, rows: int = 0,
    ) -> None:
        with self._lock:
            op = self.operators.setdefault(
                label,
                {"device_secs": 0.0, "host_secs": 0.0, "compile_secs": 0.0,
                 "rows": 0, "invocations": 0},
            )
            op["device_secs"] += device_secs
            op["host_secs"] += host_secs
            op["compile_secs"] += compile_secs
            op["rows"] += rows
            op["invocations"] += 1

    def add_node(
        self,
        key: str,
        kind: str = "",
        actual_rows: int = 0,
        estimated_rows: Optional[float] = None,
        q_error: Optional[float] = None,
        input_rows: int = 0,
        output_bytes: int = 0,
        null_fraction: Optional[float] = None,
        build_rows: Optional[int] = None,
        dynamic_filter_selectivity: Optional[float] = None,
    ) -> None:
        """Per-plan-node cardinality actuals (statstore.observe_query is the
        one writer; re-observation of the same key overwrites — actuals are
        aggregated across fragments/attempts BEFORE they land here)."""
        with self._lock:
            self.nodes[key] = {
                "kind": kind,
                "actualRows": int(actual_rows),
                "estimatedRows": estimated_rows,
                "qError": q_error,
                "inputRows": int(input_rows),
                "outputBytes": int(output_bytes),
                "nullFraction": null_fraction,
                "buildRows": build_rows,
                "dynamicFilterSelectivity": dynamic_filter_selectivity,
            }

    def add_kernel_cost(self, node_label: str, record: dict) -> None:
        """Fold one program's cost record (kernelcost.CostJit attribution)
        into the plan node's aggregate."""
        with self._lock:
            agg = self.kernel_costs.setdefault(
                node_label,
                {"flops": 0.0, "bytesAccessed": 0.0, "peakHbmBytes": 0,
                 "programs": 0, "unavailable": 0},
            )
            agg["programs"] += 1
            if record.get("status") != "ok":
                agg["unavailable"] += 1
                return
            if record.get("flops"):
                agg["flops"] += float(record["flops"])
            if record.get("bytes_accessed"):
                agg["bytesAccessed"] += float(record["bytes_accessed"])
            if record.get("peak_hbm_bytes"):
                agg["peakHbmBytes"] = max(
                    agg["peakHbmBytes"], int(record["peak_hbm_bytes"])
                )

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "syncMode": self.sync_mode,
                "times": dict(self.times),
                "counts": dict(self.counts),
                "fragments": {
                    str(fid): dict(v) for fid, v in sorted(self.fragments.items())
                },
                "operators": {k: dict(v) for k, v in self.operators.items()},
                "planNodes": {k: dict(v) for k, v in self.nodes.items()},
                "kernelCosts": {
                    k: dict(v) for k, v in self.kernel_costs.items()
                },
            }



def query_stats_fields(snapshot: dict) -> dict:
    """QueryStatsCollector.snapshot() -> Trino-parity queryStats fields
    (QueryStats.java naming). The ONE mapping the /v1/query/{id} payload
    uses — keep field additions here, not inlined in the coordinator."""
    times = snapshot.get("times", {})
    counts = snapshot.get("counts", {})
    return {
        "deviceBusyTime": round(times.get("device_busy_secs", 0.0), 6),
        "hostWaitTime": round(times.get("host_wait_secs", 0.0), 6),
        "dispatchTime": round(times.get("dispatch_secs", 0.0), 6),
        # the last wait for the device (unfenced mode), not its busy time
        "drainTime": round(times.get("drain_secs", 0.0), 6),
        # off the statement's spans: the planner's, and parse to optimizer
        "analysisTime": round(times.get("analysis_secs", 0.0), 6),
        "planningTime": round(times.get("planning_secs", 0.0), 6),
        "compileTime": round(times.get("compile_secs", 0.0), 6),
        "spilledDataSize": counts.get("spill_write_bytes", 0),
        "spilledReadDataSize": counts.get("spill_read_bytes", 0),
        "internalNetworkInputDataSize": counts.get("exchange_pull_bytes", 0),
        "internalNetworkOutputDataSize": counts.get("exchange_push_bytes", 0),
        "physicalInputDataSize": counts.get("h2d_bytes", 0),
        "rawInputPositions": counts.get("input_rows", 0),
        "prefetchHits": counts.get("prefetch_hits", 0),
        "prefetchMisses": counts.get("prefetch_misses", 0),
        "compileCount": counts.get("compile_count", 0),
        "capacityVectorsFromStore": counts.get("caps_from_store", 0),
        "syncAttribution": snapshot.get("syncMode", False),
        "operatorSummaries": snapshot.get("operators", {}),
        "planNodeStats": snapshot.get("planNodes", {}),
        # XLA cost-model attribution per plan node (kernel_cost sessions)
        "kernelCostSummaries": snapshot.get("kernelCosts", {}),
        # warm-path cache plane (runtime/cachestore.py): the tier that
        # served the query ("result"/"fragment"/"plan"; None = cold) and
        # human provenance text ("result cache HIT @ snapshot 42")
        "cacheHitTier": snapshot.get("cacheHitTier"),
        "cacheProvenance": snapshot.get("cacheProvenance"),
    }


# ----------------------------------------------------------- active collector

_tls = threading.local()


def current_collector() -> Optional[QueryStatsCollector]:
    return getattr(_tls, "collector", None)


@contextmanager
def collecting(collector: Optional[QueryStatsCollector]):
    """Install ``collector`` as this thread's active collector (spill /
    exchange / compile hooks report to it without explicit plumbing)."""
    prev = getattr(_tls, "collector", None)
    _tls.collector = collector
    try:
        yield collector
    finally:
        _tls.collector = prev


class _CompileWindow:
    __slots__ = ("seconds", "count")

    def __init__(self):
        self.seconds = 0.0
        self.count = 0


@contextmanager
def compile_window():
    """Accumulates XLA backend-compile seconds that land on THIS thread while
    the window is open. Windows nest (an operator window inside a query
    window): the listener credits every open window, so exclusive times are
    derived by subtracting child windows."""
    _ensure_jax_listener()
    stack = getattr(_tls, "compile_windows", None)
    if stack is None:
        stack = []
        _tls.compile_windows = stack
    w = _CompileWindow()
    stack.append(w)
    try:
        yield w
    finally:
        stack.pop()


_listener_lock = threading.Lock()
_listener_registered = False


def _on_jax_duration(event: str, duration: float, **kwargs) -> None:
    if not event.endswith("backend_compile_duration"):
        return
    for w in getattr(_tls, "compile_windows", ()):
        w.seconds += duration
        w.count += 1
    c = current_collector()
    if c is not None:
        c.add_time("compile_secs", duration)
        c.add_count("compile_count")
    if RECORDER.enabled:
        RECORDER.complete("xla_compile", "compile", duration)
    try:
        from .metrics import DEFAULT_BUCKETS, REGISTRY

        REGISTRY.counter(
            "trino_tpu_xla_compiles_total", help="XLA backend compiles"
        ).inc()
        REGISTRY.histogram(
            "trino_tpu_xla_compile_secs", help="XLA backend compile duration",
            buckets=DEFAULT_BUCKETS,
        ).observe(duration)
    except Exception:
        pass


def _on_jax_event(event: str, **kwargs) -> None:
    # a backend_compile_duration event also fires when the executable came
    # from the persistent cache; this counter tells the two apart
    if event == "/jax/compilation_cache/cache_hits":
        from .metrics import REGISTRY

        REGISTRY.counter(
            "trino_tpu_xla_persistent_cache_hits_total",
            help="XLA backend compiles answered by the persistent cache",
        ).inc()


def _ensure_jax_listener() -> None:
    global _listener_registered
    if _listener_registered:
        return
    with _listener_lock:
        if _listener_registered:
            return
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration
            )
            jax.monitoring.register_event_listener(_on_jax_event)
        except Exception:
            pass  # plane degrades to no compile attribution, never fails
        _listener_registered = True


# ------------------------------------------------------------- event helpers

# process counters resolved ONCE: the hooks below sit on per-page hot paths
# (exchange sink add, output buffer add, spill blobs) where a registry
# lookup — lock + sorted-label key build — per call would be real overhead
_counters: Dict[str, object] = {}


def _counter(name: str, help_: str):
    c = _counters.get(name)
    if c is None:
        from .metrics import REGISTRY

        c = _counters[name] = REGISTRY.counter(name, help=help_)
    return c


def on_spill_write(nbytes: int, event: bool = True) -> None:
    """Spill-to-host/disk write: counters + flight event (callable from any
    thread; collector attribution rides the caller thread's collector).
    Pass ``event=False`` when the call site emits its own richer span."""
    c = current_collector()
    if c is not None:
        c.add_count("spill_write_bytes", nbytes)
        c.add_count("spill_count")
    _counter(
        "trino_tpu_spill_write_bytes_total", "bytes spilled to host/disk"
    ).inc(nbytes)
    if event:
        RECORDER.instant("spill_write", "spill", bytes=nbytes)


def on_spill_read(nbytes: int, event: bool = True) -> None:
    c = current_collector()
    if c is not None:
        c.add_count("spill_read_bytes", nbytes)
    _counter(
        "trino_tpu_spill_read_bytes_total", "bytes read back from spill"
    ).inc(nbytes)
    if event:
        RECORDER.instant("spill_read", "spill", bytes=nbytes)


def on_exchange_push(nbytes: int) -> None:
    c = current_collector()
    if c is not None:
        c.add_count("exchange_push_bytes", nbytes)
    _counter(
        "trino_tpu_exchange_push_bytes_total",
        "bytes written to exchange sinks",
    ).inc(nbytes)
    RECORDER.instant("exchange_push", "exchange", bytes=nbytes)


def on_exchange_pull(nbytes: int) -> None:
    c = current_collector()
    if c is not None:
        c.add_count("exchange_pull_bytes", nbytes)
    _counter(
        "trino_tpu_exchange_pull_bytes_total",
        "bytes read from exchange sources",
    ).inc(nbytes)
    RECORDER.instant("exchange_pull", "exchange", bytes=nbytes)
