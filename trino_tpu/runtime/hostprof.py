"""Host-path observability plane: sampling profiler + GIL-contention probe.

ROADMAP item 4's measurement half: every device-side plane is instrumented
(flight spans, cluster traces, kernel-cost rooflines) but the host/protocol
path — the single-process coordinator front the r13 saturation replay blamed
for p99@16c — had no instrument at all. This module turns "single-core
host/GIL contention" from a hand diagnosis into three measurements:

- ``HostProfiler``: a continuous wall-clock sampling profiler. A daemon
  sampler thread walks ``sys._current_frames()`` every
  ``$TRINO_TPU_HOSTPROF_INTERVAL_MS`` (default 19ms — co-prime with common
  10/20/100ms periodic work so the sampler doesn't alias against it) and
  appends one collapsed stack per engine thread to a bounded ring
  (``$TRINO_TPU_HOSTPROF_RING`` samples; overflow counted, never blocking).
  Exports: folded collapsed-stack text (flamegraph.pl style), speedscope
  JSON (``speedscope()``, schema-checked by ``validate_speedscope``), and a
  Perfetto lane — sampler ticks land in the flight recorder on the
  ``hostprof-sampler`` thread, so the round-17 deterministic-tid contract
  (clusterobs.canonicalize_trace keys lanes on thread NAMES) merges the
  profiler into cluster traces with zero new plumbing. Default OFF: the
  off path starts no thread, touches no registry, and query results are
  byte-identical (tests/test_hostprof.py asserts it poisoning-style).

- Protocol-phase spans: ``phase_span(...)`` names the
  accept → auth/verify → parse → queue → admit → execute-dispatch →
  result-stream request phases uniformly (category ``protocol``) so a slow
  request decomposes into host scheduling vs device work in the same trace
  UI as everything else.

- ``ContentionProbe``: GIL/scheduler contention as expected-vs-actual sleep
  jitter. A probe thread sleeps a short fixed interval and records how late
  the wakeup was — under a GIL hogged by one runnable thread the lateness
  is the switch interval (default 5ms), not the scheduler's microseconds.
  Jitter feeds ``trino_tpu_host_switch_latency_secs``; the sampler's
  runnable/blocked classification feeds ``trino_tpu_host_threads{state=}``.
  Both ride ``/v1/metrics`` and the announcement metric snapshot into the
  federated cluster tables for free.

``system.runtime.host_profile`` (connectors/system.py) serves the live
collapsed-stack aggregation.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from .. import knobs

# thread states the sampler distinguishes (gauge label values)
THREAD_STATES = ("runnable", "blocked")

# leaf frame names that mean "off-CPU, waiting" — a thread parked in one of
# these is blocked (not competing for the GIL); anything else is runnable.
# Python-level sampling cannot see C-level blocking beyond the stdlib's
# named wait points, so the split is approximate but stable.
_WAIT_LEAVES = frozenset({
    "wait", "wait_for", "sleep", "select", "poll", "epoll", "accept",
    "acquire", "recv", "recv_into", "read", "readinto", "readline",
    "get", "join", "getaddrinfo", "connect", "settrace", "park",
    "serve_forever", "handle_request", "_handle_request_noblock",
})

# the request phases phase_span names; kept ordered for docs/tests.
# "route"/"proxy" are the coordinator-fleet additions (runtime/fleet.py):
# ownership hashing + non-owner forwarding cost is attributed, not hidden
# "client_turn" (server/coordinator.py) is the one the work waits in for the
# client: from a page with a nextUri going out to the next request coming in
PROTOCOL_PHASES = (
    "accept", "auth", "verify", "parse", "route", "proxy", "queue",
    "admit", "result_stream", "client_turn", "dispatch",
)


def phase_span(recorder, phase: str, **args):
    """The protocol-phase span: ``with phase_span(RECORDER, "auth"): ...``.

    A call into ``TRACER`` (runtime/tracing.py), the one span source: the
    span is named by the phase and lands under the statement's root when one
    is current on this thread (``queue``, ``admit``, ``result_stream``); a
    phase before its statement exists (``accept``, ``auth``, the worker's)
    is kept in no tree. While ``RECORDER`` records, the tracer's sink hands
    each finished phase to it as one ``proto_<phase>`` X event, category
    ``protocol``, so trace tooling selects the host/protocol side of a
    request with a single prefix (``recorder``: a worker's own ring, where
    it has one). Yields the span's attributes: keys written while it is open
    ride the event."""
    if phase not in PROTOCOL_PHASES:
        raise ValueError(f"unknown protocol phase: {phase!r}")
    return _phase(recorder, phase, args)


@contextmanager
def _phase(recorder, phase: str, args: dict):
    from .tracing import TRACER

    with TRACER.span(phase, root=False, cat="protocol", **args) as span:
        span.recorder = recorder
        yield span.attributes


def _interval_secs() -> float:
    """Sampling interval: $TRINO_TPU_HOSTPROF_INTERVAL_MS, floored at 1ms
    (a sub-millisecond Python sampler would measure mostly itself)."""
    ms = knobs.env_float("TRINO_TPU_HOSTPROF_INTERVAL_MS", 19.0)
    return max(ms, 1.0) / 1000.0


def _ring_capacity() -> int:
    """Sample-ring capacity: $TRINO_TPU_HOSTPROF_RING (per-thread samples),
    floored at 16 like the flight ring."""
    return max(knobs.env_int("TRINO_TPU_HOSTPROF_RING", 4096), 16)


def _frame_label(frame) -> str:
    code = frame.f_code
    filename = code.co_filename.rsplit("/", 1)[-1]
    return f"{code.co_name} ({filename}:{code.co_firstlineno})"


def _collapse(frame) -> Tuple[Tuple[str, ...], str]:
    """(root..leaf frame labels, leaf co_name) of one thread's live stack."""
    labels: List[str] = []
    leaf = ""
    f = frame
    while f is not None:
        labels.append(_frame_label(f))
        f = f.f_back
    labels.reverse()
    if frame is not None:
        leaf = frame.f_code.co_name
    return tuple(labels), leaf


class HostProfiler:
    """Continuous wall-clock sampling profiler over the process's threads.

    Enable/refcount semantics mirror the flight recorder: ``enable()`` /
    ``disable()`` for manual control (servers, tools), ``acquire()`` /
    ``release()`` for scoped users (the ``host_profile`` session property) —
    the sampler thread runs while anyone wants it and exits when the last
    user leaves. The ring never blocks the sampled threads: sampling reads
    interpreter state only (``sys._current_frames``), writes only its own
    deque, and skips its own thread and the probe thread.
    """

    SAMPLER_THREAD_NAME = "hostprof-sampler"

    def __init__(self, interval_secs: Optional[float] = None,
                 capacity: Optional[int] = None):
        self._interval = interval_secs
        self._capacity = capacity
        self.enabled = False  # plain attribute, same contract as RECORDER
        self._lock = threading.Lock()
        # ring of (ts_us, thread_name, (frame labels root..leaf))
        self._buf: deque = deque(maxlen=capacity or _ring_capacity())
        self.dropped_samples = 0
        self.tick_count = 0
        self._manual = False
        self._refs = 0
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Event()

    # ------------------------------------------------------------- control

    def _recompute_locked(self) -> None:
        want = self._manual or self._refs > 0
        self.enabled = want
        if want and (self._thread is None or not self._thread.is_alive()):
            self._wake.clear()
            self._thread = threading.Thread(
                target=self._sample_loop, daemon=True,
                name=self.SAMPLER_THREAD_NAME,
            )
            self._thread.start()
        elif not want:
            self._wake.set()  # sampler exits at its next tick

    def enable(self) -> None:
        with self._lock:
            self._manual = True
            self._recompute_locked()

    def disable(self) -> None:
        with self._lock:
            self._manual = False
            self._recompute_locked()

    def acquire(self) -> None:
        """Scoped enable (refcounted): pair with release()."""
        with self._lock:
            self._refs += 1
            self._recompute_locked()

    def release(self) -> None:
        with self._lock:
            self._refs = max(0, self._refs - 1)
            self._recompute_locked()

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped_samples = 0
            self.tick_count = 0

    def join(self, timeout: float = 2.0) -> None:
        """Wait for the sampler thread to exit (tests; disable() first)."""
        t = self._thread
        if t is not None:
            t.join(timeout)

    # ------------------------------------------------------------ sampling

    def _sample_loop(self) -> None:
        interval = (
            self._interval if self._interval is not None else _interval_secs()
        )
        me = threading.get_ident()
        while self.enabled:
            self._sample_once(me)
            # Event.wait instead of sleep: disable() wakes the thread so a
            # released profiler stops sampling immediately, not a tick later
            if self._wake.wait(interval):
                break

    def _sample_once(self, skip_ident: int) -> None:
        ts_us = time.monotonic_ns() // 1000
        names = {t.ident: t.name for t in threading.enumerate()}
        frames = sys._current_frames()
        runnable = blocked = 0
        samples: List[tuple] = []
        for ident, frame in frames.items():
            if ident == skip_ident:
                continue
            name = names.get(ident)
            if name is None or name == ContentionProbe.PROBE_THREAD_NAME:
                continue
            labels, leaf = _collapse(frame)
            if leaf in _WAIT_LEAVES:
                blocked += 1
            else:
                runnable += 1
                samples.append((ts_us, name, labels))
        dropped = 0
        with self._lock:
            self.tick_count += 1
            for s in samples:
                if len(self._buf) == self._buf.maxlen:
                    self.dropped_samples += 1
                    dropped += 1
                self._buf.append(s)
        update_thread_gauges(runnable=runnable, blocked=blocked)
        if dropped:
            _metric_counter(
                "trino_tpu_hostprof_dropped_samples_total",
                "host-profiler samples pushed off the ring by overflow",
            ).inc(dropped)
        # Perfetto lane: the tick rides the flight ring on THIS thread, so
        # the cluster-trace assembly and canonicalize_trace give the
        # profiler a deterministic "hostprof-sampler" lane for free
        from .observability import RECORDER

        if RECORDER.enabled:
            RECORDER.counter_event(
                "host_threads", "hostprof",
                runnable=runnable, blocked=blocked,
            )
            for _ts, name, labels in samples:
                RECORDER.instant(
                    "host_sample", "hostprof",
                    thread=name, stack=";".join(labels),
                )

    # -------------------------------------------------------------- export

    def samples(self) -> List[tuple]:
        with self._lock:
            return list(self._buf)

    def collapsed(self) -> Dict[str, int]:
        """``"<thread>;<root>;...;<leaf>" -> sample count`` aggregation of
        the current ring (the folded flamegraph key space, thread-rooted)."""
        agg: Dict[str, int] = {}
        for _ts, name, labels in self.samples():
            key = ";".join((name,) + labels)
            agg[key] = agg.get(key, 0) + 1
        return agg

    def collapsed_text(self) -> str:
        """flamegraph.pl folded format, sorted for deterministic output."""
        agg = self.collapsed()
        return "\n".join(f"{k} {n}" for k, n in sorted(agg.items()))

    def speedscope(self, name: str = "trino-tpu host profile") -> dict:
        """The ring as a speedscope 'sampled' document — one profile per
        thread name, frames deduplicated in the shared table, every sample
        weight 1 (wall-clock sampling at a fixed interval). Ordering is
        deterministic: frames and profiles sort on their labels."""
        by_thread: Dict[str, List[Tuple[str, ...]]] = {}
        for _ts, tname, labels in self.samples():
            by_thread.setdefault(tname, []).append(labels)
        frame_index: Dict[str, int] = {}
        all_labels = sorted({
            lab for stacks in by_thread.values() for s in stacks for lab in s
        })
        for lab in all_labels:
            frame_index[lab] = len(frame_index)
        profiles = []
        for tname in sorted(by_thread):
            stacks = by_thread[tname]
            profiles.append({
                "type": "sampled",
                "name": tname,
                "unit": "none",
                "startValue": 0,
                "endValue": len(stacks),
                "samples": [
                    [frame_index[lab] for lab in s] for s in stacks
                ],
                "weights": [1] * len(stacks),
            })
        return {
            "$schema": "https://www.speedscope.app/file-format-schema.json",
            "name": name,
            "exporter": "trino-tpu hostprof",
            "activeProfileIndex": 0,
            "shared": {"frames": [{"name": lab} for lab in all_labels]},
            "profiles": profiles,
        }

    def profile_rows(self) -> List[tuple]:
        """``system.runtime.host_profile`` rows: (thread, stack, samples,
        share) per collapsed stack, heaviest first, share within thread."""
        agg = self.collapsed()
        per_thread: Dict[str, int] = {}
        for key, n in agg.items():
            thread = key.split(";", 1)[0]
            per_thread[thread] = per_thread.get(thread, 0) + n
        rows = []
        for key, n in sorted(agg.items(), key=lambda kv: (-kv[1], kv[0])):
            thread, _, stack = key.partition(";")
            total = per_thread.get(thread, 0)
            rows.append((thread, stack, n, round(n / total, 4) if total else 0.0))
        return rows


def validate_speedscope(doc: dict) -> List[str]:
    """Minimal speedscope-schema validation, the collapsed-stack analogue of
    ``observability.validate_chrome_trace``: required top-level keys, a
    shared frame table of named frames, 'sampled' profiles whose sample
    frame indices are in range and whose weights align 1:1 with samples.
    Returns problems; [] = valid (the smoke check/--speedscope contract)."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    if doc.get("$schema") != (
        "https://www.speedscope.app/file-format-schema.json"
    ):
        problems.append("missing/unknown $schema")
    shared = doc.get("shared")
    frames = shared.get("frames") if isinstance(shared, dict) else None
    if not isinstance(frames, list):
        problems.append("shared.frames missing")
        frames = []
    for i, fr in enumerate(frames):
        if not (isinstance(fr, dict) and isinstance(fr.get("name"), str)
                and fr["name"]):
            problems.append(f"frame {i} has no name")
    profiles = doc.get("profiles")
    if not isinstance(profiles, list) or not profiles:
        problems.append("profiles missing or empty")
        profiles = []
    for pi, prof in enumerate(profiles):
        if not isinstance(prof, dict):
            problems.append(f"profile {pi} not an object")
            continue
        if prof.get("type") != "sampled":
            problems.append(f"profile {pi} type != 'sampled'")
        if not isinstance(prof.get("name"), str):
            problems.append(f"profile {pi} missing name")
        if prof.get("unit") not in (
            "none", "nanoseconds", "microseconds", "milliseconds",
            "seconds", "bytes",
        ):
            problems.append(f"profile {pi} unknown unit {prof.get('unit')!r}")
        samples = prof.get("samples")
        weights = prof.get("weights")
        if not isinstance(samples, list) or not isinstance(weights, list):
            problems.append(f"profile {pi} missing samples/weights")
            continue
        if len(samples) != len(weights):
            problems.append(
                f"profile {pi} samples/weights length mismatch "
                f"({len(samples)} vs {len(weights)})"
            )
        for si, stack in enumerate(samples):
            if not isinstance(stack, list):
                problems.append(f"profile {pi} sample {si} not a list")
                continue
            for idx in stack:
                if not isinstance(idx, int) or not (0 <= idx < len(frames)):
                    problems.append(
                        f"profile {pi} sample {si} frame index {idx!r} "
                        "out of range"
                    )
    return problems


# --------------------------------------------------------------------------- #
# GIL/scheduler contention probe
# --------------------------------------------------------------------------- #


class ContentionProbe:
    """Switch-latency probe: measures how late a short timed sleep wakes up.

    The probe thread asks for ``interval_secs`` of sleep and records
    ``actual - expected`` (clamped at 0). On an idle interpreter the
    lateness is scheduler noise (tens of microseconds); when a runnable
    thread is hogging the GIL the sleeper cannot be rescheduled until the
    holder yields, so the lateness jumps toward the GIL switch interval
    (``sys.getswitchinterval()``, default 5ms) and beyond — the direct,
    per-process measurement of the r13 "host/GIL contention" claim. Jitter
    lands in a bounded ring and the
    ``trino_tpu_host_switch_latency_secs`` histogram.
    """

    PROBE_THREAD_NAME = "hostprof-gilprobe"

    def __init__(self, interval_secs: float = 0.005, capacity: int = 2048):
        self.interval_secs = float(interval_secs)
        self.enabled = False
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=max(capacity, 16))
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        with self._lock:
            if self.enabled:
                return
            self.enabled = True
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name=self.PROBE_THREAD_NAME
            )
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            self.enabled = False
            t = self._thread
        if t is not None:
            t.join(max(self.interval_secs * 4, 0.25))

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def _loop(self) -> None:
        from .metrics import REGISTRY, exponential_buckets

        hist = REGISTRY.histogram(
            "trino_tpu_host_switch_latency_secs",
            help="observed lateness of a timed sleep vs its deadline "
                 "(GIL/scheduler contention probe; ~0 when idle, >= the "
                 "GIL switch interval under a runnable-thread hog)",
            buckets=exponential_buckets(0.0001, 2.0, 12),
        )
        while self.enabled:
            t0 = time.monotonic()
            time.sleep(self.interval_secs)
            jitter = max(time.monotonic() - t0 - self.interval_secs, 0.0)
            with self._lock:
                self._buf.append(jitter)
            hist.observe(jitter)

    def jitters(self) -> List[float]:
        with self._lock:
            return list(self._buf)

    def summary(self) -> dict:
        """p50/p99/max lateness (seconds) over the ring."""
        js = sorted(self.jitters())
        if not js:
            return {"samples": 0, "p50_secs": 0.0, "p99_secs": 0.0,
                    "max_secs": 0.0}
        import math

        def pct(q: float) -> float:
            return js[max(0, min(len(js) - 1, math.ceil(q * len(js)) - 1))]

        return {
            "samples": len(js),
            "p50_secs": round(pct(0.50), 6),
            "p99_secs": round(pct(0.99), 6),
            "max_secs": round(js[-1], 6),
        }


# --------------------------------------------------------------------------- #
# metrics plumbing
# --------------------------------------------------------------------------- #

_counters: Dict[str, object] = {}


def _metric_counter(name: str, help_: str):
    c = _counters.get(name)
    if c is None:
        from .metrics import REGISTRY

        c = _counters[name] = REGISTRY.counter(name, help=help_)
    return c


def update_thread_gauges(runnable: Optional[int] = None,
                         blocked: Optional[int] = None) -> Dict[str, int]:
    """Set ``trino_tpu_host_threads{state=}`` from a sampler classification,
    or (with no arguments) from a one-shot stack walk — the announcement
    path refreshes the gauges this way on hostprof-enabled servers without
    waiting for a sampler tick."""
    from .metrics import REGISTRY

    if runnable is None or blocked is None:
        names = {t.ident: t.name for t in threading.enumerate()}
        me = threading.get_ident()
        runnable = blocked = 0
        for ident, frame in sys._current_frames().items():
            if ident == me or names.get(ident) in (
                HostProfiler.SAMPLER_THREAD_NAME,
                ContentionProbe.PROBE_THREAD_NAME,
            ):
                continue
            _, leaf = _collapse(frame)
            if leaf in _WAIT_LEAVES:
                blocked += 1
            else:
                runnable += 1
    for state, value in (("runnable", runnable), ("blocked", blocked)):
        REGISTRY.gauge(
            "trino_tpu_host_threads", labels={"state": state},
            help="live engine threads by sampled state (hostprof "
                 "classification: leaf frame parked in a known wait -> "
                 "blocked, else runnable)",
        ).set(float(value))
    return {"runnable": runnable, "blocked": blocked}


# --------------------------------------------------------------------------- #
# gating + process singletons
# --------------------------------------------------------------------------- #


def server_enabled() -> bool:
    """Server-process gate: ``$TRINO_TPU_HOSTPROF`` starts the sampler and
    the contention probe at server startup. Default off — a flag-off
    process starts no threads and registers no hostprof series."""
    return knobs.env_flag("TRINO_TPU_HOSTPROF", False)


def session_enabled(session) -> bool:
    """Query-level gate: the ``host_profile`` session property."""
    if session is None:
        return False
    try:
        return bool(session.get("host_profile"))
    except KeyError:
        return False


PROFILER = HostProfiler()
PROBE = ContentionProbe()


def start_server_profiling() -> bool:
    """Idempotent server-startup hook (coordinator/worker ``start()``):
    with $TRINO_TPU_HOSTPROF on, run the sampler + probe for the process
    lifetime. Returns whether the plane is on."""
    if not server_enabled():
        return False
    PROFILER.enable()
    PROBE.start()
    return True
