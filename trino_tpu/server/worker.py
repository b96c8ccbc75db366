"""Worker server: async task lifecycle + pull/ack output buffers.

Reference blueprint (SURVEY.md §2.7, §3.2-3.3):
- server/TaskResource.java:93 — `POST /v1/task/{id}` creates/updates a task,
  `GET /v1/task/{id}?maxWait=..` long-polls status (:230),
  `GET /v1/task/{id}/results/{buffer}/{token}` pulls pages (:334) with
  at-least-once delivery + token acknowledgement (:375),
  `DELETE /v1/task/{id}` aborts.
- execution/SqlTaskManager.java:109 — the task registry;
  execution/buffer/PartitionedOutputBuffer.java:42 — per-consumer buffers
  with backpressure (OutputBufferMemoryManager analogue: bounded unacked
  bytes block the producer).

A task = one fragment × one partition. The plan travels in the schema'd JSON
codec (runtime/plancodec.py) — never executable serialization — and every
internal request carries an HMAC-SHA256 signature under the cluster's shared
secret (ref: server/InternalAuthenticationManager.java).

Tasks pull their RemoteSource inputs directly from the producing workers'
output buffers (worker→worker, DirectExchangeClient.java:270 analogue), so
stages of one query overlap across the cluster instead of executing behind a
coordinator barrier.
"""

from __future__ import annotations

import hashlib
import heapq
import hmac as hmac_mod
import json
import socket
import threading
import time
import urllib.request
from collections import deque
from dataclasses import dataclass, field
from enum import Enum
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

from ..metadata import CatalogManager, Metadata, Session
from ..planner.plan import LogicalPlan
from ..runtime import plancodec
from ..runtime.failure import TaskDeadlineExceeded, chaos_fire
from ..runtime.observability import RECORDER, on_exchange_pull, on_exchange_push
from ..runtime.serde import deserialize_page, serialize_page
from ..runtime.tracing import TRACER
from .. import knobs

SECRET_ENV = "TRINO_TPU_INTERNAL_SECRET"
SIGNATURE_HEADER = "X-Trino-Tpu-Signature"
# producer-side backpressure: unacknowledged bytes per consumer buffer before
# add() blocks (OutputBufferMemoryManager analogue)
MAX_UNACKED_BYTES = 64 * 1024 * 1024


def sign(secret: Optional[str], method: str, path: str, body: bytes = b"") -> str:
    """HMAC over method + path + body hash: a captured signature cannot be
    replayed as a different method (status poll -> DELETE) or task id."""
    if not secret:
        return ""
    msg = (
        method.encode() + b"\n" + path.encode() + b"\n"
        + hashlib.sha256(body).digest()
    )
    return hmac_mod.new(secret.encode(), msg, hashlib.sha256).hexdigest()


def verify(
    secret: Optional[str], method: str, path: str, body: bytes, signature: Optional[str]
) -> bool:
    if not secret:
        return True  # localhost-only deployments may run unauthenticated
    if not signature:
        return False
    return hmac_mod.compare_digest(sign(secret, method, path, body), signature)


class TaskFailedError(RuntimeError):
    """A producer task reported FAILED. ``error_text`` is the task's error;
    callers distinguish infrastructure failures (retryable) from
    deterministic query errors by it."""

    def __init__(self, task_id: str, error_text: str):
        super().__init__(f"producer task {task_id} failed: {error_text}")
        self.error_text = error_text or ""


def pull_buffer(url: str, task_id: str, buffer_id: int, secret: Optional[str],
                deadline: Optional[float] = None):
    """Generator of page blobs from a producer task's output buffer — THE
    exchange-client wire protocol (token-acked pulls, at-least-once; ref:
    operator/DirectExchangeClient.java:270, HttpPageBufferClient:348). Shared
    by worker->worker input pulls and the coordinator's root-result pull.
    Raises TaskFailedError when the producer task failed.

    ``deadline`` (monotonic seconds) bounds the WHOLE pull: a producer that
    accepts its task then hangs raises TaskDeadlineExceeded here instead of
    stalling the consumer forever (each 2 s long-poll returning empty used
    to loop unbounded)."""
    token = 0
    while True:
        if deadline is not None and time.monotonic() >= deadline:
            raise TaskDeadlineExceeded(
                f"pull of task {task_id} buffer {buffer_id} exceeded its "
                f"completion deadline"
            )
        timeout = 300.0
        if deadline is not None:
            timeout = max(1.0, min(300.0, deadline - time.monotonic() + 5.0))
        rel = f"/v1/task/{task_id}/results/{buffer_id}/{token}"
        req = urllib.request.Request(f"{url.rstrip('/')}{rel}?maxWait=2", method="GET")
        req.add_header(SIGNATURE_HEADER, sign(secret, "GET", rel))
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            meta = json.loads(resp.headers.get("X-Page-Meta", "{}"))
            body = resp.read()
        # failure checked BEFORE completion: a task that failed without
        # emitting pages must never read as an empty successful buffer
        if meta.get("failed"):
            raise TaskFailedError(task_id, str(meta.get("error")))
        off = 0
        for size in meta.get("sizes", []):
            yield body[off : off + size]
            off += size
        token = int(meta.get("next_token", token))
        if meta.get("complete") and not meta.get("sizes"):
            return


class TaskState(Enum):
    RUNNING = "RUNNING"
    FINISHED = "FINISHED"
    FAILED = "FAILED"
    CANCELED = "CANCELED"


@dataclass
class TaskDescriptor:
    """What the coordinator ships per task (HttpRemoteTask's update payload).

    ``inputs``: fragment_id -> {"exchange_type": str, "buffer": int,
    "sources": [{"url": str, "task": str}], "inline": [page bytes hex]}.
    ``output``: {"kind": "partitioned"|"gather"|"broadcast", "n": int,
    "keys": [symbol, ...]} — how this task's output splits into buffers.
    """

    root: object = None
    types: Dict[str, object] = field(default_factory=dict)
    session_props: Dict[str, object] = field(default_factory=dict)
    partition: int = 0
    n_workers: int = 1
    inputs: Dict[int, dict] = field(default_factory=dict)
    output: dict = field(default_factory=lambda: {"kind": "gather", "n": 1})
    # coordinator-side trace parentage (Tracer.capture_ids()): worker task
    # spans join the query trace instead of orphaning — task creation
    # arrives over HTTP, so a same-process capture can't carry it
    trace: Optional[Dict[str, str]] = None
    # task completion deadline RELATIVE seconds (the scheduler's
    # task_completion_timeout): a task still queued past it fails instead
    # of starting work the coordinator already abandoned
    deadline_secs: Optional[float] = None
    # resource-group scheduling weight of the owning query (the
    # coordinator's device_scheduler.current_priority() at dispatch time):
    # the fair executor drains higher-weight groups first
    priority: float = 1.0


def encode_task(desc: TaskDescriptor) -> bytes:
    payload = {
        "root": plancodec.encode(desc.root),
        "types": plancodec.encode(desc.types),
        "session_props": plancodec.encode(desc.session_props),
        "partition": desc.partition,
        "n_workers": desc.n_workers,
        "inputs": {
            str(fid): {
                **{k: v for k, v in spec.items() if k != "inline"},
                "inline": [b.hex() for b in spec.get("inline", [])],
            }
            for fid, spec in desc.inputs.items()
        },
        "output": desc.output,
    }
    if desc.trace:
        payload["trace"] = desc.trace
    if desc.deadline_secs is not None:
        payload["deadline_secs"] = desc.deadline_secs
    if desc.priority != 1.0:
        payload["priority"] = desc.priority
    return json.dumps(payload, separators=(",", ":")).encode()


def decode_task(data: bytes) -> TaskDescriptor:
    payload = json.loads(data)
    return TaskDescriptor(
        root=plancodec.decode(payload["root"]),
        types=plancodec.decode(payload["types"]),
        session_props=plancodec.decode(payload["session_props"]),
        partition=payload["partition"],
        n_workers=payload["n_workers"],
        inputs={
            int(fid): {
                **{k: v for k, v in spec.items() if k != "inline"},
                "inline": [bytes.fromhex(h) for h in spec.get("inline", [])],
            }
            for fid, spec in payload["inputs"].items()
        },
        output=payload["output"],
        trace=payload.get("trace"),
        deadline_secs=payload.get("deadline_secs"),
        priority=float(payload.get("priority", 1.0)),
    )


class OutputBuffer:
    """Per-task partitioned output: n consumer buffers of serialized pages,
    pull-based with token acknowledgement (at-least-once + dedup by token,
    ref: execution/buffer/PartitionedOutputBuffer.java:42, ClientBuffer).
    Acknowledged pages are FREED — the ack exists to release memory, not just
    to relieve backpressure accounting.

    Backpressure accounting is a TRACKED byte counter per consumer buffer
    (the old path re-summed every buffered page on each 0.1 s poll wakeup —
    O(pages) work burning CPU under a slow consumer); producers now block on
    the condition and are woken by the ack that frees bytes. Broadcast blobs
    are stored once (one shared bytes object in every buffer) and their
    length is CHARGED once, split across the consumer buffers — the old
    accounting charged the same blob n times and tripped backpressure at 1/n
    of the real memory limit."""

    def __init__(self, n_buffers: int):
        self._cond = threading.Condition()
        self._pages: List[List[bytes]] = [[] for _ in range(n_buffers)]
        # charged bytes per buffered page (== len(page) for exclusive blobs,
        # a 1/n share for broadcast blobs), aligned with _pages
        self._charges: List[List[int]] = [[] for _ in range(n_buffers)]
        self._bytes: List[int] = [0] * n_buffers  # tracked unacked charge
        self._base: List[int] = [0] * n_buffers  # token of _pages[b][0]
        self._complete = False

    def buffered_bytes(self) -> int:
        """Total unacked charged bytes (observability; shared blobs once)."""
        with self._cond:
            return sum(self._bytes)

    def _append_locked(self, buffer_id: int, page: bytes, charge: int) -> None:
        self._pages[buffer_id].append(page)
        self._charges[buffer_id].append(charge)
        self._bytes[buffer_id] += charge
        self._cond.notify_all()

    def add(self, buffer_id: int, page: bytes) -> None:
        on_exchange_push(len(page))
        with self._cond:
            # backpressure: block while this consumer is too far behind
            # (woken by the ack in get() or by set_complete — no polling)
            while self._bytes[buffer_id] > MAX_UNACKED_BYTES and not self._complete:
                self._cond.wait()
            self._append_locked(buffer_id, page, len(page))

    def add_broadcast(self, page: bytes) -> None:
        """One blob into EVERY consumer buffer: stored shared (n references
        to one bytes object) and charged ONCE — len(page) split across the
        buffers — so a broadcast edge hits backpressure at the same real
        memory bound as a partitioned one."""
        n = len(self._pages)
        if n == 0:
            return
        on_exchange_push(len(page))  # pushed once, not n times
        share, rem = divmod(len(page), n)
        with self._cond:
            while (
                max(self._bytes) > MAX_UNACKED_BYTES and not self._complete
            ):
                self._cond.wait()
            for b in range(n):
                self._append_locked(b, page, share + (1 if b < rem else 0))

    def set_complete(self) -> None:
        with self._cond:
            self._complete = True
            self._cond.notify_all()

    def get(
        self, buffer_id: int, token: int, max_wait: float
    ) -> Tuple[List[bytes], int, bool]:
        """Pages from sequence ``token`` on; requesting token N acknowledges
        (and frees) everything below N. Re-requests of unacked tokens are
        served (at-least-once); acked tokens are gone."""
        deadline = time.monotonic() + max_wait
        with self._cond:
            drop = max(0, min(token - self._base[buffer_id], len(self._pages[buffer_id])))
            if drop:
                self._bytes[buffer_id] -= sum(self._charges[buffer_id][:drop])
                del self._pages[buffer_id][:drop]
                del self._charges[buffer_id][:drop]
                self._base[buffer_id] += drop
            self._cond.notify_all()
            while True:
                start = token - self._base[buffer_id]
                pages = self._pages[buffer_id][max(start, 0):]
                if pages or self._complete:
                    return pages, token + len(pages), self._complete
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return [], token, False
                self._cond.wait(remaining)


@dataclass
class Task:
    task_id: str
    state: TaskState = TaskState.RUNNING
    error: Optional[str] = None
    buffer: Optional[OutputBuffer] = None
    version: int = 0  # bumped on each state change (status long-poll)
    ended_at: Optional[float] = None  # monotonic time of terminal transition
    # scheduling observability (PrioritizedSplitRunner stats analogue)
    queued_at: Optional[float] = None
    started_at: Optional[float] = None
    # absolute (monotonic) completion deadline, from the descriptor
    deadline: Optional[float] = None

    @property
    def queued_secs(self) -> Optional[float]:
        if self.queued_at is None or self.started_at is None:
            return None
        return self.started_at - self.queued_at

    @property
    def run_secs(self) -> Optional[float]:
        if self.started_at is None:
            return None
        return (self.ended_at or time.monotonic()) - self.started_at


class FairTaskExecutor:
    """Bounded worker pool draining a FAIR queue: the next task to start is
    the one whose QUERY has accumulated the least WEIGHTED scheduled time
    (ref: executor/timesharing/TimeSharingTaskExecutor.java:84 +
    MultilevelSplitQueue; weights are the reference's resource-group
    scheduling weights). Our work units are whole single-dispatch device
    programs — not preemptible mid-run on a TPU — so the reference's 1 s
    quanta fairness acts at task-start granularity here: a query that has
    consumed the executor yields the next slot to the least-served query.
    The heap key is ``usage / weight``: a weight-4 group's query pops
    ahead of an equal-usage weight-1 query (it is "owed" 4x the share) —
    the round-9 per-query FIFO ignored the group weight entirely, so
    high-priority groups queued behind whoever arrived first. Per-task
    queue/run times are recorded for EXPLAIN-level observability (the
    PrioritizedSplitRunner stats analogue)."""

    def __init__(self, n_threads: int = 4):
        self._cond = threading.Condition()
        # per-query FIFO + a heap of (usage/weight snapshot, head seq,
        # query_id): picking the next task is O(log n) instead of the old
        # full re-sort under the lock. Heap entries go stale when a query's
        # usage (or weight) moves between push and pop; a stale entry is
        # re-pushed with the current key (lazy decrease-key), so each pop
        # is amortized O(log n).
        self._queues: Dict[str, deque] = {}  # query -> [(seq, task_id, fn), ...]
        self._heap: list = []  # [usage/weight, head_seq, query_id]
        self._in_heap: set = set()
        self._pending = 0
        self._usage: Dict[str, float] = {}
        self._weights: Dict[str, float] = {}  # query -> group weight (>=, max)
        self._running: Dict[str, int] = {}  # query -> in-flight task count
        self._seq = 0
        self._shutdown = False
        self._threads = [
            threading.Thread(target=self._loop, daemon=True, name=f"fair-exec-{i}")
            for i in range(max(1, n_threads))
        ]
        for t in self._threads:
            t.start()

    def _key_locked(self, query_id: str) -> float:
        return self._usage.get(query_id, 0.0) / self._weights.get(query_id, 1.0)

    def submit(self, query_id: str, task_id: str, fn, weight: float = 1.0) -> None:
        with self._cond:
            self._seq += 1
            self._usage.setdefault(query_id, 0.0)
            self._weights[query_id] = max(
                self._weights.get(query_id, 1.0), float(weight) or 1.0
            )
            dq = self._queues.get(query_id)
            if dq is None:
                dq = self._queues[query_id] = deque()
            dq.append((self._seq, task_id, fn))
            self._pending += 1
            if query_id not in self._in_heap:
                heapq.heappush(
                    self._heap, (self._key_locked(query_id), dq[0][0], query_id)
                )
                self._in_heap.add(query_id)
            # bound the usage ledger on long-lived workers: evict idle
            # queries (none queued) once the ledger grows past a cap —
            # re-arrival simply restarts them at zero (slightly favored,
            # exactly how a fresh query is treated)
            if len(self._usage) > 512:
                active = {q for q, dq in self._queues.items() if dq} | {
                    q for q, n in self._running.items() if n > 0
                }
                for q in [q for q in self._usage if q not in active][:256]:
                    del self._usage[q]
                    self._weights.pop(q, None)
            self._cond.notify()

    def _pop_locked(self):
        """Least weighted-served query first; FIFO within a query (heap
        invariant: every query with queued tasks has exactly one heap
        entry)."""
        while True:
            key, _, query_id = heapq.heappop(self._heap)
            q = self._queues.get(query_id)
            if not q:  # ledger-evicted or drained under a stale entry
                self._in_heap.discard(query_id)
                continue
            current = self._key_locked(query_id)
            if key != current:  # stale snapshot: re-key and retry
                heapq.heappush(self._heap, (current, q[0][0], query_id))
                continue
            seq, task_id, fn = q.popleft()
            self._pending -= 1
            if q:
                heapq.heappush(self._heap, (current, q[0][0], query_id))
            else:
                del self._queues[query_id]
                self._in_heap.discard(query_id)
            return query_id, seq, task_id, fn

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._pending and not self._shutdown:
                    self._cond.wait()
                if self._shutdown:
                    return
                query_id, _, task_id, fn = self._pop_locked()
                self._running[query_id] = self._running.get(query_id, 0) + 1
            t0 = time.monotonic()
            try:
                fn()
            finally:
                with self._cond:
                    self._usage[query_id] = (
                        self._usage.get(query_id, 0.0) + time.monotonic() - t0
                    )
                    left = self._running.get(query_id, 1) - 1
                    if left:
                        self._running[query_id] = left
                    else:
                        self._running.pop(query_id, None)

    def stop(self) -> None:
        with self._cond:
            self._shutdown = True
            self._cond.notify_all()


def _query_of(task_id: str) -> str:
    """Task ids are '<query>_f<fid>_p<p>...' — fall back to the whole id."""
    return task_id.split("_f")[0] if "_f" in task_id else task_id


# process-wide TaskManager registry: system.runtime.tasks snapshots every
# live manager in this host process (weak — a stopped WorkerServer's manager
# disappears with it)
import weakref

_TASK_MANAGERS: "weakref.WeakSet" = weakref.WeakSet()


def all_task_managers():
    """Live TaskManagers in this process (system.runtime.tasks source)."""
    return list(_TASK_MANAGERS)


class TaskManager:
    """ref: execution/SqlTaskManager.java:109 — the worker-side registry.
    Terminal tasks are evicted after ``task_ttl_secs`` (QueryTracker-style
    expiry), so long-lived workers don't retain query outputs forever."""

    def __init__(
        self,
        metadata: Metadata,
        secret: Optional[str],
        task_ttl_secs: float = 300.0,
        task_threads: int = 4,
        memory_pool=None,
        recorder=None,
    ):
        from ..runtime.memory import default_pool

        self.metadata = metadata
        self.secret = secret
        self.task_ttl_secs = task_ttl_secs
        # cluster observability plane: the flight recorder this worker's
        # task spans land in and /v1/flightrecorder serves from. Defaults
        # to the process-global ring (one process = one node); tests and
        # multi-worker-per-process harnesses install per-node rings here.
        self.recorder = recorder if recorder is not None else RECORDER
        # worker memory pool (ref: the worker half of io.trino.memory): task
        # fragment executors reserve against it under the TASK id, so one
        # worker's HBM backpressures its tasks; the pool state rides the
        # announcement path for the coordinator's ClusterMemoryManager.
        # Kill decisions stay coordinator-side (no kill_fn here).
        self.memory_pool = memory_pool if memory_pool is not None else default_pool()
        self._tasks: Dict[str, Task] = {}
        self.created_total = 0  # lifetime counter (placement observability)
        self._cond = threading.Condition()
        self.executor = FairTaskExecutor(task_threads)
        # local-exchange shortcut: this worker's own URLs (set by
        # WorkerServer.start) — pulls from self read the producer buffer
        # in-process instead of looping through HTTP
        self.self_urls: set = set()
        self.local_exchange_pages = 0
        # system.runtime.tasks identity (WorkerServer sets the bound address)
        self.node_id = "worker"
        _TASK_MANAGERS.add(self)

    def count(self) -> int:
        """Lifetime created-task count (scheduler-placement observability)."""
        return self.created_total

    def snapshot(self) -> List[dict]:
        """Lock-brief task rows for system.runtime.tasks: the registry lock
        is held only to copy the task list; per-task fields are plain reads
        of monotonic attributes (a racing transition skews one row by one
        state, which the eventually-consistent contract allows)."""
        with self._cond:
            tasks = list(self._tasks.values())
        rows = []
        for t in tasks:
            buffered = None
            if t.buffer is not None:
                buffered = t.buffer.buffered_bytes()
            rows.append({
                "nodeId": self.node_id,
                "taskId": t.task_id,
                "queryId": _query_of(t.task_id),
                "state": t.state.value,
                "error": t.error,
                "queuedSecs": t.queued_secs,
                "runSecs": t.run_secs,
                "bufferedPages": buffered,
            })
        return rows

    def get(self, task_id: str) -> Optional[Task]:
        with self._cond:
            return self._tasks.get(task_id)

    def _evict_expired_locked(self) -> None:
        now = time.monotonic()
        for tid in [
            t.task_id
            for t in self._tasks.values()
            if t.state != TaskState.RUNNING
            and t.ended_at is not None
            and now - t.ended_at > self.task_ttl_secs
        ]:
            del self._tasks[tid]

    def create(self, task_id: str, desc: TaskDescriptor) -> Task:
        with self._cond:
            self._evict_expired_locked()
            existing = self._tasks.get(task_id)
            if existing is not None:
                return existing  # idempotent create-or-update
            self.created_total += 1
            task = Task(task_id, buffer=OutputBuffer(int(desc.output.get("n", 1))))
            task.queued_at = time.monotonic()
            if desc.deadline_secs is not None:
                task.deadline = task.queued_at + float(desc.deadline_secs)
            self._tasks[task_id] = task
        # ONLY fully self-contained tasks ride the bounded fair pool: durable
        # (FTE) outputs commit to the exchange store and push a zero-byte
        # buffer marker, so a pooled task can never block. Tasks that either
        # PULL peer buffers ("sources" inputs) or PRODUCE consumer-pulled
        # buffers can block on peers/backpressure while holding a pool
        # thread — with a bounded pool that deadlocks (producers waiting on
        # a consumer that waits on a queued producer) — so they keep a
        # dedicated thread (ThreadPerDriverTaskExecutor role).
        streaming = (
            any(spec.get("sources") for spec in desc.inputs.values())
            or desc.output.get("kind") != "durable"
        )
        # trace-context propagation: FTE/streaming task threads (and fair-
        # pool slots) get fresh Tracer thread-local stacks — capture the
        # submitting thread's span so task spans join the query trace
        run = TRACER.wrap(lambda: self._run(task, desc))
        if streaming:
            thread = threading.Thread(
                target=run, daemon=True, name=f"task-{task_id}",
            )
            thread.start()
        else:
            # the descriptor carries the owning query's resource-group
            # scheduling weight — the fair pop drains heavy groups first
            self.executor.submit(
                _query_of(task_id), task_id, run, weight=desc.priority
            )
        return task

    def cancel(self, task_id: str) -> Optional[Task]:
        task = self.get(task_id)
        if task is not None:
            self._transition(task, TaskState.CANCELED)
            task.buffer.set_complete()
        return task

    def delete(self, task_id: str) -> Optional[Task]:
        """Abort + drop immediately (the coordinator's end-of-query cleanup)."""
        task = self.cancel(task_id)
        with self._cond:
            self._tasks.pop(task_id, None)
        return task

    def status_longpoll(self, task_id: str, version: int, max_wait: float) -> Optional[Task]:
        deadline = time.monotonic() + max_wait
        with self._cond:
            while True:
                task = self._tasks.get(task_id)
                if task is None or task.version > version or task.state != TaskState.RUNNING:
                    return task
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return task
                self._cond.wait(remaining)

    def _transition(self, task: Task, state: TaskState, error: Optional[str] = None):
        with self._cond:
            if task.state == TaskState.RUNNING:
                task.state = state
                task.error = error
                task.ended_at = time.monotonic()
            task.version += 1
            self._cond.notify_all()

    # --------------------------------------------------------------- execution

    def _run(self, task: Task, desc: TaskDescriptor) -> None:
        from ..runtime.memory import memory_scope

        task.started_at = time.monotonic()
        try:
            if task.deadline is not None and task.started_at > task.deadline:
                # queued past its completion deadline: the coordinator has
                # already abandoned this attempt — fail fast instead of
                # burning executor time on work nobody will read
                raise TaskDeadlineExceeded(
                    f"task {task.task_id} started after its completion deadline"
                )
            # parentage into the query trace comes from desc.trace (the
            # coordinator's capture_ids(), shipped in the descriptor — task
            # creation arrives over HTTP on a span-less handler thread) or,
            # for in-process schedulers, the context captured at create()
            # via TRACER.wrap. Without either the task span would orphan.
            # The memory scope charges the fragment executor's reservations
            # to the worker pool under the TASK id (freed when it ends).
            with TRACER.attach_remote(desc.trace), TRACER.span(
                "task", task_id=task.task_id
            ), self.recorder.span("task", "task", task_id=task.task_id), \
                    memory_scope(task.task_id, self.memory_pool):
                self._run_inner(task, desc)
            task.buffer.set_complete()
            self._transition(task, TaskState.FINISHED)
        except Exception as e:  # noqa: BLE001 — failures become task state
            # transition BEFORE completing the buffer: a consumer woken by
            # set_complete must observe FAILED, never a "successful" partial
            # buffer (cancel() relies on the same order)
            self._transition(task, TaskState.FAILED, f"{type(e).__name__}: {e}")
            task.buffer.set_complete()
        finally:
            if self.memory_pool is not None:
                self.memory_pool.free_owner(task.task_id)

    def memory_info(self) -> dict:
        """This worker's pool state for the announcement path (empty dict
        when no pool is configured — arbitration is opt-in)."""
        if self.memory_pool is None:
            return {}
        return self.memory_pool.memory_announcement()

    def _run_inner(self, task: Task, desc: TaskDescriptor) -> None:
        from ..parallel.runner import _FragmentExecutor, run_fragment_partition
        from ..spi.host_pages import (
            page_from_host_chunks as _page_from_host_chunks,
            page_to_host as _page_to_host,
        )

        from ..runtime.spiller import io_pool

        staged = {}
        for fid, spec in desc.inputs.items():
            # deserialize on the shared I/O pool: frame decode (LZ4 +
            # device_put) of blob i overlaps the pull of blob i+1 — the
            # exchange-tier mirror of the OOC double buffer
            pool = io_pool()
            futs = [
                pool.submit(deserialize_page, b) for b in spec.get("inline", [])
            ]
            for src in spec.get("sources", []):
                for blob in self._pull_pages(
                    src["url"], src["task"], int(spec.get("buffer", 0))
                ):
                    futs.append(pool.submit(deserialize_page, blob))
            pages = [f.result() for f in futs]
            durable = spec.get("durable")
            if durable is not None:
                # worker-direct FTE data plane: read this task's input
                # parts straight from the durable exchange store — the
                # coordinator shipped only this descriptor (ref:
                # FileSystemExchangeSource; exchange bytes never touch
                # the coordinator)
                from ..runtime.fte_plane import stage_durable_input

                staged[fid] = [stage_durable_input(durable, desc.types)]
                continue
            if not pages:
                raise RuntimeError(f"no input pages for fragment {fid}")
            staged[fid] = [
                _page_from_host_chunks([_page_to_host(p) for p in pages])
            ]
        session = Session(properties=dict(desc.session_props))
        plan = LogicalPlan(desc.root, desc.types)
        executor = _FragmentExecutor(
            plan, self.metadata, session, staged, desc.partition, desc.n_workers
        )
        # device batching plane: concurrent tasks on this worker pack
        # compatible fragment subtrees / share overlapping scans (no-op
        # unless the session ships device_batching=true)
        from ..runtime.device_scheduler import attach as _attach_batching

        _attach_batching(
            executor, self.metadata, session,
            catalogs=getattr(self.metadata, "catalogs", None),
            scope=f"part{desc.partition}/{desc.n_workers}",
        )
        out_page = run_fragment_partition(executor, desc.root)
        self._emit_output(task, desc, out_page)

    def _emit_output(self, task: Task, desc: TaskDescriptor, page) -> None:
        from ..ops.repartition import (
            device_repartition_enabled,
            repartition_frames,
            supports_device_repartition,
        )
        from ..runtime.spiller import io_pool
        from ..spi.host_pages import (
            host_partition_targets,
            page_to_host as _page_to_host,
            pages_from_host_rows as _pages_from_host_rows,
        )

        kind = desc.output.get("kind", "gather")
        n = int(desc.output.get("n", 1))
        if kind == "durable":
            # worker-direct FTE data plane: partition + COMMIT to the durable
            # exchange here; the coordinator learns success from task state
            # only (ref: FileSystemExchangeSink — workers write shuffle
            # storage directly)
            self._emit_durable(desc, page)
            task.buffer.add(0, b"")  # completion marker, no payload
            return
        if kind == "gather" or n == 1:
            task.buffer.add(0, serialize_page(page))
            return
        if kind == "broadcast":
            # serialized once, stored shared, charged once (add_broadcast)
            task.buffer.add_broadcast(serialize_page(page))
            return
        # partitioned: split rows by key hash. Primary path is the compiled
        # device epilogue (ops/repartition.py): ONE D2H of a partition-
        # contiguous page + sliced v2 frames, instead of whole-page D2H +
        # numpy hashing + n boolean selection passes.
        out_syms = list(desc.output.get("symbols", []))
        key_idx = [out_syms.index(k) for k in desc.output.get("keys", [])]
        if (
            page.columns
            and device_repartition_enabled()
            and supports_device_repartition(page)
        ):
            blobs, _ = repartition_frames(page, key_idx, n, pool=io_pool())
            for b in range(n):
                task.buffer.add(b, blobs[b])
            return
        # host fallback: nested layouts or the A/B kill-switch
        cols = _page_to_host(page)
        if not cols or len(cols[0][1]) == 0:
            task.buffer.add_broadcast(serialize_page(page))
            return
        target = host_partition_targets(cols, key_idx, n)
        for b in range(n):
            sel = target == b
            task.buffer.add(b, serialize_page(_pages_from_host_rows(cols, sel)))

    def _emit_durable(self, desc: TaskDescriptor, page) -> None:
        from ..runtime.fte_plane import emit_durable_output

        emit_durable_output(desc.output, page)

    def _pull_pages(self, url: str, producer_task: str, buffer_id: int):
        """STREAM one producer's buffer to completion (DirectExchangeClient):
        blobs yield as they arrive (exchange-pull accounted per frame), so
        the caller overlaps deserialization with the remaining pulls. When
        the producer runs on THIS worker the pages hand off in-process
        (LocalExchange.java:66 role — no HTTP loop through the kernel)."""
        if url.rstrip("/") in self.self_urls:
            source = self._pull_local(producer_task, buffer_id)
        else:
            source = pull_buffer(url, producer_task, buffer_id, self.secret)
        for p in source:
            on_exchange_pull(len(p))
            yield p

    def _pull_local(self, producer_task: str, buffer_id: int):
        token = 0
        while True:
            task = self.get(producer_task)
            if task is None:
                raise TaskFailedError(producer_task, "task vanished")
            blobs, next_token, complete = task.buffer.get(
                buffer_id, token, max_wait=2.0
            )
            # failure checked BEFORE completion (same order as the HTTP
            # handler): a failed task must never read as an empty success
            if task.state == TaskState.FAILED:
                raise TaskFailedError(producer_task, str(task.error))
            self.local_exchange_pages += len(blobs)
            yield from blobs
            token = next_token
            if complete and not blobs:
                return


class WorkerServer:
    """Executes fragments against locally-registered catalogs (workers mount
    the same catalog config as the coordinator, as in Trino)."""

    def __init__(
        self,
        catalogs: CatalogManager,
        host: str = "127.0.0.1",
        port: int = 0,
        secret: Optional[str] = None,
        task_threads: int = 4,
    ):
        self.catalogs = catalogs
        self.metadata = Metadata(catalogs)
        self.host = host
        self.secret = secret if secret is not None else knobs.env_str(SECRET_ENV)
        if host not in ("127.0.0.1", "localhost") and not self.secret:
            raise ValueError(
                "non-localhost workers require a shared secret "
                f"({SECRET_ENV} or secret=...) for request authentication"
            )
        self.tasks = TaskManager(self.metadata, self.secret, task_threads=task_threads)
        # cluster observability: RTT of the last announce round trip (µs),
        # carried on the NEXT announcement's clock rider; None until the
        # first round trip is measured (a claimed rtt=0 would win ClockSync's
        # min-RTT rule forever and lock in a one-way-delay-biased offset)
        self._last_announce_rtt_us: Optional[float] = None
        worker = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _chaos_transport(self) -> bool:
                """Chaos-harness RPC faults (ref: InjectedFailureType's
                TASK_MANAGEMENT_REQUEST_FAILURE/TIMEOUT): ``transport_refuse``
                drops the connection unanswered (the client sees a reset,
                exactly like a crashed worker), ``transport_hang`` stalls the
                reply past the caller's deadline, ``transport_slow`` adds
                latency but answers. Returns True when the request was
                swallowed."""
                text = self.path
                if chaos_fire("transport_refuse", text=text) is not None:
                    try:
                        self.connection.shutdown(socket.SHUT_RDWR)
                    except OSError:  # lint: disable=bare-except-swallow -- chaos refusal path: the socket may already be gone
                        pass
                    self.close_connection = True
                    return True
                act = chaos_fire("transport_hang", text=text)
                if act is not None:
                    time.sleep(float(act.get("delay", 5.0)))
                else:
                    act = chaos_fire("transport_slow", text=text)
                    if act is not None:
                        time.sleep(float(act.get("delay", 0.1)))
                return False

            def _reply(self, code: int, body: bytes = b"", headers=()):
                self.send_response(code)
                for k, v in headers:
                    self.send_header(k, v)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                if body:
                    self.wfile.write(body)

            def _task_parts(self):
                parts = [p for p in self.path.split("?")[0].split("/") if p]
                if len(parts) >= 3 and parts[0] == "v1" and parts[1] == "task":
                    return parts[2:]
                return None

            def do_POST(self):
                # host-path plane: the worker's protocol phases (accept ->
                # HMAC verify -> parse/decode -> dispatch) get the same
                # paired proto_* spans as the coordinator front
                from ..runtime.hostprof import phase_span

                rec = worker.tasks.recorder
                with phase_span(rec, "accept", path="task_create"):
                    if self._chaos_transport():
                        return
                    length = int(self.headers.get("Content-Length", 0))
                    body = self.rfile.read(length)
                    rel = self.path.split("?")[0]
                    with phase_span(rec, "verify"):
                        ok = verify(
                            worker.secret, "POST", rel, body,
                            self.headers.get(SIGNATURE_HEADER),
                        )
                    if not ok:
                        self._reply(401, b"invalid signature")
                        return
                    parts = self._task_parts()
                    if parts is None or len(parts) != 1:
                        self._reply(404)
                        return
                    try:
                        with phase_span(rec, "parse", task_id=parts[0]):
                            desc = decode_task(body)
                        with phase_span(rec, "dispatch", task_id=parts[0]):
                            task = worker.tasks.create(parts[0], desc)
                        self._reply(200, _status_json(task))
                    except Exception as e:  # noqa: BLE001
                        self._reply(400, f"{type(e).__name__}: {e}".encode())

            def do_GET(self):
                if self._chaos_transport():
                    return
                if self.path.split("?")[0] == "/v1/flightrecorder":
                    # cluster observability plane: this node's flight-
                    # recorder segment, filtered by query id — the
                    # coordinator's cross-node trace assembly pulls it.
                    # Gated on $TRINO_TPU_CLUSTER_OBS (404 when off, byte-
                    # identical to the pre-plane worker) and signed like
                    # every other internal request.
                    from ..runtime import clusterobs

                    if not clusterobs.server_enabled():
                        self._reply(404)
                        return
                    if not verify(
                        worker.secret, "GET", "/v1/flightrecorder", b"",
                        self.headers.get(SIGNATURE_HEADER),
                    ):
                        self._reply(401, b"invalid signature")
                        return
                    query = dict(
                        kv.split("=", 1)
                        for kv in (self.path.split("?", 1) + [""])[1].split("&")
                        if "=" in kv
                    )
                    qid = query.get("query_id", "")
                    trace = clusterobs.local_segment(
                        [qid] if qid else [], recorder=worker.tasks.recorder
                    )
                    self._reply(200, json.dumps({
                        "node": worker.tasks.node_id,
                        "monoUs": time.monotonic_ns() // 1000,
                        "trace": trace,
                    }).encode())
                    return
                if self.path.split("?")[0] == "/v1/memory":
                    # worker pool state (the announcement payload's source of
                    # truth) — signed like every other worker request: pool
                    # pressure is cluster-internal state
                    if not verify(
                        worker.secret, "GET", "/v1/memory", b"",
                        self.headers.get(SIGNATURE_HEADER),
                    ):
                        self._reply(401, b"invalid signature")
                        return
                    self._reply(
                        200, json.dumps(worker.tasks.memory_info()).encode()
                    )
                    return
                parts = self._task_parts()
                if parts is None:
                    self._reply(404)
                    return
                if not verify(
                    worker.secret,
                    "GET",
                    self.path.split("?")[0],
                    b"",
                    self.headers.get(SIGNATURE_HEADER),
                ):
                    self._reply(401, b"invalid signature")
                    return
                query = dict(
                    kv.split("=", 1)
                    for kv in (self.path.split("?", 1) + [""])[1].split("&")
                    if "=" in kv
                )
                if len(parts) == 1:
                    task = worker.tasks.status_longpoll(
                        parts[0],
                        int(query.get("version", -1)),
                        float(query.get("maxWait", 0)),
                    )
                    if task is None:
                        self._reply(404)
                    else:
                        self._reply(200, _status_json(task))
                    return
                if len(parts) == 4 and parts[1] == "results":
                    task = worker.tasks.get(parts[0])
                    if task is None:
                        self._reply(404)
                        return
                    from ..runtime.hostprof import phase_span

                    with phase_span(
                        worker.tasks.recorder, "result_stream",
                        task_id=parts[0],
                    ) as stream_end:
                        pages, next_token, complete = task.buffer.get(
                            int(parts[2]), int(parts[3]),
                            float(query.get("maxWait", 1.0)),
                        )
                        meta = {
                            "sizes": [len(p) for p in pages],
                            "next_token": next_token,
                            "complete": complete,
                            "failed": task.state == TaskState.FAILED,
                            "error": task.error,
                        }
                        stream_end["bytes"] = sum(len(p) for p in pages)
                        self._reply(
                            200,
                            b"".join(pages),
                            headers=[("X-Page-Meta", json.dumps(meta))],
                        )
                    return
                self._reply(404)

            def do_DELETE(self):
                parts = self._task_parts()
                if parts is None or len(parts) != 1:
                    self._reply(404)
                    return
                if not verify(
                    worker.secret,
                    "DELETE",
                    self.path.split("?")[0],
                    b"",
                    self.headers.get(SIGNATURE_HEADER),
                ):
                    self._reply(401, b"invalid signature")
                    return
                task = worker.tasks.delete(parts[0])
                self._reply(200 if task else 404, _status_json(task) if task else b"")

        self._server = ThreadingHTTPServer((host, port), Handler)
        self.port = self._server.server_port
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def announcement_body(self) -> dict:
        """The /v1/announcement payload this worker reports: uri + version +
        device + live memory-pool state (ref: node/Announcer.java with the
        MemoryInfo rider). With $TRINO_TPU_CLUSTER_OBS on, the announcement
        additionally piggybacks a BOUNDED metric snapshot (federated
        metrics) and a clock rider — this node's monotonic timestamp plus
        the last observed announce round-trip — from which the coordinator
        estimates the clock offset (RTT midpoint) that skew-aligns this
        node's trace segments. Flag off: byte-identical to the pre-plane
        payload."""
        from .. import __version__
        from ..connectors.system import device_kind
        from ..runtime import clusterobs

        body = {
            "uri": f"http://{self.address}",
            "version": __version__,
            "device": device_kind(),
            "memory": self.tasks.memory_info(),
        }
        if clusterobs.server_enabled():
            from ..runtime import hostprof, kernelcost

            # host-path plane rider: refresh the runnable/blocked thread
            # gauges at announce time so the federated cluster tables carry
            # live values, not the last sampler tick (no-op when off — the
            # series never registers and the payload is byte-identical)
            if hostprof.server_enabled():
                hostprof.update_thread_gauges()
            series, _dropped = clusterobs.announcement_metrics()
            body["metrics"] = series
            # kernel cost plane rider: bounded latest-attributions snapshot
            # so system.runtime.kernel_costs on the coordinator shows every
            # node's rows (omitted while the ledger is empty)
            kc_rows = kernelcost.announcement_rows()
            if kc_rows:
                body["kernel_costs"] = kc_rows
            body["clock"] = {
                "mono_us": time.monotonic_ns() // 1000,
                # null until measured: the receiver ranks an unmeasured
                # sample below any real RTT instead of trusting a fake 0
                "rtt_us": (
                    None if self._last_announce_rtt_us is None
                    else int(self._last_announce_rtt_us)
                ),
            }
        return body

    def announce_to(self, coordinator_url: str, timeout: float = 5.0) -> bool:
        """PUT one announcement to ``coordinator_url`` and record the
        observed round-trip — the next announcement's clock rider carries
        it (the coordinator's RTT-midpoint offset estimate needs the
        sender-side RTT). Returns True on a 2xx response."""
        body = json.dumps(self.announcement_body()).encode()
        url = (
            f"{coordinator_url.rstrip('/')}/v1/announcement/"
            f"{self.tasks.node_id or self.address}"
        )
        req = urllib.request.Request(url, data=body, method="PUT")
        req.add_header("Content-Type", "application/json")
        t0 = time.monotonic()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                ok = 200 <= resp.status < 300
        except OSError:
            return False
        self._last_announce_rtt_us = (time.monotonic() - t0) * 1e6
        return ok

    def start(self) -> "WorkerServer":
        # named: the hostprof sampler and the deterministic-tid Perfetto
        # contract both group on thread names
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name=f"worker-http-{self.port}",
        )
        self._thread.start()
        # host-path plane: $TRINO_TPU_HOSTPROF runs the sampling profiler +
        # GIL-contention probe for the process lifetime (no-op when off)
        from ..runtime.hostprof import start_server_profiling

        start_server_profiling()
        # the local-exchange shortcut recognizes pulls addressed to self
        self.tasks.self_urls = {
            f"http://{self.address}", f"http://localhost:{self._server.server_port}"
        }
        self.tasks.node_id = self.address
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self.tasks.executor.stop()


def _status_json(task: Task) -> bytes:
    return json.dumps(
        {
            "taskId": task.task_id,
            "state": task.state.value,
            "error": task.error,
            "version": task.version,
            # per-driver scheduling stats (PrioritizedSplitRunner analogue)
            "queuedSecs": task.queued_secs,
            "runSecs": task.run_secs,
        }
    ).encode()
