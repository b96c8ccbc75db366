"""Serving fabric plane: coordinator HA, dispatch handoff, shared warm
tiers, and worker elasticity.

Reference blueprint: Trino's fault-tolerant execution lets TASKS outlive
their workers (EventDrivenFaultTolerantQueryScheduler over the durable
exchange); this module extends the same disaggregation one level up so a
QUERY outlives its COORDINATOR — "Near Data Processing in Taurus Database"
(PAPERS.md) motivates the move: push the shared state down to the storage
substrate and any compute node can pick the work back up. Every durable
plane needed already exists (query-history JSONL, statstore, capstore,
result cache, the FTE durable exchange); what this module adds is the
coordination layer over them:

- :class:`LeaderLease` — a leader election primitive on the ``fs.py``
  object-store substrate: an atomic-rename lease file carrying a FENCED
  EPOCH, TTL renewal, and standby takeover through an O_EXCL epoch-claim
  object (``write_if_absent``), so two standbys racing an expired lease
  can never both win the same epoch. A paused old leader discovers the
  advanced epoch on its next renew/fence check and steps down — at no
  observable point do two holders believe the same epoch.
- :class:`DispatchJournal` — the per-query dispatch handoff record,
  persisted NEXT TO the durable exchange (``<exchange>/<query_id>/
  journal.jsonl``): begin (sql + the planning-relevant session props),
  stage_start / winner (keyed like the FTE scheduler's attempt ring) /
  stage_done / finished. On failover :func:`resume_fte_query` replays it:
  completed stages are skipped outright, committed exchange attempts of
  the in-flight stage are RE-ADOPTED, and scheduling resumes from the
  last completed stage instead of failing the query. Readers skip a
  truncated trailing record (kill-mid-append) and count it instead of
  crashing (``trino_tpu_recovery_torn_records_total``).
- :class:`SharedCacheTier` — the cross-process warm tier over the fs.py
  object-store layer (the round-11 follow-up): a fleet of coordinators
  shares one warm result cache, and single-flight is extended with a
  leased flight object so two coordinators never double-materialize the
  same entry (``write_if_absent`` again; an abandoned flight expires by
  TTL so a crashed materializer never wedges the key).
- :class:`ScaleController` — worker elasticity driven by the signals
  ``system.metrics`` already exports (resource-group queue depth,
  memory-pool pressure, blacklist churn): scale-up admits a late-joining
  worker into RUNNING FTE queries (``EventDrivenFteScheduler.
  admit_worker``), scale-down drains gracefully (no new dispatch, live
  attempts finish) before retiring the node.

Everything is gated off by default (``ha_plane`` / ``shared_cache_tier``
/ ``elastic_workers`` session properties): with the gates off the
execution path is byte-identical to the pre-HA engine.

Chaos sites: ``coordinator_crash`` (the stage loop raises
:class:`CoordinatorCrashError` mid-query, leaving journal + committed
exchange attempts on disk exactly as a dead process would) and
``lease_expire`` (the leader's renewal forfeits, modelling a GC pause /
partition long enough for the lease to lapse).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from .. import knobs
from ..fs import Location
from .failure import chaos_fire
from .objectstore import (
    ObjectJournal,
    backend_for_root,
    is_object_uri,
    object_journal_queries,
)
from .observability import RECORDER

# one shared HELP string per counter: the metric HELP lint requires every
# call site of a name to agree
TORN_RECORDS_HELP = (
    "truncated trailing JSONL records skipped during restart recovery"
)
FAILOVERS_HELP = "coordinator failovers (standby lease takeovers)"
RENEWALS_HELP = "leader lease renewals"
SHARED_HITS_HELP = "shared warm-tier cache hits served across processes"
SHARED_MISSES_HELP = "shared warm-tier cache lookups that found no entry"
SHARED_PUBLISH_HELP = "entries published into the shared warm tier"
ADMIT_HELP = "workers admitted by the elastic scale controller"
DRAIN_HELP = "workers drained by the elastic scale controller"

# how long a shared-tier single-flight loser waits for the winner's publish
# before falling back to executing itself (mirrors the fragment cache's
# hung-winner fallback)
SHARED_FLIGHT_WAIT_SECS = 10.0
# flight-lease TTL: a crashed materializer's abandoned flight frees itself
SHARED_FLIGHT_TTL_SECS = 30.0


def _counter(name: str, help_: str):
    from .metrics import REGISTRY

    return REGISTRY.counter(name, help=help_)


def note_torn_record(n: int = 1) -> None:
    """Count a torn trailing JSONL record skipped during recovery — the
    QueryHistoryStore, statstore, and dispatch-journal readers all report
    through this one hook instead of crashing on a kill-mid-append tail."""
    if n > 0:
        _counter("trino_tpu_recovery_torn_records_total", TORN_RECORDS_HELP).inc(n)


# --------------------------------------------------------------------------- #
# leader lease
# --------------------------------------------------------------------------- #


class LeadershipLost(RuntimeError):
    """The caller believed it was the leader but the lease says otherwise."""


class FencedWriteError(RuntimeError):
    """A write carrying a superseded epoch was rejected — the fencing rule:
    once a standby takes over at epoch N+1, every epoch-N writer is dead to
    the substrate even if its process is still running."""

    def __init__(self, held: int, current: int):
        super().__init__(
            f"fenced write rejected: holder epoch {held} superseded by "
            f"epoch {current}"
        )
        self.held = held
        self.current = current


class LeaderLease:
    """Fenced leader lease on the fs.py substrate.

    State is one lease object (``lease.json``: holder / epoch /
    expires_at) plus conditional-put epoch-claim objects
    (``claims/epoch-N``). Takeover protocol: read the lease; if expired,
    CAS-create the claim for ``epoch+1`` — ``write_if_absent``
    (If-None-Match on the object backend, tmp+link locally) guarantees
    exactly one winner per epoch — then publish the new lease via an
    etag-fenced ``write_if_match`` CAS, so a paused OLD leader's late
    renewal can never clobber a newer epoch's lease even on a rename-free
    substrate. Renewal re-reads and FAILS if the stored epoch moved on
    (the paused-leader case). ``check_fenced`` is the write-side fencing
    hook journal appends go through.

    The root may be an ``object://`` URI: the lease then runs on the
    retrying object backend with identical exactly-one-winner semantics.
    """

    LEASE = Location("local", "lease.json")

    def __init__(self, root: str, node_id: str, ttl: float = 10.0):
        self.fs, self.root = backend_for_root(root)
        self.node_id = node_id
        self.ttl = float(ttl)
        self.epoch = 0  # the epoch THIS holder owns; 0 = not leader
        self._lease_etag: Optional[str] = None  # etag of the last lease read

    # ------------------------------------------------------------------ state

    def _read(self) -> Optional[dict]:
        try:
            raw, etag = self.fs.read_with_etag(self.LEASE)
            data = json.loads(raw.decode())
        except (OSError, ValueError):
            self._lease_etag = None
            return None
        self._lease_etag = etag
        return data if isinstance(data, dict) else None

    def _publish(self, now: float) -> bool:
        """Etag-fenced lease publication. Returns False when the lease
        advanced past our epoch mid-publish (we are superseded); retries
        through lower-epoch interference (an old leader's concurrent late
        renewal) because our epoch is the newer claim."""
        body = json.dumps({
            "holder": self.node_id,
            "epoch": self.epoch,
            "expires_at": now + self.ttl,
        }).encode()
        for _ in range(16):
            if self._lease_etag is None:
                if self.fs.write_if_absent(self.LEASE, body):
                    self._lease_etag = hashlib.md5(body).hexdigest()
                    return True
            else:
                new = self.fs.write_if_match(self.LEASE, body, self._lease_etag)
                if new is not None:
                    self._lease_etag = new
                    return True
            cur = self._read()  # refreshes the etag for the next round
            if cur is not None and int(cur.get("epoch", 0)) > self.epoch:
                return False  # superseded while publishing: step down
        return False

    def current_epoch(self) -> int:
        cur = self._read()
        return int(cur.get("epoch", 0)) if cur else 0

    def holder(self) -> Optional[str]:
        cur = self._read()
        if cur is None or time.time() >= float(cur.get("expires_at", 0)):
            return None
        return cur.get("holder")

    # -------------------------------------------------------------- lifecycle

    def acquire(self) -> bool:
        """Become leader if the lease is free/expired (or already ours).
        Returns True when this node holds the lease afterwards."""
        with RECORDER.span("leader_lease", "ha", node=self.node_id) as end:
            now = time.time()
            cur = self._read()
            if (
                cur is not None
                and cur.get("holder") == self.node_id
                and int(cur.get("epoch", 0)) == self.epoch
                and self.epoch > 0
            ):
                end["outcome"] = "renewed"
                return self.renew()
            if cur is not None and now < float(cur.get("expires_at", 0)):
                end["outcome"] = "held"
                end["holder"] = cur.get("holder")
                return False
            next_epoch = (int(cur.get("epoch", 0)) if cur else 0) + 1
            claim = Location("local", f"claims/epoch-{next_epoch}")
            if not self.fs.write_if_absent(
                claim,
                json.dumps({"holder": self.node_id, "ts": now}).encode(),
            ):
                # another standby won this epoch's CAS first
                end["outcome"] = "lost_claim"
                return False
            self.epoch = next_epoch
            if not self._publish(now):
                # a newer epoch published mid-claim (shouldn't happen: the
                # claim CAS serializes epochs) — don't pretend to lead
                self.epoch = 0
                end["outcome"] = "lost_publish"
                return False
            end["outcome"] = "acquired"
            end["epoch"] = next_epoch
            if next_epoch > 1:
                _counter("trino_tpu_failovers_total", FAILOVERS_HELP).inc()
            return True

    def renew(self) -> bool:
        """Extend the lease; False (and step down) when leadership is gone.
        The ``lease_expire`` chaos site models a GC pause: the renewal is
        skipped and the holder forfeits locally, so the on-disk lease
        lapses and a standby takes over — is_leader() goes False HERE
        first, which is what makes "never two leaders" hold."""
        if self.epoch <= 0:
            return False
        act = chaos_fire("lease_expire", text=self.node_id)
        if act is not None:
            self.epoch = 0
            return False
        cur = self._read()
        if (
            cur is None
            or cur.get("holder") != self.node_id
            or int(cur.get("epoch", 0)) != self.epoch
        ):
            self.epoch = 0  # superseded while we slept
            return False
        if not self._publish(time.time()):
            self.epoch = 0  # CAS lost to a newer epoch: step down
            return False
        _counter("trino_tpu_lease_renewals_total", RENEWALS_HELP).inc()
        return True

    def release(self) -> None:
        """Voluntary step-down: expire the lease immediately (same epoch) so
        a standby can claim the next one without waiting out the TTL."""
        if self.epoch <= 0:
            return
        cur = self._read()
        if cur is not None and cur.get("holder") == self.node_id \
                and int(cur.get("epoch", 0)) == self.epoch \
                and self._lease_etag is not None:
            cur["expires_at"] = 0.0
            # best-effort CAS: losing means someone already superseded us,
            # which achieves the same end (we no longer hold the lease)
            self.fs.write_if_match(
                self.LEASE, json.dumps(cur).encode(), self._lease_etag
            )
        self.epoch = 0

    def is_leader(self) -> bool:
        if self.epoch <= 0:
            return False
        cur = self._read()
        return bool(
            cur is not None
            and cur.get("holder") == self.node_id
            and int(cur.get("epoch", 0)) == self.epoch
            and time.time() < float(cur.get("expires_at", 0))
        )

    def check_fenced(self, epoch: int) -> None:
        """Write-side fencing: raise when ``epoch`` has been superseded.
        (The check-then-write window is inherent to a filesystem substrate;
        it is safe here because journal records are ADVISORY over the
        idempotent first-commit-wins exchange — a late stale record can
        never change which attempt a resumed consumer reads.)"""
        current = self.current_epoch()
        if current > epoch:
            raise FencedWriteError(epoch, current)

    def snapshot(self) -> dict:
        cur = self._read() or {}
        return {
            "node": self.node_id,
            "leader": self.is_leader(),
            "epoch": self.epoch,
            "currentEpoch": int(cur.get("epoch", 0) or 0),
            "holder": cur.get("holder"),
            "expiresAt": float(cur.get("expires_at", 0) or 0),
        }


# --------------------------------------------------------------------------- #
# dispatch journal + resume
# --------------------------------------------------------------------------- #


class CoordinatorCrashError(RuntimeError):
    """The ``coordinator_crash`` chaos site fired: the query aborts exactly
    the way a dead coordinator process would leave it — dispatch journal
    and committed exchange attempts intact on the shared substrate, no
    cleanup — so a standby can adopt and resume it."""

    def __init__(self, query_id: str, journal_path: Optional[str] = None):
        super().__init__(f"injected coordinator crash during {query_id}")
        self.query_id = query_id
        self.journal_path = journal_path


# session properties whose values shape the distributed plan: the resuming
# coordinator must re-plan with the SAME values or fragment/partition
# topology would not line up with the committed exchange attempts
PLAN_SESSION_PROPS = (
    "retry_policy", "join_distribution_type", "join_reordering_strategy",
    "hash_partition_count", "target_partition_rows",
    "push_partial_aggregation", "broadcast_join_threshold_rows",
    "distributed_sort", "enable_dynamic_filtering", "task_retry_attempts",
    "fte_exchange_dir", "ha_plane",
)


def repair_jsonl_tail(path: str) -> bool:
    """Terminate a torn trailing line (kill-mid-append) with a newline so
    the NEXT append starts a fresh record instead of concatenating onto the
    unterminated fragment — without this, one torn tail silently corrupts
    the first post-recovery record too. Returns True when a repair was
    needed."""
    try:
        with open(path, "rb+") as f:
            f.seek(0, os.SEEK_END)
            if f.tell() == 0:
                return False
            f.seek(-1, os.SEEK_END)
            if f.read(1) == b"\n":
                return False
            f.write(b"\n")
            return True
    except OSError:
        return False


def read_jsonl_tolerant(path: str) -> Tuple[List[dict], int]:
    """All decodable JSON records in ``path`` plus how many torn/corrupt
    lines were skipped (counted via :func:`note_torn_record`). A file
    truncated mid-append (coordinator killed between write and newline)
    yields every complete record instead of crashing the reader."""
    records: List[dict] = []
    torn = 0
    try:
        with open(path, "r") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    torn += 1
                    continue
                if isinstance(rec, dict):
                    records.append(rec)
                else:
                    torn += 1
    except OSError:
        return [], 0
    note_torn_record(torn)
    return records, torn


class DispatchJournal:
    """Per-query dispatch handoff journal, JSONL next to the durable
    exchange. Appends are epoch-fenced when a lease is attached: a paused
    old leader's late write raises :class:`FencedWriteError` instead of
    landing. Record kinds::

        {"kind": "begin", "query_id", "sql", "session", "n_workers"}
        {"kind": "stage_start", "fid", "n_parts"}
        {"kind": "winner", "fid", "p", "attempt"}   # the attempt ring key
        {"kind": "stage_done", "fid"}
        {"kind": "finished"}
    """

    FILENAME = "journal.jsonl"

    def __init__(self, path: str, lease: Optional[LeaderLease] = None,
                 epoch: Optional[int] = None):
        self.path = path
        self.lease = lease
        self.epoch = int(
            epoch if epoch is not None
            else (lease.epoch if lease is not None else 0)
        )
        # dedicated I/O serializer (lint blocking-call-under-lock: appends
        # are its only job, no shared state hides behind it)
        self._io_lock = threading.Lock()
        self._tail_checked = False
        # object substrate: appends become sequenced record objects with a
        # CAS'd tail pointer (no JSONL append primitive on a rename-free
        # store); the record schema and fencing are identical
        self._obj = ObjectJournal(path) if is_object_uri(path) else None

    @staticmethod
    def path_for(exchange_base: str, query_id: str) -> str:
        if is_object_uri(exchange_base):
            # no .jsonl on the object substrate: the journal is a PREFIX
            # of sequenced record objects (<prefix>/00000001.json + TAIL)
            return f"{str(exchange_base).rstrip('/')}/{query_id}/journal"
        return os.path.join(exchange_base, query_id, DispatchJournal.FILENAME)

    # ---------------------------------------------------------------- writes

    def append(self, record: dict) -> None:
        if self.lease is not None:
            self.lease.check_fenced(self.epoch)
        record = dict(record)
        record["epoch"] = self.epoch
        record["ts"] = time.time()
        if self._obj is not None:
            with self._io_lock:
                self._obj.append(record)
            return
        line = json.dumps(record)
        with self._io_lock:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            if not self._tail_checked:
                # a takeover leader appends to the DEAD leader's journal:
                # terminate any torn trailing line first
                self._tail_checked = True
                repair_jsonl_tail(self.path)
            with open(self.path, "a") as f:
                f.write(line + "\n")

    def begin(self, query_id: str, sql: str, session, n_workers: int,
              exchange_dir: str = "") -> None:
        props = {}
        for name in PLAN_SESSION_PROPS:
            try:
                props[name] = session.get(name)
            except KeyError:
                continue
        if exchange_dir:
            # the RESOLVED substrate location, not the session default — a
            # temp-managed exchange dir must still be findable on takeover
            props["fte_exchange_dir"] = exchange_dir
        self.append({
            "kind": "begin", "query_id": query_id, "sql": sql,
            "session": props, "n_workers": int(n_workers),
        })

    def stage_start(self, fid: int, n_parts: int) -> None:
        self.append({"kind": "stage_start", "fid": fid, "n_parts": n_parts})

    def winner(self, fid: int, p: int, attempt: int) -> None:
        self.append({"kind": "winner", "fid": fid, "p": p, "attempt": attempt})

    def stage_done(self, fid: int) -> None:
        self.append({"kind": "stage_done", "fid": fid})

    def finished(self) -> None:
        self.append({"kind": "finished"})

    # ----------------------------------------------------------------- reads

    @staticmethod
    def read(path: str) -> Tuple[List[dict], int]:
        if is_object_uri(path):
            records, torn = ObjectJournal(path).read()
            note_torn_record(torn)
            return records, torn
        return read_jsonl_tolerant(path)


class ResumeState:
    """Parsed dispatch journal: what a takeover leader adopts."""

    def __init__(self):
        self.query_id: str = ""
        self.sql: str = ""
        self.session_props: Dict[str, Any] = {}
        self.n_workers: int = 0
        self.stages_done: Set[int] = set()
        self.winners: Dict[Tuple[int, int], int] = {}
        self.finished: bool = False

    @staticmethod
    def from_records(records: List[dict]) -> "ResumeState":
        st = ResumeState()
        for rec in records:
            kind = rec.get("kind")
            if kind == "begin":
                st.query_id = str(rec.get("query_id", ""))
                st.sql = str(rec.get("sql", ""))
                props = rec.get("session")
                if isinstance(props, dict):
                    st.session_props = props
                st.n_workers = int(rec.get("n_workers", 0) or 0)
            elif kind == "stage_done":
                st.stages_done.add(int(rec["fid"]))
            elif kind == "winner":
                st.winners[(int(rec["fid"]), int(rec["p"]))] = int(
                    rec["attempt"]
                )
            elif kind == "finished":
                st.finished = True
        return st

    @staticmethod
    def load(path: str) -> "ResumeState":
        records, _ = DispatchJournal.read(path)
        return ResumeState.from_records(records)


def orphaned_journals(exchange_base: str) -> List[str]:
    """Journal paths of queries that began but never journaled
    ``finished`` — the takeover leader's adoption worklist."""
    out: List[str] = []
    if is_object_uri(exchange_base):
        for _qid, journal_uri in object_journal_queries(exchange_base):
            st = ResumeState.load(journal_uri)
            if st.sql and not st.finished:
                out.append(journal_uri)
        return out
    try:
        names = sorted(os.listdir(exchange_base))
    except OSError:
        return out
    for name in names:
        path = os.path.join(exchange_base, name, DispatchJournal.FILENAME)
        if not os.path.isfile(path):
            continue
        st = ResumeState.load(path)
        if st.sql and not st.finished:
            out.append(path)
    return out


def resume_fte_query(runner, journal_path: str):
    """Failover dispatch handoff: replay ``journal_path`` on ``runner``
    (the NEW leader's runner, mounted over the same catalogs and exchange
    substrate), re-adopt committed exchange attempts, and resume from the
    last completed stage. Returns the finished QueryResult — bit-identical
    to the uninterrupted run because every adopted stage's committed
    attempts are exactly what an uninterrupted consumer would have read."""
    from .clusterobs import session_enabled as _obs_enabled

    # profile breakdown contract: everything from handoff entry to the
    # stage loop counts as the resumed query's planning phase
    if _obs_enabled(runner.session):
        runner._obs_planning_t0 = time.monotonic()
    state = ResumeState.load(journal_path)
    if not state.sql:
        raise ValueError(f"journal {journal_path!r} has no begin record")
    if state.finished:
        raise ValueError(f"query {state.query_id} already finished")
    with RECORDER.span(
        "dispatch_replay", "ha",
        query_id=state.query_id, stages_done=len(state.stages_done),
        winners=len(state.winners),
    ) as end:
        for name, value in state.session_props.items():
            try:
                runner.session.set(name, value)
            except (KeyError, ValueError):
                continue
        if state.n_workers:
            runner.n_workers = state.n_workers
        # per-query observability normally reset by _execute_once — the
        # handoff enters the FTE tier directly
        runner.last_partition_counts = {}
        runner.last_tier, runner.last_tier_reason = "fte", None
        subplan = runner.plan_distributed(state.sql)
        result = runner._execute_fte(subplan, sql=state.sql, resume=state)
        end["outcome"] = "resumed"
        end["adopted"] = getattr(runner, "last_fte_adopted", 0)
    return result


# --------------------------------------------------------------------------- #
# shared warm tier (cross-process result cache over the object store)
# --------------------------------------------------------------------------- #


class SharedCacheTier:
    """Cross-process warm tier on the fs.py object-store layer: one value
    object per cache key plus a leased single-flight object so a FLEET of
    coordinators materializes each entry exactly once.

    Layout under the tier root::

        result/<key>.json     the published entry (atomic put)
        flight/<key>.json     the materialization lease (O_EXCL create,
                              expires after SHARED_FLIGHT_TTL_SECS)
    """

    def __init__(self, root: str):
        # an object:// root mounts the retrying object backend; the value
        # objects (atomic whole-object puts) and flight leases
        # (write_if_absent) already speak pure contract, so the tier runs
        # unchanged on either substrate
        self.fs, self.root = backend_for_root(root)
        self._held: Set[str] = set()
        self._lock = threading.Lock()

    @staticmethod
    def _value_loc(key: str) -> Location:
        return Location("local", f"result/{key}.json")

    @staticmethod
    def _flight_loc(key: str) -> Location:
        return Location("local", f"flight/{key}.json")

    # ----------------------------------------------------------------- value

    def get(self, key: str) -> Optional[dict]:
        try:
            raw = json.loads(self.fs.read(self._value_loc(key)).decode())
        except (OSError, ValueError):
            _counter(
                "trino_tpu_shared_cache_misses_total", SHARED_MISSES_HELP
            ).inc()
            return None
        _counter("trino_tpu_shared_cache_hits_total", SHARED_HITS_HELP).inc()
        return raw if isinstance(raw, dict) else None

    def publish(self, key: str, payload: dict) -> None:
        """Atomic put of the materialized entry; releases a held flight."""
        try:
            self.fs.write(self._value_loc(key), json.dumps(payload).encode())
            _counter(
                "trino_tpu_shared_cache_publishes_total", SHARED_PUBLISH_HELP
            ).inc()
        finally:
            self.end_flight(key)

    def invalidate(self, key: str) -> None:
        """Remove a published entry. Atomic unlink: a concurrent reader
        sees either the old FULL object or a miss — never a torn value
        (the cross-process race test in tests/test_ha_plane.py hammers
        this against concurrent lookup/publish)."""
        self.fs.delete(self._value_loc(key))

    # ---------------------------------------------------------------- flight

    def try_flight(self, key: str) -> bool:
        """Claim the materialization flight for ``key``. True = this caller
        is the winner and must publish (or let the lease expire). An
        expired flight (crashed materializer) is reclaimed."""
        loc = self._flight_loc(key)
        body = json.dumps(
            {"pid": os.getpid(), "expires_at": time.time()
             + SHARED_FLIGHT_TTL_SECS}
        ).encode()
        if self.fs.write_if_absent(loc, body):
            with self._lock:
                self._held.add(key)
            return True
        try:
            cur = json.loads(self.fs.read(loc).decode())
            expired = time.time() >= float(cur.get("expires_at", 0))
        except (OSError, ValueError):
            expired = True  # vanished/corrupt between exists and read
        if not expired:
            return False
        # stale flight: reclaim (delete + CAS again; two reclaimers race the
        # CAS, exactly one wins)
        self.fs.delete(loc)
        if self.fs.write_if_absent(loc, body):
            with self._lock:
                self._held.add(key)
            return True
        return False

    def end_flight(self, key: str) -> None:
        with self._lock:
            held = key in self._held
            self._held.discard(key)
        if held:
            self.fs.delete(self._flight_loc(key))

    def flight_active(self, key: str) -> bool:
        try:
            cur = json.loads(self.fs.read(self._flight_loc(key)).decode())
        except (OSError, ValueError):
            return False
        return time.time() < float(cur.get("expires_at", 0))

    def wait_for(self, key: str, timeout: float) -> Optional[dict]:
        """Single-flight loser path: poll for the winner's publish; give up
        at ``timeout`` or as soon as the flight lease vanished without a
        value (winner died — the caller self-executes)."""
        deadline = time.monotonic() + max(0.0, timeout)
        while True:
            try:
                raw = json.loads(
                    self.fs.read(self._value_loc(key)).decode()
                )
                if isinstance(raw, dict):
                    _counter(
                        "trino_tpu_shared_cache_hits_total", SHARED_HITS_HELP
                    ).inc()
                    return raw
            except (OSError, ValueError):
                pass
            if time.monotonic() >= deadline or not self.flight_active(key):
                return None
            time.sleep(0.01)


_SHARED_TIERS: Dict[str, SharedCacheTier] = {}
_SHARED_TIERS_LOCK = threading.Lock()


def shared_tier(session) -> Optional[SharedCacheTier]:
    """The process's shared warm tier, or None when the gate is off. Opt-in
    is BOTH the ``shared_cache_tier`` session property and a configured
    ``$TRINO_TPU_SHARED_CACHE_DIR`` (matching the result tier's deployment
    opt-in contract) — with either missing the lookup path is untouched."""
    try:
        if not bool(session.get("shared_cache_tier")):
            return None
    except KeyError:
        return None
    root = knobs.env_path("TRINO_TPU_SHARED_CACHE_DIR")
    if not root:
        return None
    with _SHARED_TIERS_LOCK:
        tier = _SHARED_TIERS.get(root)
        if tier is None:
            tier = SharedCacheTier(root)
            _SHARED_TIERS[root] = tier
        return tier


# --------------------------------------------------------------------------- #
# elastic workers
# --------------------------------------------------------------------------- #


class ScaleController:
    """Worker elasticity driven by the signals the metrics plane already
    exports: resource-group queue depth, memory-pool pressure, and
    blacklist churn. ``spawn()`` must return the new worker's url;
    ``retire(url)`` stops it after a graceful drain. Scale-up admits the
    worker into every RUNNING FTE query's scheduler (late join); scale-down
    drains first — no new dispatch, in-flight attempts finish — before
    retiring."""

    def __init__(
        self,
        node_manager=None,
        resource_groups=None,
        memory_pool=None,
        spawn: Optional[Callable[[], str]] = None,
        retire: Optional[Callable[[str], None]] = None,
        min_workers: int = 1,
        max_workers: int = 8,
        queue_high: int = 4,
        pressure_high: float = 0.85,
    ):
        self.node_manager = node_manager
        self.resource_groups = resource_groups
        self.memory_pool = memory_pool
        self.spawn = spawn
        self.retire = retire
        self.min_workers = int(min_workers)
        self.max_workers = int(max_workers)
        self.queue_high = int(queue_high)
        self.pressure_high = float(pressure_high)
        self.workers: List[str] = []  # urls this controller manages
        self._last_blacklisted: Optional[float] = None
        self.decisions: List[dict] = []

    # --------------------------------------------------------------- signals

    def signals(self) -> dict:
        queue_depth = 0
        if self.resource_groups is not None:
            try:
                queue_depth = sum(
                    int(row.get("queued", 0))
                    for row in self.resource_groups.flat_info()
                )
            except Exception:  # noqa: BLE001 — signals are advisory
                queue_depth = 0
        pressure = 0.0
        if self.memory_pool is not None:
            try:
                snap = self.memory_pool.snapshot()
                if snap.get("maxBytes"):
                    pressure = (
                        float(snap.get("reservedBytes", 0))
                        / float(snap["maxBytes"])
                    )
            except Exception:  # noqa: BLE001 — signals are advisory
                pressure = 0.0
        from .metrics import REGISTRY

        blacklisted = REGISTRY.counter(
            "trino_tpu_workers_blacklisted_total",
            help="workers blacklisted by the FTE scheduler",
        ).value
        churn = 0.0
        if self._last_blacklisted is not None:
            churn = max(0.0, blacklisted - self._last_blacklisted)
        self._last_blacklisted = blacklisted
        return {
            "queue_depth": queue_depth,
            "memory_pressure": pressure,
            "blacklist_churn": churn,
            "workers": len(self.workers),
        }

    # --------------------------------------------------------------- actions

    def scale_up(self) -> Optional[str]:
        if self.spawn is None or len(self.workers) >= self.max_workers:
            return None
        url = (self.spawn() or "").rstrip("/")
        if not url:
            return None
        self.workers.append(url)
        self.admit_into_running(url)
        _counter("trino_tpu_worker_admissions_total", ADMIT_HELP).inc()
        return url

    @staticmethod
    def admit_into_running(url: str) -> int:
        """Late-join: hand the new worker to every live FTE scheduler that
        dispatches remotely (a local in-process scheduler must never grow a
        remote worker mid-query). Returns how many queries admitted it."""
        from .fte_scheduler import active_schedulers

        n = 0
        for sched in active_schedulers():
            if sched.workers and sched.admit_worker(url):
                n += 1
        return n

    def drain(self, url: str, node_id: Optional[str] = None,
              wait_secs: float = 10.0) -> bool:
        """Graceful scale-down: mark the node DRAINING (no new dispatch),
        tell every live scheduler to steer away, wait for in-flight
        attempts to finish, then retire. Returns True when the worker
        drained clean inside ``wait_secs`` (it is retired either way —
        remaining attempts fail over through the normal FTE retry path)."""
        url = url.rstrip("/")
        from .fte_scheduler import active_schedulers

        with RECORDER.span("worker_drain", "ha", worker=url) as end:
            if self.node_manager is not None and node_id is not None:
                try:
                    self.node_manager.drain(node_id)
                except Exception:  # noqa: BLE001 — registry drain is advisory
                    pass
            for sched in active_schedulers():
                sched.drain_worker(url)
            deadline = time.monotonic() + max(0.0, wait_secs)
            clean = False
            while True:
                busy = sum(
                    sched.worker_inflight(url)
                    for sched in active_schedulers()
                )
                if busy == 0:
                    clean = True
                    break
                if time.monotonic() >= deadline:
                    break
                time.sleep(0.02)
            end["outcome"] = "drained" if clean else "timeout"
        if url in self.workers:
            self.workers.remove(url)
        if self.retire is not None:
            self.retire(url)
        _counter("trino_tpu_worker_drains_total", DRAIN_HELP).inc()
        return clean

    def tick(self) -> dict:
        """One control-loop step: read the signals, maybe act."""
        sig = self.signals()
        decision = {"action": "hold", **sig}
        overloaded = (
            sig["queue_depth"] >= self.queue_high
            or sig["memory_pressure"] >= self.pressure_high
            or sig["blacklist_churn"] > 0
        )
        if overloaded and len(self.workers) < self.max_workers:
            url = self.scale_up()
            if url:
                decision["action"] = "scale_up"
                decision["worker"] = url
        elif (
            sig["queue_depth"] == 0
            and sig["memory_pressure"] < 0.5 * self.pressure_high
            and len(self.workers) > self.min_workers
        ):
            url = self.workers[-1]
            decision["action"] = "scale_down"
            decision["worker"] = url
            decision["clean"] = self.drain(url)
        self.decisions.append(decision)
        return decision
