"""Active-active coordinator fleet: partitioned admission, follower
reads, and a multi-process protocol front.

The ceiling this plane is meant to remove: with many clients the chip is
idle while ONE Python process's protocol loop serializes every client
(not measured on the chip; no cell runs the fleet).
"Accelerating Presto with GPUs" (PAPERS.md) names the pattern: once the
device path is fast, the host/protocol path must scale OUT. The round-16
serving fabric (runtime/ha.py) already made a query outlive its
coordinator; this module makes the standby fleet *serve*:

- :class:`FleetMember` — membership on the ``fs.py`` object-store
  substrate (``members/<node_id>.json`` heartbeat objects, atomic puts,
  TTL liveness). Heartbeats carry the same bounded metric snapshot worker
  announcements do (``clusterobs.announcement_metrics``), and every member
  folds its peers' snapshots into its :class:`~.clusterobs.ClusterMetrics`
  — so ``system.metrics.cluster_counters`` shows per-coordinator
  ``trino_tpu_protocol_queue_depth`` / admission counters (node column)
  from ANY member, and fleet hot-spotting is visible without a scrape tier.
- :class:`HashRing` — consistent-hash ownership over the LIVE member set:
  each member projects ``RING_POINTS`` virtual points; a statement's
  partition key is owned by the first point clockwise. A dead member's
  arcs fall to its clockwise successors — the failover reassignment
  contract is that every key NOT owned by the dead node keeps its owner
  (no fleet-wide reshuffle), and in-flight queries of the dead owner are
  recovered by the journal replay path that already exists
  (``ha.resume_fte_query`` over ``orphaned_journals``).
- Partitioned admission: a non-owner coordinator receiving POST
  /v1/statement either 307-redirects the client to the owner's unique
  address or proxies the statement there (``$TRINO_TPU_FLEET_ROUTE``),
  under ``proto_route`` / ``proto_proxy`` phase spans so routing cost is
  attributed, not hidden.
- Follower reads: ``system.*``-only statements, warm result-cache hits
  (the round-16 ``peek_cached_result`` PURE probe against the shared
  tier), and ``GET /v1/query/{id}`` status polls (served from the
  ``status/<query_id>.json`` board the owner publishes on lifecycle
  transitions) are answered by ANY member without touching the owner.
- Multi-process protocol front: N forked coordinator processes share one
  client-facing listen port via ``SO_REUSEPORT`` (each also binds a
  unique per-node port that membership advertises for redirect/proxy
  targets), so concurrent client protocol loops stop convoying on one
  GIL. Each front process is a FULL coordinator in the lease/journal
  protocol. ``python -m trino_tpu.runtime.fleet`` serves one such process
  (deployments fork N of them).

Everything is gated off by default: with ``$TRINO_TPU_FLEET_DIR`` unset
no membership object, no heartbeat thread, and no routing branch exists —
the single-coordinator path is byte-identical (poisoning-tested).
"""

from __future__ import annotations

import bisect
import hashlib
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from .. import knobs
from ..fs import LocalFileSystem, Location
from .observability import RECORDER

# one shared HELP string per counter: the metric HELP lint requires every
# call site of a name to agree
ROUTED_HELP = "statements 307-redirected to their owning coordinator"
PROXIED_HELP = "statements proxied to their owning coordinator"
FOLLOWER_READS_HELP = (
    "read-only requests served by a non-owner fleet coordinator"
)
HEARTBEATS_HELP = "fleet membership heartbeats published"
REASSIGNS_HELP = (
    "fleet members whose hash range was reassigned after their heartbeat "
    "lapsed"
)

# virtual points per member on the ownership ring: enough that N<=8 real
# members split a realistic key population within a few percent of even;
# rings are memoized per live-member set, so the build cost is paid once
# per membership change, never per routing decision
RING_POINTS = 512


def _counter(name: str, help_: str):
    from .metrics import REGISTRY

    return REGISTRY.counter(name, help=help_)


def _hash64(text: str) -> int:
    return int.from_bytes(
        hashlib.sha256(text.encode()).digest()[:8], "big"
    )


class HashRing:
    """Consistent-hash ownership over an id set (see module docstring)."""

    def __init__(self, members, points: int = RING_POINTS):
        ring = sorted(
            (_hash64(f"{m}#{i}"), m)
            for m in set(members)
            for i in range(points)
        )
        self._points = [p for p, _ in ring]
        self._owners = [m for _, m in ring]

    def owner(self, key: str) -> Optional[str]:
        if not self._points:
            return None
        idx = bisect.bisect_right(self._points, _hash64(key))
        return self._owners[idx % len(self._owners)]


def partition_key(user: str, source: str = "", group: str = "") -> str:
    """The ownership hash key for one statement: the session identity
    (``user@source``) by default; ``$TRINO_TPU_FLEET_PARTITION_BY=group``
    overrides to the resolved resource-group path so every session of a
    group lands on one coordinator (its admission queue stays a single
    total order, exactly as in a one-coordinator deployment)."""
    mode = knobs.env_str("TRINO_TPU_FLEET_PARTITION_BY", "session")
    if mode == "group" and group:
        return f"group:{group}"
    return f"session:{user}@{source}"


class FleetMember:
    """One coordinator's view of the fleet (substrate + ring + board)."""

    def __init__(self, fleet_dir: str, node_id: str, url: str,
                 heartbeat_secs: Optional[float] = None,
                 cluster_metrics=None):
        self.fs = LocalFileSystem(fleet_dir)
        self.fleet_dir = fleet_dir
        self.node_id = node_id
        self.url = url  # the member's UNIQUE address (redirect/proxy target)
        self.heartbeat_secs = (
            heartbeat_secs
            if heartbeat_secs is not None
            else knobs.env_float("TRINO_TPU_FLEET_HEARTBEAT_SECS", 1.0)
        )
        # a member is live while its last heartbeat's deadline is ahead of
        # the reader's clock; 3 beats of grace mirrors the worker
        # heartbeat-loss ladder (one missed beat must not reshuffle the ring)
        self.ttl_secs = 3.0 * max(self.heartbeat_secs, 0.05)
        self.cluster_metrics = cluster_metrics
        # wired by the server: live queue depth for the heartbeat record
        self.queue_depth_fn: Optional[Callable[[], int]] = None
        self._known_live: set = set()
        # routing hot path: the live set is re-read from the substrate at
        # most every quarter-heartbeat (membership changes no faster), and
        # rings are memoized per member set
        self.live_cache_secs = self.heartbeat_secs / 4.0
        self._live_cache: Optional[Dict[str, dict]] = None
        self._live_cache_at = 0.0
        self._ring_cache: Dict[tuple, HashRing] = {}
        self._cache_lock = threading.Lock()
        self._stop: Optional[threading.Event] = None
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ substrate

    def _member_loc(self, node_id: str) -> Location:
        return Location("local", f"members/{node_id}.json")

    def _status_loc(self, query_id: str) -> Location:
        return Location("local", f"status/{query_id}.json")

    # ----------------------------------------------------------- membership

    def publish_heartbeat(self) -> None:
        """Atomic put of this member's liveness record, with the bounded
        metric snapshot riding along (the announcement contract: heartbeats
        must never bloat past the liveness budget, overflow is counted)."""
        from .clusterobs import announcement_metrics

        series, _dropped = announcement_metrics()
        record = {
            "node_id": self.node_id,
            "url": self.url,
            "pid": os.getpid(),
            "deadline": time.time() + self.ttl_secs,
            "queue_depth": (
                int(self.queue_depth_fn()) if self.queue_depth_fn else 0
            ),
            "metrics": series,
        }
        self.fs.write(
            self._member_loc(self.node_id),
            json.dumps(record).encode(),
        )
        _counter("trino_tpu_fleet_heartbeats_total", HEARTBEATS_HELP).inc()

    def live_members(self, now: Optional[float] = None) -> Dict[str, dict]:
        """Every member whose heartbeat deadline is ahead of ``now``.
        Unreadable/partial objects are skipped (atomic puts make them
        impossible locally; a real object store can list-before-put).
        Results are cached for a quarter-heartbeat (pass ``now`` to
        bypass — tests and the reassignment check do)."""
        use_cache = now is None
        if use_cache:
            with self._cache_lock:
                if (
                    self._live_cache is not None
                    and time.time() - self._live_cache_at
                    < self.live_cache_secs
                ):
                    return dict(self._live_cache)
        now = time.time() if now is None else now
        live: Dict[str, dict] = {}
        try:
            entries = list(self.fs.list_files(Location("local", "members")))
        except OSError:
            entries = []
        for entry in entries:
            try:
                rec = json.loads(self.fs.read(entry.location))
            except (OSError, ValueError):
                continue
            if not isinstance(rec, dict):
                continue
            if float(rec.get("deadline", 0)) > now:
                live[str(rec.get("node_id", ""))] = rec
        if use_cache:
            with self._cache_lock:
                self._live_cache = dict(live)
                self._live_cache_at = time.time()
        return live

    def ring(self, live: Optional[Dict[str, dict]] = None) -> HashRing:
        live = self.live_members() if live is None else live
        ids = set(live) | {self.node_id}  # self serves even pre-first-beat
        key = tuple(sorted(ids))
        with self._cache_lock:
            ring = self._ring_cache.get(key)
            if ring is None:
                if len(self._ring_cache) > 64:
                    self._ring_cache.clear()  # bounded across churn
                ring = HashRing(ids)
                self._ring_cache[key] = ring
        return ring

    def owner_of(self, key: str) -> dict:
        """The live member record owning ``key`` (self when the ring picks
        this node or the owner's record is unreadable). Also the
        reassignment observation point: a member that left the live set
        since the last look is counted and marked in the flight recorder —
        the smoke reads failover off this signal."""
        live = self.live_members()
        departed = self._known_live - set(live) - {self.node_id}
        self._known_live = set(live)
        for dead in sorted(departed):
            _counter(
                "trino_tpu_fleet_reassigns_total", REASSIGNS_HELP
            ).inc()
            with RECORDER.span(
                "fleet_reassign", "fleet", dead=dead,
                survivors=len(live),
            ):
                pass
        owner_id = self.ring(live).owner(key)
        if owner_id == self.node_id or owner_id not in live:
            return {"node_id": self.node_id, "url": self.url}
        return live[owner_id]

    def ingest_peer_metrics(self) -> None:
        """Fold every live peer's heartbeat metric snapshot into the local
        ClusterMetrics — the federation satellite: any member's
        ``system.metrics.cluster_counters`` shows every coordinator's
        queue depth / admission counters under its node label."""
        if self.cluster_metrics is None:
            return
        for node_id, rec in self.live_members().items():
            if node_id == self.node_id:
                continue
            series = rec.get("metrics")
            if isinstance(series, list) and series:
                self.cluster_metrics.ingest(node_id, series)

    # --------------------------------------------------------- status board

    def publish_status(self, query_id: str, payload: dict) -> None:
        """Owner-side: atomic put of one query's status for follower
        ``GET /v1/query/{id}`` polls (lifecycle-event shaped + owner id)."""
        body = dict(payload)
        body["fleet_owner"] = self.node_id
        self.fs.write(
            self._status_loc(query_id), json.dumps(body).encode()
        )

    def read_status(self, query_id: str) -> Optional[dict]:
        try:
            rec = json.loads(self.fs.read(self._status_loc(query_id)))
        except (OSError, ValueError):
            return None
        return rec if isinstance(rec, dict) else None

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "FleetMember":
        self.publish_heartbeat()  # visible before the first loop tick
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"fleet-heartbeat-{self.node_id}",
        )
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.heartbeat_secs):
            try:
                self.publish_heartbeat()
                self.ingest_peer_metrics()
            except Exception:  # noqa: BLE001 — liveness must never die
                pass

    def stop(self, deregister: bool = True) -> None:
        """Graceful stop deletes the membership object so the ring
        reassigns immediately; ``deregister=False`` models a crash — the
        record stays until its TTL lapses, exactly like a dead process."""
        if self._stop is not None:
            self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        if deregister:
            try:
                self.fs.delete(self._member_loc(self.node_id))
            except OSError:
                pass


class FleetStatusListener:
    """EventListener publishing owner-side lifecycle records onto the
    status board (created and completed overwrite the same object — last
    write wins, reads are atomic). Intermediate state changes are NOT
    published: each publish is a synchronous board write on the serving
    path, a warm hit runs PLANNING→RUNNING→FINISHED in microseconds, and
    the follower-read contract is bounded-stale anyway — the created
    record plus the terminal record (query_completed fires on EVERY
    terminal transition, cancel included) bound a query's lifetime."""

    def __init__(self, member: FleetMember):
        self._member = member

    def _publish(self, event: dict) -> None:
        qid = event.get("queryId")
        if qid:
            try:
                self._member.publish_status(qid, event)
            except OSError:
                pass

    def query_created(self, event: dict) -> None:
        self._publish(event)

    def query_completed(self, event: dict) -> None:
        self._publish(event)


def member_from_env(url: str, node_id: Optional[str] = None,
                    cluster_metrics=None) -> Optional[FleetMember]:
    """The deployment gate: a FleetMember iff ``$TRINO_TPU_FLEET_DIR`` is
    set (the plane's single opt-in). Everything else has safe defaults."""
    fleet_dir = knobs.env_path("TRINO_TPU_FLEET_DIR")
    if not fleet_dir:
        return None
    node_id = node_id or f"coordinator-{os.getpid()}-{url.rsplit(':', 1)[-1]}"
    return FleetMember(
        fleet_dir, node_id, url, cluster_metrics=cluster_metrics
    )


def is_system_read(sql: str) -> bool:
    """Conservative follower-read classifier: a SELECT whose every
    FROM/JOIN target is in the ``system`` catalog (three-part names only —
    anything the cheap scan cannot prove system-only routes to the owner).
    No parse: this runs inside proto_route on every fleet statement."""
    import re

    text = sql.strip()
    if not re.match(r"(?is)^select\b", text):
        return False
    # capture the whole comma list after FROM (implicit cross joins): every
    # relation in "FROM a, b" must prove system-only, not just the first
    targets = []
    for clause in re.findall(
        r"(?is)\b(?:from|join)\s+([a-z_][\w.\"]*(?:\s*,\s*[a-z_][\w.\"]*)*)",
        text,
    ):
        targets.extend(t.strip() for t in clause.split(","))
    if not targets:
        return False
    return all(t.lower().startswith("system.") for t in targets)


# --------------------------------------------------------------------- front


def main(argv: Optional[List[str]] = None) -> int:
    """Serve ONE coordinator process of a multi-process fleet front:
    binds the shared client-facing port with SO_REUSEPORT (kernel
    load-balances accepts across the forked siblings) plus a unique
    per-node port that membership advertises as the redirect/proxy
    target. A deployment forks N of these."""
    import argparse
    import signal
    import sys

    parser = argparse.ArgumentParser(prog="trino_tpu.runtime.fleet")
    parser.add_argument("--front-port", type=int, required=True,
                        help="shared SO_REUSEPORT client-facing port")
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--scale", type=float, default=0.0005)
    parser.add_argument("--ready-file", default="",
                        help="written with this node's unique url once up")
    parser.add_argument("--session", action="append", default=[],
                        metavar="K=V", help="session property overrides")
    parser.add_argument("--http-backlog", type=int, default=128,
                        help="listen(2) accept-backlog per front process "
                        "(the front plane's storm sizing; the default "
                        "deployment keeps the stdlib listen(5))")
    args = parser.parse_args(argv)

    # accept-queue sizing is part of the front plane: a concurrent-session
    # storm must queue in the kernel, not drop SYNs into ~1s retransmits
    if args.http_backlog > 0:
        os.environ.setdefault(
            "TRINO_TPU_HTTP_BACKLOG", str(args.http_backlog)
        )

    from ..runtime.local import LocalQueryRunner
    from ..server.coordinator import CoordinatorServer

    runner = LocalQueryRunner.tpch(scale=args.scale)
    for kv in args.session:
        k, _, v = kv.partition("=")
        parsed: object = v
        if v.lower() in ("true", "false"):
            parsed = v.lower() == "true"
        else:
            try:
                parsed = int(v)
            except ValueError:
                try:
                    parsed = float(v)
                except ValueError:
                    pass
        runner.session.set(k, parsed)
    server = CoordinatorServer(
        runner, node_id=args.node_id, front_port=args.front_port
    ).start()
    if args.ready_file:
        tmp = args.ready_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"http://{server.address}")
        os.replace(tmp, args.ready_file)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    server.stop()
    return 0


if __name__ == "__main__":  # pragma: no cover — exercised via subprocess
    import sys

    sys.exit(main())
