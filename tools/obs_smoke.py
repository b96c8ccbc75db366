#!/usr/bin/env python
"""Observability smoke check (tier-1): one TPC-H query, flight recorder on.

Runs a small TPC-H join query with the pipeline flight recorder enabled,
exports the Chrome/Perfetto trace JSON via tools/query_trace.py, and
validates it against the minimal schema contract:

- monotonic timestamps per (pid, tid) track
- paired B/E duration events (no unclosed/unopened spans)
- every event's pid/tid declared by process_name/thread_name metadata
- the events the plane promises are actually present (operator or bucket
  spans, and an XLA compile on a cold cache)

Exit code 0 = pass. Wired into the tier-1 suite as a fast test
(tests/test_observability.py::TestSmokeCheck) and runnable standalone:

    JAX_PLATFORMS=cpu python tools/obs_smoke.py
"""

from __future__ import annotations

import json
import os
import sys
from typing import List, Optional

SMOKE_SQL = """
SELECT n.n_name, count(*) AS suppliers
FROM supplier s JOIN nation n ON s.s_nationkey = n.n_nationkey
GROUP BY n.n_name
ORDER BY suppliers DESC, n.n_name
LIMIT 5
"""


def _registry_help_problems(required=()):
    """Shared HELP lint (registry-contract half) from the engine lint suite
    (tools/lint/rules.py) — the single implementation the per-plane copies
    collapsed into."""
    import os

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from tools.lint.rules import registry_help_problems

    return registry_help_problems(required=required)


def run_smoke(scale: float = 0.001, ooc: bool = False) -> List[str]:
    """Returns a list of problems; [] means the smoke check passed."""
    import os

    tools_dir = os.path.dirname(os.path.abspath(__file__))
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import query_trace  # sibling module

    trace, stats, rows = query_trace.run_query_trace(
        SMOKE_SQL, scale=scale, ooc=ooc
    )
    problems = query_trace.validate(trace)
    if rows == 0:
        problems.append("smoke query returned no rows")
    events = trace.get("traceEvents", [])
    cats = {e.get("cat") for e in events}
    if not ({"operator", "bucket"} & cats):
        problems.append(
            f"no operator/bucket spans recorded (cats={sorted(c for c in cats if c)})"
        )
    if ooc and "prefetch" not in cats and "transfer" not in cats:
        problems.append("ooc run recorded no prefetch/transfer events")
    return problems


def run_system_smoke(scale: float = 0.001) -> List[str]:
    """System-catalog smoke: the engine can query its own runtime state.

    Runs queries THROUGH a QueryManager (so system.runtime.queries has live
    + historical rows), then checks that

    - ``SELECT state, count(*) FROM system.runtime.queries GROUP BY 1``
      returns rows matching the declared schema (varchar state, bigint
      count) including the RUNNING scan itself and a FINISHED entry, and
    - a ``system.runtime.flight_events`` query under the recorder returns
      rows matching the declared schema (varchar kind, bigint dur).

    Returns a list of problems; [] means the smoke check passed.
    """
    from trino_tpu.runtime.local import LocalQueryRunner
    from trino_tpu.runtime.observability import RECORDER
    from trino_tpu.runtime.query_manager import QueryManager, QueryState

    problems: List[str] = []
    runner = LocalQueryRunner.tpch(scale=scale)
    mgr = QueryManager(runner.execute)
    warm = mgr.submit("SELECT count(*) FROM nation")
    warm.wait_done(120)
    if warm.state is not QueryState.FINISHED:
        return [f"warm-up query did not finish: {warm.state} {warm.error}"]

    q = mgr.submit(
        "SELECT state, count(*) FROM system.runtime.queries GROUP BY 1"
    )
    q.wait_done(120)
    if q.state is not QueryState.FINISHED:
        problems.append(f"queries scan failed: {q.error}")
    else:
        if not q.rows:
            problems.append("system.runtime.queries returned no rows")
        bad = [
            r for r in q.rows
            if not isinstance(r[0], str) or not isinstance(r[1], int)
        ]
        if bad:
            problems.append(f"queries rows off-schema: {bad}")
        states = dict(q.rows)
        if not states.get("FINISHED"):
            problems.append("no FINISHED query visible in history")
        if not states.get("RUNNING"):
            problems.append("the scan did not see itself RUNNING")

    RECORDER.enable()
    try:
        mgr.submit("SELECT count(*) FROM supplier").wait_done(120)
    finally:
        RECORDER.disable()
    fq = mgr.submit(
        "SELECT kind, cat, dur FROM system.runtime.flight_events "
        "ORDER BY dur DESC"
    )
    fq.wait_done(120)
    if fq.state is not QueryState.FINISHED:
        problems.append(f"flight_events scan failed: {fq.error}")
    else:
        if not fq.rows:
            problems.append("flight_events returned no rows under recorder")
        bad = [
            r for r in fq.rows
            if not isinstance(r[0], str) or not isinstance(r[2], int)
        ]
        if bad:
            problems.append(f"flight_events rows off-schema: {bad[:3]}")
    return problems


def run_exchange_smoke(scale: float = 0.001) -> List[str]:
    """Exchange data-plane smoke: a repartitioned TPC-H join under the flight
    recorder must leave a valid Perfetto export in which the plane's three
    stages — ``repartition_kernel`` (device epilogue), ``serde_encode``
    (sliced v2 frames), ``exchange_flush`` (coalesced sink writes) — appear
    as PAIRED B/E spans on monotonic tracks, so the observability plane can
    attribute the exchange win end to end.

    Returns a list of problems; [] means the smoke check passed.
    """
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    runner, sql = _fte_smoke_runner(scale)
    RECORDER.clear()
    RECORDER.enable()
    try:
        rows = runner.execute(sql).rows
    finally:
        RECORDER.disable()
    if not rows or not rows[0][0]:
        problems.append(f"exchange smoke join returned {rows!r}")
    trace = RECORDER.chrome_trace()
    RECORDER.clear()
    problems += validate_chrome_trace(trace)  # paired B/E + monotonic tracks
    events = trace.get("traceEvents", [])
    for name in ("repartition_kernel", "serde_encode", "exchange_flush"):
        b = sum(1 for e in events if e.get("name") == name and e.get("ph") == "B")
        e_ = sum(1 for e in events if e.get("name") == name and e.get("ph") == "E")
        if not b:
            problems.append(f"no {name} span in the exchange trace")
        elif b != e_:
            problems.append(f"{name} spans unpaired: {b} B vs {e_} E")
    return problems


def _fte_smoke_runner(scale: float):
    """Shared smoke shape for the FTE-tier checks: a 2-worker distributed
    runner under retry_policy=TASK, pinned to the repartitioned join shape
    (smoke data is tiny — AUTO would broadcast, and the stats-derived
    partition-count target would collapse the hash stage to one part)."""
    from trino_tpu.parallel.runner import DistributedQueryRunner

    runner = DistributedQueryRunner.tpch(scale=scale, n_workers=2)
    runner.session.set("retry_policy", "TASK")
    runner.session.set("join_distribution_type", "PARTITIONED")
    runner.session.set("target_partition_rows", 500)
    sql = "SELECT count(*) FROM lineitem JOIN orders ON l_orderkey = o_orderkey"
    return runner, sql


def run_fte_smoke(scale: float = 0.001) -> List[str]:
    """FTE control-plane smoke: a distributed query under an INJECTED task
    failure must recover via the event-driven scheduler, leaving a valid
    Perfetto export in which ``task_attempt`` spans are PAIRED/monotonic
    with outcome labels on their close events (a failed attempt followed by
    a higher-numbered ok attempt of the same task), and the retry counter
    (``trino_tpu_task_retries_total``) incremented.

    Returns a list of problems; [] means the smoke check passed.
    """
    from trino_tpu.runtime.failure import ChaosInjector
    from trino_tpu.runtime.metrics import REGISTRY
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    runner, sql = _fte_smoke_runner(scale)
    retries = REGISTRY.counter(
        "trino_tpu_task_retries_total",
        help="FTE task retries after classified retryable failures",
    )
    before = retries.value
    RECORDER.clear()
    RECORDER.enable()
    try:
        with ChaosInjector() as chaos:
            chaos.arm("task_crash_mid_execute", times=1)
            rows = runner.execute(sql).rows
    finally:
        RECORDER.disable()
    if not rows or not rows[0][0]:
        problems.append(f"fte smoke join returned {rows!r}")
    if chaos.fired.get("task_crash_mid_execute", 0) != 1:
        problems.append("chaos harness never fired the mid-execute crash")
    trace = RECORDER.chrome_trace()
    RECORDER.clear()
    problems += validate_chrome_trace(trace)  # paired B/E + monotonic tracks
    events = trace.get("traceEvents", [])
    begins = [
        e for e in events
        if e.get("name") == "task_attempt" and e.get("ph") == "B"
    ]
    ends = [
        e for e in events
        if e.get("name") == "task_attempt" and e.get("ph") == "E"
    ]
    if not begins:
        problems.append("no task_attempt span in the FTE trace")
    elif len(begins) != len(ends):
        problems.append(
            f"task_attempt spans unpaired: {len(begins)} B vs {len(ends)} E"
        )
    outcomes = [(e.get("args") or {}).get("outcome") for e in ends]
    if any(o not in ("ok", "failed") for o in outcomes):
        problems.append(f"task_attempt E events missing outcome labels: {outcomes}")
    # per-task attempt numbers must be monotonic, and the injected failure
    # must show as failed attempt N -> ok attempt > N for the SAME task.
    # Key by the task TEXT (query id + fragment + partition): a leftover
    # attempt thread from an earlier query in this process must not collide
    # with this run's (fragment, partition) numbering
    by_task = {}
    for e in begins:
        args = e.get("args") or {}
        task = str(args.get("task") or "")
        key = (task.rsplit("_a", 1)[0],
               args.get("fragment"), args.get("partition"))
        by_task.setdefault(key, []).append(int(args.get("attempt", -1)))
    if any(a != sorted(set(a)) for a in by_task.values()):
        problems.append(f"task attempt numbers not monotonic: {by_task}")
    if not any(len(a) > 1 for a in by_task.values()):
        problems.append("no task shows a retried attempt in the trace")
    if retries.value <= before:
        problems.append(
            "trino_tpu_task_retries_total did not increment under injected failure"
        )
    return problems


def run_memory_smoke() -> List[str]:
    """Memory-arbitration smoke: the three new flight events —
    ``memory_reserve_blocked`` (backpressure), ``memory_revoke`` (spill
    escalation), ``low_memory_kill`` (the killer) — must appear as PAIRED
    B/E spans on monotonic tracks in one deterministic exercise of the pool,
    and the new Prometheus counters (``trino_tpu_memory_blocked_queries``,
    ``trino_tpu_low_memory_kills_total``, ``trino_tpu_revoked_bytes_total``)
    must be registered with HELP text (the existing HELP lint contract).

    Single-threaded by design: blocked reservers drive the arbiter
    themselves (runtime/memory.py), so one thread exercises block -> revoke
    -> kill without races. Returns a list of problems; [] = pass.
    """
    from trino_tpu.runtime.memory import (
        AggregatedMemoryContext,
        ClusterMemoryManager,
        MemoryPool,
    )
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    RECORDER.clear()
    RECORDER.enable()
    try:
        pool = MemoryPool(1000, name="smoke", reserve_timeout=10)
        killed: List[str] = []
        ClusterMemoryManager(
            pool,
            kill_fn=lambda q, r: (killed.append(q), pool.free_owner(q)),
            spill_after=0.0, kill_after=0.05,
        )
        # qa parks 600 revocable bytes behind a revoker
        ctx_a = AggregatedMemoryContext(pool=pool, owner="qa")
        parked = ctx_a.new_local("parked", revocable=True)
        parked.set_bytes(600)

        class Revoker:
            def revoke(self, nbytes):
                freed = parked.get_bytes()
                parked.set_bytes(0)
                return freed

        revoker = Revoker()
        pool.add_revoker(revoker)
        # qb wants 700: blocks (600+700 > 1000) -> arbiter REVOKES qa -> fits
        AggregatedMemoryContext(pool=pool, owner="qb").new_local("op").set_bytes(700)
        # qc wants 700: blocks, nothing revocable left -> the KILLER sheds qb
        AggregatedMemoryContext(pool=pool, owner="qc").new_local("op").set_bytes(700)
        if killed != ["qb"]:
            problems.append(f"killer shed {killed!r}, expected ['qb']")
    finally:
        RECORDER.disable()
    trace = RECORDER.chrome_trace()
    RECORDER.clear()
    problems += validate_chrome_trace(trace)  # paired B/E + monotonic tracks
    events = trace.get("traceEvents", [])
    for name in ("memory_reserve_blocked", "memory_revoke", "low_memory_kill"):
        b = sum(1 for e in events if e.get("name") == name and e.get("ph") == "B")
        e_ = sum(1 for e in events if e.get("name") == name and e.get("ph") == "E")
        if not b:
            problems.append(f"no {name} span in the memory trace")
        elif b != e_:
            problems.append(f"{name} spans unpaired: {b} B vs {e_} E")
    outcomes = [
        (e.get("args") or {}).get("outcome")
        for e in events
        if e.get("name") == "memory_reserve_blocked" and e.get("ph") == "E"
    ]
    if "granted" not in outcomes:
        problems.append(
            f"no blocked reservation was granted (outcomes={outcomes})"
        )
    problems += _registry_help_problems(required=(
        "trino_tpu_memory_blocked_queries",
        "trino_tpu_low_memory_kills_total",
        "trino_tpu_revoked_bytes_total",
        "trino_tpu_memory_reserve_blocked_total",
    ))
    return problems


def run_stats_smoke(scale: float = 0.001) -> List[str]:
    """Statistics-feedback-plane smoke: a deliberately mis-estimated query
    under the flight recorder must leave a valid Perfetto export with a
    PAIRED ``stats_feedback`` span (monotonic per track, like every event)
    containing ``cardinality_misestimate`` instants; the per-node actuals
    must be queryable through a schema-checked
    ``system.runtime.operator_stats``; and the q-error metrics plus the
    ``system.metrics.histograms`` p50/p95/p99 interpolation columns must be
    registered with HELP text and ordered sanely.

    Returns a list of problems; [] means the smoke check passed.
    """
    from trino_tpu.runtime.local import LocalQueryRunner
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    runner = LocalQueryRunner.tpch(scale=scale)
    # any q-error > 1 counts as a mis-estimate: the LIKE filter below is a
    # guaranteed misestimate (unknown-selectivity coefficient vs near-zero
    # actual), so events fire deterministically
    runner.session.set("qerror_threshold", 1.0)
    RECORDER.clear()
    RECORDER.enable()
    try:
        rows = runner.execute(
            "SELECT count(*) FROM orders "
            "WHERE o_comment LIKE '%no such comment ever%'"
        ).rows
    finally:
        RECORDER.disable()
    if not rows:
        problems.append(f"stats smoke query returned {rows!r}")
    trace = RECORDER.chrome_trace()
    RECORDER.clear()
    problems += validate_chrome_trace(trace)  # paired B/E + monotonic tracks
    events = trace.get("traceEvents", [])
    b = sum(1 for e in events
            if e.get("name") == "stats_feedback" and e.get("ph") == "B")
    e_ = sum(1 for e in events
             if e.get("name") == "stats_feedback" and e.get("ph") == "E")
    if not b:
        problems.append("no stats_feedback span in the trace")
    elif b != e_:
        problems.append(f"stats_feedback spans unpaired: {b} B vs {e_} E")
    mis = [e for e in events if e.get("name") == "cardinality_misestimate"]
    if not mis:
        problems.append("no cardinality_misestimate event under a forced "
                        "misestimate")
    for ev in mis:
        args = ev.get("args") or {}
        if args.get("q") is None or args.get("actual") is None:
            problems.append(f"misestimate event missing q/actual: {args}")

    # per-node actuals are SQL-queryable and on-schema
    res = runner.execute(
        "SELECT plan_node, actual_rows, q_error "
        "FROM system.runtime.operator_stats"
    )
    if not res.rows:
        problems.append("system.runtime.operator_stats returned no rows")
    bad = [
        r for r in res.rows
        if not isinstance(r[0], str) or not isinstance(r[1], int)
        or not (r[2] is None or isinstance(r[2], float))
    ]
    if bad:
        problems.append(f"operator_stats rows off-schema: {bad[:3]}")
    if not any(r[2] is not None and r[2] > 1.0 for r in res.rows):
        problems.append("no operator_stats row carries the misestimate q-error")
    hist = runner.execute(
        "SELECT actual_rows FROM system.optimizer.stats_history"
    )
    if not hist.rows:
        problems.append("system.optimizer.stats_history returned no rows")

    # histogram quantile columns: monotone p50 <= p95 <= p99 on a populated
    # series (the q-error histogram the run above observed into)
    q = runner.execute(
        "SELECT p50, p95, p99 FROM system.metrics.histograms "
        "WHERE name = 'trino_tpu_cardinality_qerror' AND count > 0"
    )
    if not q.rows:
        problems.append("q-error histogram missing from system.metrics.histograms")
    for p50, p95, p99 in q.rows:
        if p50 is None or p95 is None or p99 is None:
            problems.append(f"NULL quantile on a populated histogram: "
                            f"{(p50, p95, p99)}")
            break
        if not (p50 <= p95 <= p99):
            problems.append(f"quantiles not monotone: {(p50, p95, p99)}")
            break

    # HELP lint (shared rule): trino_tpu_flight_dropped_events_total is NOT
    # required — it registers on first overflow and absence is healthy; when
    # present the shared rule covers its HELP text like every other series
    problems += _registry_help_problems(required=(
        "trino_tpu_cardinality_misestimates_total",
        "trino_tpu_cardinality_qerror",
    ))
    return problems


def run_cache_smoke(scale: float = 0.001) -> List[str]:
    """Warm-path cache plane smoke (runtime/cachestore.py): a warm-up /
    hit / invalidate cycle under the flight recorder must leave a valid
    Perfetto export with PAIRED ``cache_lookup``/``cache_store``/
    ``cache_invalidate`` spans (monotonic per track) carrying hit/miss
    outcomes on the E-event args; the tier counters must be registered
    with HELP text; and ``system.runtime.caches`` must be on-schema.

    Returns a list of problems; [] means the smoke check passed.
    """
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.runtime.cachestore import CACHES
    from trino_tpu.runtime.local import LocalQueryRunner
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    runner = LocalQueryRunner.tpch(scale=scale)
    runner.register_catalog("mem", MemoryConnector())
    runner.execute("CREATE TABLE mem.default.kv (x bigint)")
    runner.execute("INSERT INTO mem.default.kv VALUES (1), (2)")
    runner.session.set("result_cache", True)
    runner.session.set("plan_cache_size", 16)
    runner.session.set("fragment_cache", True)
    CACHES.clear()
    RECORDER.clear()
    RECORDER.enable()
    try:
        q = "SELECT count(*) FROM mem.default.kv"
        r1 = runner.execute(q)  # cold: misses, then stores
        r2 = runner.execute(q)  # warm: result-tier hit
        runner.execute("INSERT INTO mem.default.kv VALUES (3)")  # invalidate
        r3 = runner.execute(q)  # fresh data, never the stale entry
    finally:
        RECORDER.disable()
    if r1.rows != [(2,)] or r2.rows != [(2,)] or r3.rows != [(3,)]:
        problems.append(
            f"cache smoke rows wrong: {r1.rows} {r2.rows} {r3.rows}"
        )
    if (r2.query_stats or {}).get("cacheHitTier") != "result":
        problems.append("warm run not tagged cacheHitTier=result")
    trace = RECORDER.chrome_trace()
    RECORDER.clear()
    problems += validate_chrome_trace(trace)  # paired B/E + monotonic tracks
    events = trace.get("traceEvents", [])
    for name in ("cache_lookup", "cache_store", "cache_invalidate"):
        b = sum(1 for e in events
                if e.get("name") == name and e.get("ph") == "B")
        e_ = sum(1 for e in events
                 if e.get("name") == name and e.get("ph") == "E")
        if not b:
            problems.append(f"no {name} span in the trace")
        elif b != e_:
            problems.append(f"{name} spans unpaired: {b} B vs {e_} E")
    outcomes = {
        (e.get("args") or {}).get("outcome")
        for e in events
        if e.get("name") == "cache_lookup" and e.get("ph") == "E"
    }
    if not {"hit", "miss"} <= outcomes:
        problems.append(f"cache_lookup outcomes incomplete: {outcomes}")
    stored = [
        e for e in events
        if e.get("name") == "cache_store" and e.get("ph") == "E"
        and (e.get("args") or {}).get("outcome") == "stored"
    ]
    if not stored:
        problems.append("no cache_store span with outcome=stored")

    # the plane's snapshot table is on-schema and saw the traffic
    res = runner.execute(
        "SELECT tier, entries, bytes, hits, misses, evictions, invalidations "
        "FROM system.runtime.caches"
    )
    tiers = {r[0] for r in res.rows}
    if tiers != {"plan", "result", "fragment"}:
        problems.append(f"system.runtime.caches tiers off: {tiers}")
    bad = [
        r for r in res.rows
        if not isinstance(r[0], str)
        or not all(isinstance(v, int) for v in r[1:])
    ]
    if bad:
        problems.append(f"system.runtime.caches rows off-schema: {bad[:3]}")
    if not any(r[0] == "result" and r[3] >= 1 for r in res.rows):
        problems.append("result tier shows no hit after the warm run")

    # HELP lint (shared rule); trino_tpu_cache_evictions_total registers on
    # first eviction, so it is help-checked when present but not required
    problems += _registry_help_problems(required=(
        "trino_tpu_cache_hits_total",
        "trino_tpu_cache_misses_total",
        "trino_tpu_cache_invalidations_total",
    ))
    CACHES.clear()
    return problems


def run_batching_smoke(scale: float = 0.001) -> List[str]:
    """Device-batching-plane smoke (runtime/device_scheduler.py): a burst
    of concurrent identical queries with ``device_batching=on`` under the
    flight recorder must leave a valid Perfetto export with PAIRED
    ``batch_admit``/``batch_launch``/``batch_demux`` spans (lane count,
    packed rows, and the launch key on the E-args), results bit-identical
    to the serial run, the lane-occupancy/batched-fragments/program-launch
    metrics registered with HELP text, and at least one shared-scan hit.

    Returns a list of problems; [] means the smoke check passed.
    """
    import threading

    from trino_tpu.runtime.device_scheduler import SCHEDULER
    from trino_tpu.runtime.local import LocalQueryRunner
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    sql = (
        "SELECT l_returnflag, sum(l_quantity), count(*) "
        "FROM lineitem GROUP BY l_returnflag ORDER BY l_returnflag"
    )
    runner = LocalQueryRunner.tpch(scale=scale)
    serial = runner.execute(sql).rows
    runner.session.set("device_batching", True)
    runner.session.set("batch_admit_window_ms", 25.0)
    runner.execute(sql)  # warm compiles so the burst overlaps
    results: List[Optional[list]] = [None] * 4
    errors: List[BaseException] = []
    # a 1-core box can stagger the burst so badly nothing overlaps; the
    # smoke checks the PLANE's artifacts, not this host's scheduler, so
    # retry the burst until some dedup tier engaged (bounded attempts)
    for _ in range(3):
        SCHEDULER.reset_stats()
        RECORDER.clear()
        RECORDER.enable()
        try:
            results = [None] * 4
            errors = []

            def go(i: int) -> None:
                try:
                    results[i] = runner.execute(sql).rows
                except BaseException as e:  # noqa: BLE001 — reported below
                    errors.append(e)

            threads = [
                threading.Thread(
                    target=go, args=(i,), name=f"smoke-client-{i}"
                )
                for i in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            RECORDER.disable()
        if errors or SCHEDULER.subsumed >= 1 or SCHEDULER.batched_launches >= 1:
            break
    if errors:
        problems.append(f"batched burst raised: {errors[:2]}")
    if any(r != serial for r in results if r is not None):
        problems.append("batched results not bit-identical to serial run")
    trace = RECORDER.chrome_trace()
    RECORDER.clear()
    problems += validate_chrome_trace(trace)  # paired B/E + monotonic tracks
    events = trace.get("traceEvents", [])
    for name in ("batch_admit", "batch_launch", "batch_demux"):
        b = sum(1 for e in events
                if e.get("name") == name and e.get("ph") == "B")
        e_ = sum(1 for e in events
                 if e.get("name") == name and e.get("ph") == "E")
        if not b:
            problems.append(f"no {name} span in the trace")
        elif b != e_:
            problems.append(f"{name} spans unpaired: {b} B vs {e_} E")
    launches = [
        (e.get("args") or {})
        for e in events
        if e.get("name") == "batch_launch" and e.get("ph") == "E"
    ]
    if not any(
        a.get("lanes") and a.get("packed_rows") and a.get("key")
        for a in launches
    ):
        problems.append(
            f"batch_launch E-args missing lanes/packed_rows/key: {launches[:3]}"
        )
    multi_lane = any((a.get("lanes") or 0) >= 2 for a in launches)
    if not multi_lane and SCHEDULER.subsumed < 1:
        # identical concurrent queries normally SUBSUME (whole-subtree
        # single-flight) before they would pack; either dedup tier counts
        problems.append(
            "concurrent burst neither packed a multi-lane launch nor "
            "subsumed a fragment"
        )
    if SCHEDULER.scan_shares < 1:
        problems.append(
            f"no shared-scan elimination in the burst "
            f"(shares={SCHEDULER.scan_shares})"
        )
    problems += _registry_help_problems(required=(
        "trino_tpu_device_programs_total",
        "trino_tpu_batched_fragments_total",
        "trino_tpu_batch_lane_occupancy",
        "trino_tpu_shared_scan_hits_total",
    ))
    return problems


def run_tensor_smoke(rows: int = 64, dim: int = 8) -> List[str]:
    """Tensor-plane smoke (ops/tensor.py): a vector top-k query with
    ``tensor_plane``/``vector_topk_fusion`` on, under the flight recorder,
    must leave a valid Perfetto export with PAIRED ``vector_kernel`` and
    ``topk_fusion`` spans carrying rows/dim (and k) on their E-args, fused
    results bit-identical to the serial project+sort pair, strictly fewer
    device program launches, and the launch/fallback counters registered
    with HELP text.

    Returns a list of problems; [] means the smoke check passed.
    """
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.ops import tensor as T
    from trino_tpu.runtime.device_scheduler import program_launches
    from trino_tpu.runtime.local import LocalQueryRunner
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    runner = LocalQueryRunner.tpch(scale=0.001)
    runner.register_catalog("memory", MemoryConnector())
    runner.execute(
        f"CREATE TABLE memory.default.tensor_smoke (id bigint, v vector({dim}))"
    )
    values = ", ".join(
        "({}, ARRAY[{}])".format(
            i, ", ".join(f"{((i * 7 + j * 3) % 11) / 10.0}" for j in range(dim))
        )
        for i in range(rows)
    )
    runner.execute(f"INSERT INTO memory.default.tensor_smoke VALUES {values}")
    q = ", ".join("1.0" if j % 2 == 0 else "0.25" for j in range(dim))
    sql = (
        "SELECT id FROM memory.default.tensor_smoke "
        f"ORDER BY cosine_similarity(v, ARRAY[{q}]) DESC, id LIMIT 5"
    )
    serial = runner.execute(sql).rows
    runner.session.set("tensor_plane", True)
    runner.session.set("vector_topk_fusion", True)
    # register the fallback counter family so the HELP lint sees it
    T.on_topk_fallback("smoke_probe")
    RECORDER.clear()
    RECORDER.enable()
    try:
        v0 = T.vector_launches()
        n0 = program_launches()
        fused = runner.execute(sql).rows
        fused_launches = program_launches() - n0
        fused_vector = T.vector_launches() - v0
        n0 = program_launches()
        runner.session.set("vector_topk_fusion", False)
        serial2 = runner.execute(sql).rows
        serial_launches = program_launches() - n0
    finally:
        RECORDER.disable()
        runner.session.set("tensor_plane", False)
        runner.session.set("vector_topk_fusion", False)
    if fused != serial or serial2 != serial:
        problems.append("fused results not bit-identical to the serial pair")
    if fused_vector < 1:
        problems.append("fusion-on run booked no vector kernel launches")
    if not fused_launches < serial_launches:
        problems.append(
            f"fused path did not dispatch strictly fewer device programs "
            f"({fused_launches} vs serial {serial_launches})"
        )
    trace = RECORDER.chrome_trace()
    RECORDER.clear()
    problems += validate_chrome_trace(trace)  # paired B/E + monotonic tracks
    events = trace.get("traceEvents", [])
    for name in ("vector_kernel", "topk_fusion"):
        b = sum(1 for e in events
                if e.get("name") == name and e.get("ph") == "B")
        e_ = sum(1 for e in events
                 if e.get("name") == name and e.get("ph") == "E")
        if not b:
            problems.append(f"no {name} span in the trace")
        elif b != e_:
            problems.append(f"{name} spans unpaired: {b} B vs {e_} E")
    fusions = [
        (e.get("args") or {})
        for e in events
        if e.get("name") == "topk_fusion" and e.get("ph") == "E"
    ]
    if not any(
        a.get("rows") and a.get("dim") == dim and a.get("k") == 5
        for a in fusions
    ):
        problems.append(
            f"topk_fusion E-args missing rows/dim/k: {fusions[:3]}"
        )
    problems += _registry_help_problems(required=(
        "trino_tpu_vector_kernel_launches_total",
        "trino_tpu_vector_topk_fallbacks_total",
        "trino_tpu_device_programs_total",
    ))
    return problems


def run_ha_smoke(scale: float = 0.001) -> List[str]:
    """Serving-fabric-plane smoke: one deterministic exercise of the HA
    primitives under the flight recorder must leave paired
    ``leader_lease`` / ``dispatch_replay`` / ``worker_drain`` spans on
    monotonic tracks, a crash->resume round trip bit-identical to the
    uninterrupted run, and the new counters
    (``trino_tpu_failovers_total`` / ``trino_tpu_lease_renewals_total`` /
    ``trino_tpu_recovery_torn_records_total``) registered with HELP text.
    Returns a list of problems; [] = pass."""
    import os
    import tempfile
    import time

    from trino_tpu.parallel.runner import DistributedQueryRunner
    from trino_tpu.runtime.failure import ChaosInjector
    from trino_tpu.runtime.ha import (
        CoordinatorCrashError,
        DispatchJournal,
        LeaderLease,
        ScaleController,
        orphaned_journals,
        resume_fte_query,
    )
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    RECORDER.clear()
    RECORDER.enable()
    tmp = tempfile.mkdtemp(prefix="ha_smoke_")
    try:
        # --- leader lease: acquire, renew, chaos expiry, fenced takeover
        primary = LeaderLease(os.path.join(tmp, "ha"), "primary", ttl=0.2)
        standby = LeaderLease(os.path.join(tmp, "ha"), "standby", ttl=0.2)
        if not primary.acquire() or not primary.is_leader():
            problems.append("primary failed to acquire a free lease")
        if standby.acquire():
            problems.append("standby acquired a HELD lease (two leaders)")
        if not primary.renew():
            problems.append("holder renewal failed")
        with ChaosInjector() as chaos:
            chaos.arm("lease_expire", times=1)
            if primary.renew():
                problems.append("lease_expire chaos did not forfeit renewal")
        if primary.is_leader():
            problems.append("forfeited holder still believes it leads")
        time.sleep(0.25)
        if not standby.acquire() or standby.epoch != 2:
            problems.append("standby takeover failed after lease expiry")

        # --- dispatch handoff: crash mid-query, standby replays the journal
        exdir = os.path.join(tmp, "exchange")

        def make_runner():
            r = DistributedQueryRunner.tpch(scale=scale, n_workers=2)
            r.session.set("retry_policy", "TASK")
            r.session.set("fte_exchange_dir", exdir)
            r.session.set("ha_plane", True)
            return r

        oracle = make_runner().execute(SMOKE_SQL).rows
        with ChaosInjector() as chaos:
            chaos.arm("coordinator_crash", times=1, match="_post")
            try:
                make_runner().execute(SMOKE_SQL)
                problems.append("coordinator_crash chaos did not fire")
            except CoordinatorCrashError:
                pass
        orphans = orphaned_journals(exdir)
        if len(orphans) != 1:
            problems.append(f"expected 1 orphaned journal, found {len(orphans)}")
        else:
            resumed = resume_fte_query(make_runner(), orphans[0])
            if resumed.rows != oracle:
                problems.append("resumed result differs from the oracle run")

        # --- torn-tail recovery: a kill-mid-append journal reads clean
        torn_path = os.path.join(tmp, "torn", "journal.jsonl")
        j = DispatchJournal(torn_path)
        j.append({"kind": "begin", "query_id": "qt", "sql": "SELECT 1"})
        with open(torn_path, "a") as f:
            f.write('{"kind": "stage_done", "fid"')  # the torn tail
        records, torn = DispatchJournal.read(torn_path)
        if len(records) != 1 or torn != 1:
            problems.append(
                f"torn-tail read returned {len(records)} records / {torn} torn"
            )

        # --- elastic drain: a graceful scale-down emits worker_drain
        retired: List[str] = []
        ctl = ScaleController(retire=retired.append, min_workers=0)
        ctl.workers.append("http://127.0.0.1:9")
        if not ctl.drain("http://127.0.0.1:9", wait_secs=0.5):
            problems.append("idle worker did not drain clean")
        if retired != ["http://127.0.0.1:9"]:
            problems.append(f"drain did not retire the worker: {retired}")
    finally:
        RECORDER.disable()
    trace = RECORDER.chrome_trace()
    RECORDER.clear()
    problems += validate_chrome_trace(trace)  # paired B/E + monotonic tracks
    events = trace.get("traceEvents", [])
    for name in ("leader_lease", "dispatch_replay", "worker_drain"):
        b = sum(1 for e in events if e.get("name") == name and e.get("ph") == "B")
        e_ = sum(1 for e in events if e.get("name") == name and e.get("ph") == "E")
        if not b:
            problems.append(f"no {name} span in the ha trace")
        elif b != e_:
            problems.append(f"{name} spans unpaired: {b} B vs {e_} E")
    outcomes = [
        (e.get("args") or {}).get("outcome")
        for e in events
        if e.get("name") == "leader_lease" and e.get("ph") == "E"
    ]
    if "acquired" not in outcomes:
        problems.append(f"no lease acquisition recorded (outcomes={outcomes})")
    problems += _registry_help_problems(required=(
        "trino_tpu_failovers_total",
        "trino_tpu_lease_renewals_total",
        "trino_tpu_recovery_torn_records_total",
    ))
    return problems


def run_objectstore_smoke(scale: float = 0.001) -> List[str]:
    """Object-store substrate smoke (runtime/objectstore.py): the durable
    planes — leader lease, dispatch journal, shared warm tier, durable
    exchange — run on the rename-free object backend with the store chaos
    sites armed (throttles retry, torn puts disambiguate by re-reading the
    key, a lagging LIST only delays discovery), a killed coordinator
    resumes bit-identical to the oracle, every request leaves a paired
    ``object_store_request`` span, and the four
    ``trino_tpu_object_store_*_total`` counters are registered with HELP
    text. Returns a list of problems; [] = pass."""
    import tempfile
    import time

    from trino_tpu.fs import Location
    from trino_tpu.parallel.runner import DistributedQueryRunner
    from trino_tpu.runtime.failure import ChaosInjector
    from trino_tpu.runtime.ha import (
        CoordinatorCrashError,
        LeaderLease,
        SharedCacheTier,
        orphaned_journals,
        resume_fte_query,
    )
    from trino_tpu.runtime.metrics import REGISTRY
    from trino_tpu.runtime.objectstore import REQUESTS_HELP, backend_for_root
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    RECORDER.clear()
    RECORDER.enable()
    tmp = tempfile.mkdtemp(prefix="objstore_smoke_")
    base = "object://" + tmp
    requests = REGISTRY.counter(
        "trino_tpu_object_store_requests_total", help=REQUESTS_HELP
    )
    n0 = requests.value
    try:
        exdir = f"{base}/exchange"

        def make_runner():
            r = DistributedQueryRunner.tpch(scale=scale, n_workers=2)
            r.session.set("retry_policy", "TASK")
            r.session.set("fte_exchange_dir", exdir)
            r.session.set("ha_plane", True)
            return r

        oracle = make_runner().execute(SMOKE_SQL).rows

        # --- the conditional-put primitive: exactly one If-None-Match win
        # (also guarantees the cas_conflicts counter exists for the lint)
        fs, _ = backend_for_root(f"{base}/probe")
        if not fs.write_if_absent(Location("object", "probe"), b"a"):
            problems.append("first If-None-Match claim lost on a fresh key")
        if fs.write_if_absent(Location("object", "probe"), b"b"):
            problems.append("duplicate If-None-Match claim succeeded")

        # --- lease takeover + warm tier with the store misbehaving
        with ChaosInjector() as chaos:
            chaos.arm("object_store_throttle", times=3)
            chaos.arm("object_store_torn_put", times=2)
            primary = LeaderLease(f"{base}/ha", "primary", ttl=0.2)
            standby = LeaderLease(f"{base}/ha", "standby", ttl=0.2)
            if not primary.acquire() or not primary.is_leader():
                problems.append("primary failed to acquire the object lease")
            if standby.acquire():
                problems.append("standby acquired a HELD object lease")
            time.sleep(0.25)  # the primary "pauses" past its TTL
            if not standby.acquire() or standby.epoch != 2:
                problems.append("standby takeover failed on the object lease")
            tier = SharedCacheTier(f"{base}/warm")
            tier.publish("k1", {"rows": [[1, 2]]})
            got = tier.get("k1")
            if not got or got.get("rows") != [[1, 2]]:
                problems.append(f"object warm-tier round trip failed: {got!r}")
            for site in ("object_store_throttle", "object_store_torn_put"):
                if not chaos.fired.get(site):
                    problems.append(f"{site} chaos never fired")

        # --- crash -> resume entirely over the object exchange
        with ChaosInjector() as chaos:
            chaos.arm("coordinator_crash", times=1, match="_post")
            chaos.arm("object_store_list_lag", times=1)
            try:
                make_runner().execute(SMOKE_SQL)
                problems.append("coordinator_crash chaos did not fire")
            except CoordinatorCrashError:
                pass
            orphans = orphaned_journals(exdir)
            if not orphans:
                # the armed LIST lagged and hid the journal; per-key reads
                # stay strong, so one re-scan converges
                orphans = orphaned_journals(exdir)
            if len(orphans) != 1:
                problems.append(
                    f"expected 1 orphaned object journal, found {len(orphans)}"
                )
            else:
                resumed = resume_fte_query(make_runner(), orphans[0])
                if resumed.rows != oracle:
                    problems.append(
                        "object-substrate resume differs from the oracle run"
                    )
    finally:
        RECORDER.disable()
    trace = RECORDER.chrome_trace()
    RECORDER.clear()
    problems += validate_chrome_trace(trace)  # paired B/E + monotonic tracks
    events = trace.get("traceEvents", [])
    b = sum(
        1 for e in events
        if e.get("name") == "object_store_request" and e.get("ph") == "B"
    )
    e_ = sum(
        1 for e in events
        if e.get("name") == "object_store_request" and e.get("ph") == "E"
    )
    if not b:
        problems.append("no object_store_request span in the trace")
    elif b != e_:
        problems.append(f"object_store_request spans unpaired: {b} B vs {e_} E")
    outcomes = {
        (e.get("args") or {}).get("outcome")
        for e in events
        if e.get("name") == "object_store_request" and e.get("ph") == "E"
    }
    if "ok" not in outcomes:
        problems.append(
            "no successful object request recorded "
            f"(outcomes={sorted(o for o in outcomes if o)})"
        )
    if not ({"throttled", "timeout", "recovered"} & outcomes):
        problems.append("chaos left no throttled/timeout/recovered outcome")
    if requests.value <= n0:
        problems.append("trino_tpu_object_store_requests_total never moved")
    problems += _registry_help_problems(required=(
        "trino_tpu_object_store_requests_total",
        "trino_tpu_object_store_retries_total",
        "trino_tpu_object_store_throttles_total",
        "trino_tpu_object_store_cas_conflicts_total",
    ))
    return problems


def run_cluster_smoke(scale: float = 0.001) -> List[str]:
    """Cluster observability plane smoke (runtime/clusterobs.py): two
    leased coordinators + two REAL WorkerServers on one substrate. An FTE
    query killed mid-run by ``coordinator_crash`` chaos and resumed by the
    standby (epoch 2) must yield ONE merged Perfetto trace — the
    coordinator segment plus both workers' ``/v1/flightrecorder?query_id=``
    segments pulled over the signed wire, skew-aligned by announcement-
    clock offsets — with >=2 worker lanes carrying task spans, paired B/E
    on monotonic tracks, ``task_attempt`` spans from BOTH leader epochs,
    and dispatch-journal markers on their own lane. The federated
    exposition must pass the HELP lint with per-node labels, and the
    persisted query profile's stage breakdown must sum to within 5% of the
    resumed run's wall time. Returns a list of problems; [] = pass.
    """
    import json as _json
    import os
    import tempfile
    import time
    import urllib.request

    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.metadata import CatalogManager, Session
    from trino_tpu.parallel.runner import DistributedQueryRunner
    from trino_tpu.runtime import clusterobs
    from trino_tpu.runtime.clusterobs import (
        ClockSync,
        ClusterMetrics,
        assemble_cluster_trace,
        build_profile,
        profile_breakdown_secs,
    )
    from trino_tpu.runtime.failure import ChaosInjector
    from trino_tpu.runtime.ha import (
        CoordinatorCrashError,
        DispatchJournal,
        LeaderLease,
        orphaned_journals,
        resume_fte_query,
    )
    from trino_tpu.runtime.metrics import REGISTRY
    from trino_tpu.runtime.observability import (
        RECORDER,
        FlightRecorder,
        validate_chrome_trace,
    )
    from trino_tpu.server.worker import SIGNATURE_HEADER, WorkerServer, sign

    problems: List[str] = []
    secret = "cluster-obs-smoke"
    sql = "SELECT count(*) FROM lineitem JOIN orders ON l_orderkey = o_orderkey"
    tmp = tempfile.mkdtemp(prefix="cluster_obs_smoke_")
    exdir = os.path.join(tmp, "exchange")
    profdir = os.path.join(tmp, "profiles")
    schema = "sf" + f"{scale:g}".replace(".", "_")

    def catalogs():
        c = CatalogManager()
        c.register("tpch", TpchConnector(scale=scale, split_target_rows=512))
        return c

    # two REAL workers, each with its OWN flight ring (per-node segments —
    # in production each process's global ring is naturally per-node)
    workers = [WorkerServer(catalogs(), secret=secret).start() for _ in range(2)]
    for w in workers:
        w.tasks.recorder = FlightRecorder()
        w.tasks.recorder.enable()

    def make_runner(lease):
        r = DistributedQueryRunner(
            Session(catalog="tpch", schema=schema), n_workers=2,
            worker_urls=[f"http://{w.address}" for w in workers],
            secret=secret,
        )
        r.catalogs.register(
            "tpch", TpchConnector(scale=scale, split_target_rows=512)
        )
        r.session.set("retry_policy", "TASK")
        r.session.set("join_distribution_type", "PARTITIONED")
        r.session.set("target_partition_rows", 500)
        r.session.set("fte_exchange_dir", exdir)
        r.session.set("ha_plane", True)
        r.session.set("cluster_obs", True)
        r.ha_lease = lease
        return r

    old_env = {
        k: os.environ.get(k)
        for k in ("TRINO_TPU_CLUSTER_OBS", "TRINO_TPU_QUERY_PROFILE_DIR")
    }
    os.environ["TRINO_TPU_CLUSTER_OBS"] = "1"
    os.environ["TRINO_TPU_QUERY_PROFILE_DIR"] = profdir
    RECORDER.clear()
    RECORDER.enable()
    try:
        lease_a = LeaderLease(os.path.join(tmp, "ha"), "coord-a", ttl=0.2)
        lease_b = LeaderLease(os.path.join(tmp, "ha"), "coord-b", ttl=0.2)
        if not lease_a.acquire() or lease_a.epoch != 1:
            problems.append("primary coordinator failed to take epoch 1")
        with ChaosInjector() as chaos:
            chaos.arm("coordinator_crash", times=1, match="_post")
            try:
                make_runner(lease_a).execute(sql)
                problems.append("coordinator_crash chaos did not fire")
            except CoordinatorCrashError:
                pass
        time.sleep(0.25)  # the dead leader's lease lapses
        if not lease_b.acquire() or lease_b.epoch != 2:
            problems.append("standby coordinator failed to take epoch 2")

        orphans = orphaned_journals(exdir)
        if len(orphans) != 1:
            problems.append(f"expected 1 orphaned journal, got {len(orphans)}")
            return problems
        rb = make_runner(lease_b)
        t0 = time.monotonic()
        result = resume_fte_query(rb, orphans[0])
        wall = time.monotonic() - t0
        if not result.rows or not result.rows[0][0]:
            problems.append(f"resumed query returned {result.rows!r}")

        # ---------------- cross-node trace assembly (real wire path). The
        # journal copy rides the result's stats bundle (the on-disk journal
        # is cleaned up with the query's exchange directory on success).
        journal_records = (result.query_stats or {}).get("journal") or []
        if not journal_records:
            problems.append("resumed result carries no journal copy")
            journal_records, _ = DispatchJournal.read(orphans[0])
        qid = next(
            (str(r.get("query_id")) for r in journal_records
             if r.get("kind") == "begin"), "",
        )
        if not qid:
            problems.append("journal has no begin record with a query id")
        epochs_seen = {r.get("epoch") for r in journal_records}
        if not {1, 2} <= epochs_seen:
            problems.append(
                f"journal records span epochs {sorted(epochs_seen)}, "
                "expected both 1 and 2"
            )
        segments = {"coordinator": clusterobs.local_segment([qid])}
        clock = ClockSync()
        cm = ClusterMetrics()
        for i, w in enumerate(workers):
            node = f"worker-{i}"
            rel = "/v1/flightrecorder"
            req = urllib.request.Request(
                f"http://{w.address}{rel}?query_id={qid}", method="GET"
            )
            req.add_header(SIGNATURE_HEADER, sign(secret, "GET", rel))
            with urllib.request.urlopen(req, timeout=10) as resp:
                payload = _json.loads(resp.read())
            segments[node] = payload.get("trace") or {}
            # announcement riders feed clock sync + the federated fold
            # (the same payload shape a PUT /v1/announcement carries)
            body = w.announcement_body()
            if not isinstance(body.get("metrics"), list):
                problems.append(f"{node} announcement missing metrics rider")
            if clock.observe_announcement(node, body.get("clock")) is None:
                problems.append(f"{node} announcement missing clock rider")
            cm.ingest(node, body.get("metrics") or [])
        trace = assemble_cluster_trace(
            segments, offsets=clock.offsets(), journal_records=journal_records
        )
        problems += validate_chrome_trace(trace)  # paired B/E + monotonic
        events = trace.get("traceEvents", [])
        lanes = {
            e["pid"]: (e.get("args") or {}).get("name")
            for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        worker_pids = {p for p, n in lanes.items()
                       if str(n).startswith("worker-")}
        task_pids = {
            e["pid"] for e in events
            if e.get("name") == "task" and e.get("ph") == "B"
        }
        if len(worker_pids & task_pids) < 2:
            problems.append(
                f"merged trace has {len(worker_pids & task_pids)} worker "
                "lanes with task spans, need >= 2"
            )
        epochs = {
            (e.get("args") or {}).get("epoch")
            for e in events
            if e.get("name") == "task_attempt" and e.get("ph") == "B"
        }
        epochs.discard(None)
        if not {1, 2} <= epochs:
            problems.append(
                f"merged trace missing spans from both leader epochs: "
                f"{sorted(epochs)}"
            )
        if not any(e.get("cat") == "journal" for e in events):
            problems.append("no dispatch-journal markers in the merged trace")

        # ---------------- federated exposition: HELP lint + node labels
        text = cm.render(local_registry=REGISTRY)
        fams = [ln.split()[2] for ln in text.splitlines()
                if ln.startswith("# TYPE ")]
        helped = {ln.split()[2] for ln in text.splitlines()
                  if ln.startswith("# HELP ")}
        unhelped = [f for f in fams if f not in helped]
        if unhelped:
            problems.append(
                f"cluster exposition families missing HELP: {unhelped[:5]}"
            )
        for node in ("worker-0", "worker-1", "coordinator"):
            if f'node="{node}"' not in text:
                problems.append(f"cluster exposition missing node label {node}")
        problems += _registry_help_problems()

        # ---------------- persisted profile: schema + sums-to-wall
        qs = result.query_stats or {}
        if not qs.get("stages"):
            problems.append("resumed result carries no stage breakdown")
        store = clusterobs.profile_store()
        if store is None:
            problems.append("profile store not configured under env gate")
            return problems
        store.write(build_profile(
            qid, sql, state="FINISHED", wall_secs=wall, query_stats=qs,
        ))
        profile = store.read(qid)
        if profile is None:
            problems.append("profile bundle not readable after write")
            return problems
        required_keys = {
            "version", "queryId", "query", "state", "wallSecs", "stages",
            "phases", "times", "counts", "operators", "planNodes", "cache",
            "retries", "blacklist", "diagnosis",
        }
        missing = required_keys - set(profile)
        if missing:
            problems.append(f"profile schema missing keys: {sorted(missing)}")
        breakdown = profile_breakdown_secs(profile)
        if wall > 0 and abs(breakdown - wall) > 0.05 * wall:
            problems.append(
                f"profile stage breakdown {breakdown:.4f}s vs wall "
                f"{wall:.4f}s drifts past 5%"
            )
        if not profile.get("diagnosis"):
            problems.append("profile missing the dominant-cost diagnosis")
        if not profile.get("retries"):
            problems.append("profile missing the retry/attempt history")
    finally:
        RECORDER.disable()
        RECORDER.clear()
        for w in workers:
            w.stop()
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return problems


def run_vector_serving_smoke(rows: int = 96, dim: int = 8) -> List[str]:
    """Vector-serving-plane smoke (device_scheduler vector lanes + the IVF
    ANN tier): a burst of concurrent vector top-k statements differing only
    in their query constant, with ``vector_query_batching`` on, must coalesce
    into stacked launches (strictly fewer device programs than the serial
    replay, results bit-identical per query) and leave PAIRED
    ``vector_batch_launch`` spans carrying lanes/rows/dim/k; an
    ``ann_mode=approx`` probe over an IVF index must leave a PAIRED
    ``ann_probe`` span, advance the pruned-splits counter, and deposit an
    on-schema ``system.runtime.ann_recall`` row; the three serving counters
    must pass the HELP lint.

    Returns a list of problems; [] means the smoke check passed.
    """
    import tempfile
    import threading

    import numpy as np

    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.connectors.vector_index import IvfVectorConnector
    from trino_tpu.fs import FileSystemManager, LocalFileSystem
    from trino_tpu.ops import tensor as T
    from trino_tpu.runtime.device_scheduler import SCHEDULER, program_launches
    from trino_tpu.runtime.local import LocalQueryRunner
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace
    from trino_tpu.spi.connector import ColumnMetadata, SchemaTableName
    from trino_tpu.spi.types import BIGINT, vector_type

    problems: List[str] = []
    runner = LocalQueryRunner.tpch(scale=0.001)
    runner.register_catalog("memory", MemoryConnector())
    runner.execute(
        f"CREATE TABLE memory.default.serving_smoke (id bigint, v vector({dim}))"
    )
    values = ", ".join(
        "({}, ARRAY[{}])".format(
            i, ", ".join(f"{((i * 7 + j * 3) % 11) / 10.0}" for j in range(dim))
        )
        for i in range(rows)
    )
    runner.execute(f"INSERT INTO memory.default.serving_smoke VALUES {values}")

    def sql_for(qi: int) -> str:
        q = ", ".join(
            f"{((qi * 5 + j * 2) % 9) / 8.0 + 0.125}" for j in range(dim)
        )
        return (
            "SELECT id FROM memory.default.serving_smoke "
            f"ORDER BY cosine_similarity(v, ARRAY[{q}]) DESC, id LIMIT 5"
        )

    lanes = 4
    runner.session.set("tensor_plane", True)
    runner.session.set("vector_topk_fusion", True)
    try:
        serial = []
        n0 = program_launches()
        for i in range(lanes):
            serial.append(runner.execute(sql_for(i)).rows)
        serial_launches = program_launches() - n0

        runner.session.set("device_batching", True)
        runner.session.set("vector_query_batching", True)
        runner.session.set("batch_admit_window_ms", 25.0)
        results: List[Optional[list]] = [None] * lanes
        errors: List[BaseException] = []
        burst_launches = 0
        # a 1-core box can stagger the burst so badly nothing overlaps; the
        # smoke checks the PLANE's artifacts, not this host's scheduler, so
        # retry the burst until a stacked launch engaged (bounded attempts)
        for _ in range(3):
            SCHEDULER.reset_stats()
            RECORDER.clear()
            RECORDER.enable()
            try:
                results = [None] * lanes
                errors = []
                n0 = program_launches()

                def go(i: int) -> None:
                    try:
                        results[i] = runner.execute(sql_for(i)).rows
                    except BaseException as e:  # noqa: BLE001 — reported below
                        errors.append(e)

                threads = [
                    threading.Thread(
                        target=go, args=(i,), name=f"smoke-lane-{i}"
                    )
                    for i in range(lanes)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                burst_launches = program_launches() - n0
            finally:
                RECORDER.disable()
            if errors or SCHEDULER.vector_batched_launches >= 1:
                break
        if errors:
            problems.append(f"batched vector burst raised: {errors[:2]}")
        for i in range(lanes):
            if results[i] is not None and results[i] != serial[i]:
                problems.append(
                    f"batched lane {i} not bit-identical to its serial run"
                )
                break
        if SCHEDULER.vector_batched_launches < 1:
            problems.append("burst packed no stacked vector launch")
        elif not burst_launches < serial_launches:
            problems.append(
                f"batched burst did not dispatch strictly fewer device "
                f"programs ({burst_launches} vs serial {serial_launches})"
            )
        trace = RECORDER.chrome_trace()
        RECORDER.clear()
        problems += validate_chrome_trace(trace)
        events = trace.get("traceEvents", [])
        b = sum(1 for e in events
                if e.get("name") == "vector_batch_launch" and e.get("ph") == "B")
        e_ = sum(1 for e in events
                 if e.get("name") == "vector_batch_launch" and e.get("ph") == "E")
        if not b:
            problems.append("no vector_batch_launch span in the trace")
        elif b != e_:
            problems.append(
                f"vector_batch_launch spans unpaired: {b} B vs {e_} E"
            )
        stacked = [
            (e.get("args") or {})
            for e in events
            if e.get("name") == "vector_batch_launch" and e.get("ph") == "E"
        ]
        if not any(
            a.get("lanes") and a.get("rows") and a.get("dim") == dim
            and a.get("k") == 5
            for a in stacked
        ):
            problems.append(
                f"vector_batch_launch E-args missing lanes/rows/dim/k: "
                f"{stacked[:3]}"
            )

        # ------------------------------------------------ ANN index tier
        tmp = tempfile.mkdtemp(prefix="ivf_smoke_")
        fsm = FileSystemManager()
        fsm.register("local", lambda: LocalFileSystem(tmp))
        ivf = IvfVectorConnector(fsm, "local://ivf")
        rng = np.random.RandomState(11)
        idx_rows = [
            (i, np.round(rng.uniform(-1, 1, size=dim), 6).tolist())
            for i in range(rows)
        ]
        ivf.build_index(
            SchemaTableName("default", "emb"),
            [ColumnMetadata("id", BIGINT), ColumnMetadata("v", vector_type(dim))],
            idx_rows,
            "v",
            n_clusters=6,
        )
        runner.register_catalog("vec", ivf)
        ann_sql = (
            "SELECT id FROM vec.default.emb "
            "ORDER BY cosine_similarity(v, ARRAY["
            + ", ".join(f"{(j % 5) / 4.0 - 0.4}" for j in range(dim))
            + "]) DESC, id LIMIT 5"
        )
        runner.session.set("device_batching", False)
        runner.session.set("vector_query_batching", False)
        exact = runner.execute(ann_sql).rows
        runner.session.set("ann_mode", "approx(nprobe=2)")
        runner.session.set("ann_recall_sample_rate", 1.0)
        p0 = T.ann_pruned_splits()
        s0 = T.ann_recall_samples()
        RECORDER.clear()
        RECORDER.enable()
        try:
            runner.execute(ann_sql)
        finally:
            RECORDER.disable()
        if not T.ann_pruned_splits() > p0:
            problems.append("ann probe pruned no splits")
        if not T.ann_recall_samples() > s0:
            problems.append("ann recall oracle drew no sample")
        trace = RECORDER.chrome_trace()
        RECORDER.clear()
        problems += validate_chrome_trace(trace)
        events = trace.get("traceEvents", [])
        b = sum(1 for e in events
                if e.get("name") == "ann_probe" and e.get("ph") == "B")
        e_ = sum(1 for e in events
                 if e.get("name") == "ann_probe" and e.get("ph") == "E")
        if not b:
            problems.append("no ann_probe span in the trace")
        elif b != e_:
            problems.append(f"ann_probe spans unpaired: {b} B vs {e_} E")
        recall_rows = T.ann_recall_rows()
        if not recall_rows:
            problems.append("system.runtime.ann_recall ring is empty")
        else:
            r = recall_rows[-1]
            ok = (
                len(r) == 6
                and isinstance(r[0], str)
                and all(isinstance(x, int) for x in (r[1], r[2], r[4], r[5]))
                and isinstance(r[3], float)
                and 0.0 <= r[3] <= 1.0
                and r[4] <= r[5]
            )
            if not ok:
                problems.append(f"ann_recall row off-schema: {r!r}")
        runner.session.set("ann_mode", f"approx(nprobe=6)")
        full = runner.execute(ann_sql).rows
        if full != exact:
            problems.append("nprobe=n_clusters not bit-identical to exact")
    finally:
        for knob in (
            "tensor_plane", "vector_topk_fusion", "device_batching",
            "vector_query_batching", "batch_admit_window_ms", "ann_mode",
            "ann_recall_sample_rate",
        ):
            runner.session.properties.pop(knob, None)
    problems += _registry_help_problems(required=(
        "trino_tpu_vector_batched_queries_total",
        "trino_tpu_ann_pruned_splits_total",
        "trino_tpu_ann_recall_samples_total",
        "trino_tpu_device_programs_total",
    ))
    return problems


def run_kernelcost_smoke(scale: float = 0.001) -> List[str]:
    """Kernel cost plane smoke (runtime/kernelcost.py): EXPLAIN ANALYZE
    VERBOSE under the flight recorder must render a per-operator roofline
    diagnosis ("[kernel: flops ... -> memory-bound ...]"), leave a valid
    Perfetto export carrying ``hbm_watermark`` counter-track samples and
    paired ``kernel_cost`` spans, deposit on-schema
    ``system.runtime.kernel_costs`` rows, fold federated rows ingested
    under a worker id into the same table, and the trace validator must
    flag a counter event with a non-numeric sample (mutation check on the
    counter-track conformance rule itself).

    Returns a list of problems; [] means the smoke check passed.
    """
    from trino_tpu.runtime import kernelcost
    from trino_tpu.runtime.local import LocalQueryRunner
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

    problems: List[str] = []
    # hermetic against a deployment cap store: a persisted .kernelcost
    # sibling file would satisfy attribution reads without lowering, and
    # the paired kernel_cost spans this smoke asserts on would never emit
    prev_store = os.environ.pop("TRINO_TPU_CAP_STORE", None)
    kernelcost.clear_ledger()
    kernelcost.clear_memory()  # force fresh lowers: kernel_cost spans emit
    runner = LocalQueryRunner.tpch(scale=scale)
    RECORDER.clear()
    RECORDER.enable()
    try:
        res = runner.execute(
            "EXPLAIN ANALYZE VERBOSE "
            "SELECT l_returnflag, sum(l_extendedprice) FROM lineitem "
            "WHERE l_quantity < 24 GROUP BY l_returnflag"
        )
        text = "\n".join(str(r[0]) for r in res.rows)
        trace = RECORDER.chrome_trace()
    finally:
        RECORDER.disable()
        if prev_store is not None:
            os.environ["TRINO_TPU_CAP_STORE"] = prev_store

    if "[kernel:" not in text:
        problems.append("EXPLAIN ANALYZE VERBOSE rendered no kernel cost line")
    if "-bound" not in text:
        problems.append("no roofline classification in EXPLAIN output")
    problems += [f"trace: {p}" for p in validate_chrome_trace(trace)]
    events = trace.get("traceEvents", [])
    counters = [e for e in events if e.get("ph") == "C"]
    if not counters:
        problems.append("no counter-track events recorded")
    elif not any(e.get("name") == "hbm_watermark" for e in counters):
        problems.append("no hbm_watermark counter track")
    span_names = {e.get("name") for e in events if e.get("ph") == "B"}
    if "kernel_cost" not in span_names:
        problems.append("no paired kernel_cost spans recorded")

    # mutation check: the validator must catch a non-numeric counter sample
    if events:
        data = [e for e in events if e.get("ph") != "M"]
        if data:
            donor = data[-1]
            bad_ev = {
                "name": "hbm_watermark", "cat": "kernelcost", "ph": "C",
                "ts": max(e["ts"] for e in data) + 1,
                "pid": donor["pid"], "tid": donor["tid"],
                "args": {"hbm_bytes": "not-a-number"},
            }
            mutated = {"traceEvents": events + [bad_ev]}
            if not validate_chrome_trace(mutated):
                problems.append(
                    "validator accepted a non-numeric counter sample"
                )

    rows = runner.execute(
        "SELECT node, plan_node, flops, classification, status "
        "FROM system.runtime.kernel_costs"
    ).rows
    if not rows:
        problems.append("system.runtime.kernel_costs returned no rows")
    bad = [
        r for r in rows
        if not isinstance(r[4], str)
        or (r[2] is not None and not isinstance(r[2], float))
    ]
    if bad:
        problems.append(f"kernel_costs rows off-schema: {bad[:3]}")

    # federated fold: rows ingested under a worker id surface with its node
    kernelcost.ingest_federated("smoke-worker", kernelcost.announcement_rows())
    fed = runner.execute(
        "SELECT node FROM system.runtime.kernel_costs"
    ).rows
    if not any(r[0] == "smoke-worker" for r in fed):
        problems.append("federated kernel-cost rows missing from the table")
    problems += _registry_help_problems()
    return problems


def run_hostprof_smoke(scale: float = 0.001) -> List[str]:
    """Host-path observability plane smoke (runtime/hostprof.py): the
    ``host_profile`` session property must scope the sampling profiler to
    the statement (refcounted, off afterwards), the sampler must capture
    collapsed stacks keyed by thread NAME, the speedscope export must pass
    its schema validator, the QueryManager's protocol phases (proto_queue,
    proto_admit) and the runner's `execution` must be whole X events of a
    valid Perfetto trace (the tracer's finished spans), the
    ``system.runtime.host_profile`` table must serve on-schema rows, the
    ``trino_tpu_host_threads{state=}`` gauges must export, and the
    GIL-contention probe must produce a numeric jitter summary.

    Returns a list of problems; [] means the smoke check passed.
    """
    from trino_tpu.runtime.hostprof import (
        PROBE,
        PROFILER,
        update_thread_gauges,
        validate_speedscope,
    )
    from trino_tpu.runtime.local import LocalQueryRunner
    from trino_tpu.runtime.metrics import REGISTRY
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace
    from trino_tpu.runtime.query_manager import QueryManager

    problems: List[str] = []
    runner = LocalQueryRunner.tpch(scale=scale)
    PROFILER.clear()
    RECORDER.clear()
    RECORDER.enable()
    probe = PROBE
    probe.clear()
    probe.start()
    try:
        runner.session.set("host_profile", True)
        qm = QueryManager(runner.execute)
        q = qm.submit(
            "SELECT count(*), sum(l_quantity) FROM lineitem "
            "WHERE l_quantity < 24"
        )
        q.wait_done(timeout=60.0)
        # a second profiled statement keeps the sampler up long enough for
        # ticks at the default 19ms interval even on a warm plan
        runner.execute("SELECT count(*) FROM orders")
        trace = RECORDER.chrome_trace()
    finally:
        runner.session.set("host_profile", False)
        RECORDER.disable()
        probe.stop()
        PROFILER.join()

    if PROFILER.enabled:
        problems.append("profiler still enabled after the session released it")
    if PROFILER.tick_count == 0:
        problems.append("sampler took no ticks during profiled statements")
    collapsed = PROFILER.collapsed()
    if not collapsed:
        problems.append("no collapsed stacks captured")
    if any(not key.split(";")[0] for key in collapsed):
        problems.append("collapsed stack with an empty thread name")
    doc = PROFILER.speedscope()
    problems += [f"speedscope: {p}" for p in validate_speedscope(doc)]
    problems += [f"trace: {p}" for p in validate_chrome_trace(trace)]
    events = trace.get("traceEvents", [])
    whole = {e.get("name") for e in events if e.get("ph") == "X"}
    for want in ("proto_queue", "proto_admit", "execution"):
        if want not in whole:
            problems.append(f"no finished {want} span recorded")

    rows = runner.execute(
        "SELECT thread, stack, samples, share "
        "FROM system.runtime.host_profile"
    ).rows
    if not rows:
        problems.append("system.runtime.host_profile returned no rows")
    bad = [
        r for r in rows
        if not isinstance(r[0], str) or not isinstance(r[1], str)
        or not isinstance(r[2], int) or not isinstance(r[3], float)
    ]
    if bad:
        problems.append(f"host_profile rows off-schema: {bad[:3]}")

    update_thread_gauges()
    exposition = REGISTRY.render()
    for state in ("runnable", "blocked"):
        if f'trino_tpu_host_threads{{state="{state}"}}' not in exposition:
            problems.append(f"host thread gauge state={state} not exported")

    summary = probe.summary()
    if not summary.get("samples"):
        problems.append("contention probe recorded no sleep-jitter samples")
    elif not all(
        isinstance(summary.get(k), float)
        for k in ("p50_secs", "p99_secs", "max_secs")
    ):
        problems.append(f"contention probe summary off-schema: {summary}")
    problems += _registry_help_problems()
    return problems


def run_fleet_smoke(scale: float = 0.001) -> List[str]:
    """Active-active coordinator fleet smoke (runtime/fleet.py): a THREE
    coordinator fleet on one membership dir must converge, a non-owner must
    307 a statement to its owner (and the client must follow it to a
    correct result), killing an owner mid-run must lapse its heartbeat and
    reassign ONLY its hash range (survivor-owned keys keep their owner), a
    follower must serve a status-board read for the dead owner's query
    DURING the failover window, the dead owner's users must be served by a
    survivor afterwards, proto_route spans must be whole in a valid Perfetto
    trace with a fleet_reassign span for the departure, and the fleet
    counters must pass the shared HELP lint.

    Returns a list of problems; [] means the smoke check passed.
    """
    import tempfile
    import time
    import urllib.error
    import urllib.request

    from trino_tpu.client.client import StatementClient
    from trino_tpu.runtime.fleet import partition_key
    from trino_tpu.runtime.local import LocalQueryRunner
    from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace
    from trino_tpu.server.coordinator import CoordinatorServer

    problems: List[str] = []
    fleet_dir = tempfile.mkdtemp(prefix="fleet_smoke_")
    saved = {
        k: os.environ.get(k)
        for k in ("TRINO_TPU_FLEET_DIR", "TRINO_TPU_FLEET_HEARTBEAT_SECS")
    }
    os.environ["TRINO_TPU_FLEET_DIR"] = fleet_dir
    os.environ["TRINO_TPU_FLEET_HEARTBEAT_SECS"] = "0.2"
    RECORDER.clear()
    RECORDER.enable()
    coords: List[CoordinatorServer] = []
    trace = {}
    try:
        for nid in ("n1", "n2", "n3"):
            coords.append(
                CoordinatorServer(
                    LocalQueryRunner.tpch(scale=scale), node_id=nid
                ).start()
            )
        c1, _c2, c3 = coords
        deadline = time.time() + 5
        while time.time() < deadline:
            if len(c1.fleet.live_members(now=time.time())) == 3:
                break
            time.sleep(0.05)
        live = sorted(c1.fleet.live_members(now=time.time()))
        if live != ["n1", "n2", "n3"]:
            problems.append(f"fleet membership never converged: {live}")

        # one user per owner (the ring is deterministic, so scan)
        users = {}
        for i in range(96):
            user = f"user{i:02d}"
            owner = c1.fleet.owner_of(partition_key(user, ""))["node_id"]
            users.setdefault(owner, user)
            if len(users) == 3:
                break
        if len(users) != 3:
            problems.append(f"ring left a member without keys: {users}")
            return problems

        # partitioned admission: a statement for n3's user POSTed at n1
        # must 307 to n3 at the raw protocol level...
        req = urllib.request.Request(
            f"http://{c1.address}/v1/statement",
            data=b"SELECT count(*) FROM nation", method="POST",
            headers={"X-Trino-User": users["n3"]},
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            problems.append("non-owner served an owned statement (no 307)")
        except urllib.error.HTTPError as e:
            e.read()
            if e.code != 307:
                problems.append(f"non-owner answered {e.code}, wanted 307")
            elif e.headers.get("X-Trino-Fleet-Owner") != "n3":
                problems.append(
                    f"redirect named owner "
                    f"{e.headers.get('X-Trino-Fleet-Owner')}, wanted n3"
                )
        # ...and the client must follow it transparently
        cl = StatementClient(f"http://{c1.address}", user=users["n3"])
        res = cl.execute("SELECT count(*) FROM nation")
        if res.rows != [[25]]:
            problems.append(f"redirected statement wrong: {res.rows}")

        # pre-kill ownership snapshot for the reassignment check
        keys = [f"session:smoke{i:03d}@x" for i in range(120)]
        before = {k: c1.fleet.owner_of(k)["node_id"] for k in keys}
        if "n3" not in set(before.values()):
            keys.append(partition_key(users["n3"], ""))
            before[keys[-1]] = "n3"

        # mid-run owner kill: crash (no deregister — the membership record
        # must LAPSE via the heartbeat TTL, not be cleaned up)
        c3.stop(crash=True)
        deadline = time.time() + 5
        while time.time() < deadline:
            if "n3" not in c1.fleet.live_members(now=time.time()):
                break
            time.sleep(0.05)
        if "n3" in c1.fleet.live_members(now=time.time()):
            problems.append("crashed owner never lapsed from membership")

        # follower status read DURING failover: the dead owner's query
        # answered from a surviving coordinator's status board
        board = c1._fleet_board_status(res.query_id)
        if board is None:
            problems.append(
                "follower could not serve the dead owner's query status"
            )
        elif board.get("fleet_owner") != "n3":
            problems.append(f"status board off-owner: {board}")

        # the dead member's hash range reassigns; everyone else stays put.
        # owner_of reads the quarter-heartbeat membership cache, so poll
        # until the routing view converges (within ~a heartbeat) before
        # judging the final assignment.
        deadline = time.time() + 5
        while time.time() < deadline:
            after = {k: c1.fleet.owner_of(k)["node_id"] for k in keys}
            if "n3" not in set(after.values()):
                break
            time.sleep(0.05)
        moved_wrong = [
            k for k in keys
            if before[k] != "n3" and after[k] != before[k]
        ]
        still_dead = [k for k in keys if before[k] == "n3" and after[k] == "n3"]
        if moved_wrong:
            problems.append(
                f"survivor-owned keys moved on failover: {moved_wrong[:3]}"
            )
        if still_dead:
            problems.append(f"keys still owned by the dead member: {still_dead[:3]}")

        # the dead owner's users are now served by a survivor. The routing
        # ring is refreshed from a quarter-heartbeat cache, so a statement
        # landing inside that window can still chase a dead redirect —
        # failover clients retry, and so does the smoke.
        cl = StatementClient(f"http://{c1.address}", user=users["n3"])
        res2 = None
        deadline = time.time() + 5
        while time.time() < deadline:
            try:
                res2 = cl.execute("SELECT count(*) FROM region")
                break
            except OSError:
                time.sleep(0.1)
        if res2 is None:
            problems.append("post-failover statement never succeeded")
        elif res2.rows != [[5]]:
            problems.append(f"post-failover statement wrong: {res2.rows}")
        trace = RECORDER.chrome_trace()
    finally:
        for c in coords:
            try:
                c.stop()
            except Exception:
                pass
        RECORDER.disable()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    problems += [f"trace: {p}" for p in validate_chrome_trace(trace)]
    events = trace.get("traceEvents", [])
    begun = {e.get("name") for e in events if e.get("ph") == "B"}
    whole = {e.get("name") for e in events if e.get("ph") == "X"}
    if "proto_route" not in whole:
        problems.append("no finished proto_route span recorded")
    if "fleet_reassign" not in begun:
        problems.append("no fleet_reassign span recorded for the departure")
    problems += _registry_help_problems(
        required=(
            "trino_tpu_fleet_heartbeats_total",
            "trino_tpu_fleet_routed_total",
            "trino_tpu_fleet_follower_reads_total",
            "trino_tpu_fleet_reassigns_total",
            "trino_tpu_protocol_queue_depth",
        )
    )
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    ooc = bool(argv and "--ooc" in argv)
    problems = run_smoke(ooc=ooc)
    problems += [f"[system] {p}" for p in run_system_smoke()]
    problems += [f"[exchange] {p}" for p in run_exchange_smoke()]
    problems += [f"[fte] {p}" for p in run_fte_smoke()]
    problems += [f"[memory] {p}" for p in run_memory_smoke()]
    problems += [f"[stats] {p}" for p in run_stats_smoke()]
    problems += [f"[cache] {p}" for p in run_cache_smoke()]
    problems += [f"[batching] {p}" for p in run_batching_smoke()]
    problems += [f"[tensor] {p}" for p in run_tensor_smoke()]
    problems += [f"[vector-serving] {p}" for p in run_vector_serving_smoke()]
    problems += [f"[ha] {p}" for p in run_ha_smoke()]
    problems += [f"[objectstore] {p}" for p in run_objectstore_smoke()]
    problems += [f"[cluster] {p}" for p in run_cluster_smoke()]
    problems += [f"[kernelcost] {p}" for p in run_kernelcost_smoke()]
    problems += [f"[hostprof] {p}" for p in run_hostprof_smoke()]
    problems += [f"[fleet] {p}" for p in run_fleet_smoke()]
    if problems:
        for p in problems:
            print(f"SMOKE FAIL: {p}", file=sys.stderr)
        return 1
    print("observability smoke check passed", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
