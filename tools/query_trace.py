#!/usr/bin/env python
"""Run one SQL query with the pipeline flight recorder on; export the trace.

The observability plane's export tool: enables the process flight recorder
(runtime/observability.RECORDER), runs the query through the embedded engine
(in-core, or the out-of-core tier with --ooc), and writes the recorded
pipeline events — operator spans, bucket units, prefetch issue/complete,
host->device transfers, XLA compiles, spill writes/reads, exchange
push/pull — as Chrome trace-event JSON loadable in ui.perfetto.dev or
chrome://tracing. A stats summary (device/host/compile attribution +
counters) prints to stderr.

    python tools/query_trace.py --sql "SELECT ..." --scale 0.01 --out t.json
    python tools/query_trace.py --q q3 --ooc --validate

Exports are DETERMINISTIC: tids derive from sorted (thread-name, first
activity) instead of thread-arrival order (runtime/clusterobs.
canonicalize_trace), so repeated exports of the same ring are byte-
identical.

Cluster mode (the cluster observability plane) pulls the MERGED cross-node
timeline from a coordinator — every node's flight-recorder segment,
skew-aligned by announced clock offsets, one process lane per node:

    python tools/query_trace.py --cluster http://coord:8080 \\
        --query-id q_ab12... --out cluster.json --validate

The same module backs the observability smoke check (tools/obs_smoke.py):
``run_query_trace`` returns the trace dict + stats snapshot, and
``validate`` applies the minimal schema the smoke check enforces.

Host-path plane (runtime/hostprof.py): ``--speedscope host.json`` runs the
wall-clock sampling profiler alongside the flight recorder and writes the
collapsed host stacks as a speedscope document (drop on speedscope.app),
schema-checked by hostprof.validate_speedscope when --validate is on:

    python tools/query_trace.py --q q6 --speedscope host.json --validate
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional, Tuple

# runnable from anywhere: the repo root (trino_tpu's parent) joins sys.path
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# canned TPC-H queries for --q (kept tiny)
QUERIES = {
    "q6": """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '1994-01-01'
  AND l_shipdate < DATE '1994-01-01' + INTERVAL '1' YEAR
  AND l_discount BETWEEN 0.06 - 0.01 AND 0.06 + 0.01
  AND l_quantity < 24
""",
    "q3": """
SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM customer, orders, lineitem
WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'
  AND l_shipdate > DATE '1995-03-15'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate LIMIT 10
""",
}


def run_query_trace(
    sql: str,
    scale: float = 0.01,
    ooc: bool = False,
    sync_stats: bool = True,
    runner=None,
    profile: bool = False,
) -> Tuple[dict, dict, int]:
    """Execute ``sql`` with the flight recorder on.

    Returns (chrome_trace_dict, query_stats_snapshot, result_rows). The
    recorder is cleared first so the export covers exactly this query, and
    disabled after (tool semantics; the server endpoint manages its own
    lifecycle). ``profile=True`` additionally runs the host sampling
    profiler (runtime/hostprof.PROFILER) for the query's duration — read
    ``PROFILER.speedscope()`` / ``PROFILER.collapsed()`` afterwards.
    """
    from trino_tpu.runtime import LocalQueryRunner
    from trino_tpu.runtime.observability import RECORDER

    if runner is None:
        runner = LocalQueryRunner.tpch(scale=scale)
    RECORDER.clear()
    RECORDER.enable()
    profiler = None
    if profile:
        from trino_tpu.runtime.hostprof import PROFILER as profiler

        profiler.clear()
        profiler.acquire()
    try:
        if ooc:
            from trino_tpu.runtime import observability as obs
            from trino_tpu.runtime.ooc import OutOfCoreRunner

            plan = runner.plan_sql(sql)
            runner_ooc = OutOfCoreRunner(
                plan, runner.metadata, runner.session, n_buckets=8,
                split_batch=4,
            )
            _, page = runner_ooc.execute()
            import numpy as np

            rows = int(np.asarray(page.active).sum())
            stats = runner_ooc.collector.snapshot()
        else:
            if sync_stats:
                runner.session.set("query_stats_sync", True)
            res = runner.execute(sql)
            rows = len(res.rows)
            stats = res.query_stats or {}
    finally:
        RECORDER.disable()
        if profiler is not None:
            profiler.release()
            profiler.join()
    from trino_tpu.runtime.clusterobs import canonicalize_trace

    # deterministic tids: repeated exports of the same ring byte-identical
    return canonicalize_trace(RECORDER.chrome_trace()), stats, rows


def validate(trace: dict) -> List[str]:
    """Minimal Perfetto-schema validation (see observability.
    validate_chrome_trace): monotonic per-track timestamps, paired B/E
    events, declared pids/tids. Returns problems; [] means valid."""
    from trino_tpu.runtime.observability import validate_chrome_trace

    return validate_chrome_trace(trace)


def fetch_cluster_trace(
    coordinator_url: str, query_id: str, user: str = "tools",
    timeout: float = 30.0,
) -> dict:
    """The coordinator's merged cross-node timeline for ``query_id``
    (``GET /v1/query/{id}/trace?cluster=1`` — requires the coordinator to
    run with $TRINO_TPU_CLUSTER_OBS on)."""
    import urllib.request

    url = (
        f"{coordinator_url.rstrip('/')}/v1/query/{query_id}/trace?cluster=1"
    )
    req = urllib.request.Request(url, method="GET")
    req.add_header("X-Trino-User", user)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sql", help="SQL text to run")
    ap.add_argument("--q", choices=sorted(QUERIES), help="canned TPC-H query")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--ooc", action="store_true", help="out-of-core tier")
    ap.add_argument("--out", default="query_trace.json")
    ap.add_argument("--validate", action="store_true")
    ap.add_argument(
        "--speedscope", metavar="PATH",
        help="also run the host sampling profiler (runtime/hostprof.py) "
             "and write its collapsed stacks as a speedscope document",
    )
    ap.add_argument(
        "--cluster", metavar="COORDINATOR_URL",
        help="pull the merged cross-node timeline from this coordinator "
             "instead of executing locally (needs --query-id)",
    )
    ap.add_argument("--query-id", help="query id for --cluster mode")
    args = ap.parse_args(argv)
    if args.cluster:
        if not args.query_id:
            ap.error("--cluster requires --query-id")
        if args.speedscope:
            ap.error("--speedscope profiles a local execution, not --cluster")
        trace = fetch_cluster_trace(args.cluster, args.query_id)
        stats, rows = {}, None
    else:
        sql = args.sql or (QUERIES[args.q] if args.q else None)
        if not sql:
            ap.error("one of --sql / --q is required")
        trace, stats, rows = run_query_trace(
            sql, scale=args.scale, ooc=args.ooc,
            profile=bool(args.speedscope),
        )
    if args.speedscope:
        from trino_tpu.runtime.hostprof import PROFILER, validate_speedscope

        doc = PROFILER.speedscope(name=os.path.basename(args.speedscope))
        with open(args.speedscope, "w") as f:
            json.dump(doc, f)
        print(
            f"wrote {args.speedscope}: {len(doc['profiles'])} thread "
            f"profile(s), {len(doc['shared']['frames'])} frames "
            f"({PROFILER.tick_count} sampler ticks)",
            file=sys.stderr,
        )
        if args.validate:
            problems = validate_speedscope(doc)
            if problems:
                for p in problems:
                    print(f"INVALID speedscope: {p}", file=sys.stderr)
                return 1
            print("speedscope valid", file=sys.stderr)
    with open(args.out, "w") as f:
        json.dump(trace, f)
    n_events = len(trace.get("traceEvents", []))
    lanes = trace.get("nodes")
    extra = f", node lanes: {lanes}" if lanes else f", {rows} result rows"
    print(f"wrote {args.out}: {n_events} events{extra}", file=sys.stderr)
    print(json.dumps(stats, indent=2), file=sys.stderr)
    if args.validate:
        problems = validate(trace)
        if problems:
            for p in problems:
                print(f"INVALID: {p}", file=sys.stderr)
            return 1
        print("trace valid", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
