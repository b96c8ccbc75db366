#!/usr/bin/env python
"""TPC-DS full-corpus conformance harness: parse / plan / execute / VERIFY
all 99 canonical queries (103 files with a/b variants), each in its own
child process with a hard timeout.

ref: the reference's result-verified conformance bar (H2QueryRunner +
QueryAssertions, SURVEY.md §4); our second engine is the sqlite oracle
(tests/tpcds_oracle.py) over identical generated data. ROLLUP/GROUPING
queries are outside sqlite's dialect and report "oracle-unsupported"
(their GROUPING machinery is result-checked by the pandas families in
tests/test_tpcds.py).

Usage:
  python tools/tpcds_conformance.py              # run all, write report
  python tools/tpcds_conformance.py --child q03  # internal per-query child
  python tools/tpcds_conformance.py --timeout 600 --scale 0.01

Writes TPCDS_CONFORMANCE.json {query: {status, rows, secs, detail}} and
prints the summary table. Statuses: verified | executed (oracle
unsupported) | mismatch | parse/plan/execute-error | timeout.
"""

import argparse
import glob
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CANON = (
    "/root/reference/testing/trino-benchmark-queries/src/main/resources/sql/trino/tpcds"
)
ROLLUP = {"q05", "q14a", "q18", "q22", "q27", "q36", "q67", "q70", "q77", "q80", "q86"}


def load_sql(name: str) -> str:
    sql = open(os.path.join(CANON, f"{name}.sql")).read().strip().rstrip(";")
    sql = sql.replace('"${database}"."${schema}".', "")
    return sql.replace("${database}.${schema}.", "")


def child(name: str, scale: float) -> None:
    """Runs in a subprocess: prints ONE json line with the result."""
    sys.path.insert(0, REPO)  # script lives in tools/: repo root isn't on path
    sys.setrecursionlimit(20000)  # q08-class giant IN-lists recurse in the parser
    out = {"query": name}
    t_start = time.time()
    try:
        import jax

        # conformance is a CPU run; the package import places the compile cache
        jax.config.update("jax_platforms", "cpu")

        sql = load_sql(name)
        from trino_tpu.sql import parse_statement

        parse_statement(sql)
        out["parse"] = True

        from trino_tpu.connectors import tpcds as ds
        from trino_tpu.metadata import Session
        from trino_tpu.runtime import LocalQueryRunner

        schema = "sf" + f"{scale:g}".replace(".", "_")
        runner = LocalQueryRunner(Session(catalog="tpcds", schema=schema))
        runner.register_catalog("tpcds", ds.TpcdsConnector(scale=scale))
        runner.plan_sql(sql)
        out["plan"] = True

        res = runner.execute(sql)
        out["execute"] = True
        out["rows"] = len(res.rows)

        if name in ROLLUP:
            out["status"] = "executed"
            out["detail"] = "oracle-unsupported (ROLLUP/GROUPING)"
        else:
            sys.path.insert(0, os.path.join(REPO, "tests"))
            from tpcds_oracle import oracle_rows, rows_match, tpcds_sqlite

            con = tpcds_sqlite(scale)
            expected = oracle_rows(con, sql)
            diff = rows_match([tuple(r) for r in res.rows], expected, ordered=True)
            if diff is None:
                out["status"] = "verified"
            else:
                # ORDER BY ties differ legitimately across engines; retry
                # as a multiset before calling it a mismatch
                diff_unordered = rows_match(
                    [tuple(r) for r in res.rows], expected, ordered=False
                )
                if diff_unordered is None:
                    out["status"] = "verified"
                    out["detail"] = "tie-order differs (multiset equal)"
                else:
                    out["status"] = "mismatch"
                    out["detail"] = diff_unordered
    except Exception as e:  # noqa: BLE001 — every failure becomes a record
        if out.get("execute"):
            stage = "oracle"  # the ENGINE executed; the sqlite side failed
        elif out.get("plan"):
            stage = "execute"
        elif out.get("parse"):
            stage = "plan"
        else:
            stage = "parse"
        out["status"] = f"{stage}-error"
        out["detail"] = f"{type(e).__name__}: {str(e)[:200]}"
    out["secs"] = round(time.time() - t_start, 1)
    print(json.dumps(out), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", help="internal: run one query and exit")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--timeout", type=int, default=900)
    ap.add_argument("--only", help="comma-separated query names")
    ap.add_argument("--out", default=os.path.join(REPO, "TPCDS_CONFORMANCE.json"))
    args = ap.parse_args()

    if args.child:
        child(args.child, args.scale)
        return

    names = sorted(
        os.path.basename(f)[:-4] for f in glob.glob(os.path.join(CANON, "q*.sql"))
    )
    if args.only:
        names = [n for n in names if n in set(args.only.split(","))]

    results = {}
    # resume support: a previous partial run's records are kept
    if os.path.exists(args.out):
        try:
            results = json.load(open(args.out))
        except ValueError:
            results = {}
    for i, name in enumerate(names):
        if name in results and results[name].get("status") not in (None, "timeout"):
            continue
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--child", name, "--scale", str(args.scale),
        ]
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=args.timeout,
                cwd=REPO,
            )
            line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
            try:
                results[name] = json.loads(line)
            except ValueError:
                results[name] = {
                    "query": name,
                    "status": "execute-error",
                    "detail": (proc.stderr or "no output")[-300:],
                }
        except subprocess.TimeoutExpired:
            results[name] = {
                "query": name, "status": "timeout", "secs": args.timeout,
            }
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        r = results[name]
        print(
            f"[{i+1}/{len(names)}] {name}: {r.get('status')}"
            f" ({r.get('secs', '?')}s) {r.get('detail', '')}",
            flush=True,
        )

    counts = {}
    for r in results.values():
        counts[r.get("status", "?")] = counts.get(r.get("status", "?"), 0) + 1
    total = len(results)
    print("\n== TPC-DS conformance summary ==")
    print(f"files: {total}")
    for k in sorted(counts):
        print(f"  {k}: {counts[k]}")
    verified = counts.get("verified", 0)
    executed = verified + counts.get("executed", 0)
    print(f"executed (incl. verified): {executed}; verified: {verified}")


if __name__ == "__main__":
    main()
