"""Runner `local_memory_nested`: `local_memory` (one `LocalQueryRunner`, the
tables resident on the one device through the memory catalog, every session
property at its default), for the deployment whose statements are nested
sub-queries, a view and count(DISTINCT) (`configs/tpch_nested_1chip.json`).

It differs in one thing: it refuses, at once and before any table is made, a
program that cannot plan a WITH query with column aliases. Q15 writes the
specification's view `revenue0 (supplier_no, total_revenue)` so; the parent
of PR 40 (49a021e) raises "WITH column aliases not supported yet" on it when
the set-up reaches it, after the tables are loaded and the statements before
it compiled, and it answers Q11 wrongly besides (a decimal comparison that
wrapped in int64, PERF.md PR 40). Ending with code 4 lets a caller tell
"cannot run this deployment" from a run that failed. The test is for the
feature, not for a version."""

from benchmark.runners import local_memory

REFUSED = 4
PROBE = "WITH probe (a) AS (SELECT 1) SELECT a FROM probe"


def start(config: dict):
    runner = local_memory.start(config)
    try:
        runner.plan_sql(PROBE)
    except ValueError as e:  # the planner's SemanticError
        print(
            f"benchmark: this program cannot plan a WITH query's column aliases ({e}); "
            f"Q15 of {config['name']} is written so, and the cell is not run",
            flush=True,
        )
        raise SystemExit(REFUSED)
    return runner


load = local_memory.load
