"""Layer: executor. What the window's statements spend in the aggregations
with a DISTINCT argument: the `op:AggregationNode` spans that state
`distinct` (the dedup on the group keys and the column, then the aggregation
over it), as a share of the `statement` spans' time. A span counts with its
`sync:` and `compact` children and without its `op:` children, as in
`outer_join_pct`. None, never 0, on a program whose spans state no
`distinct` (every commit before PR 40). Beside it, under `notes`, each
statement's `distinct_rows_in` (rows into the dedup) and `distinct_groups`
(the dedup's groups)."""

from benchmark.layer_metrics import _statements as st
from benchmark.layer_metrics.outer_join_pct import own_seconds

AGGREGATION = "op:AggregationNode"


def distinct(tree) -> list:
    return [s for s in tree if s["name"] == AGGREGATION and s["endNs"] is not None
            and "distinct" in s["attributes"]]


def of(trees):
    if not any(distinct(t) for t in trees):
        return None
    return st.share_of_statements(trees, lambda t: sum(own_seconds(t, s) for s in distinct(t)))


def by_statement(trees, records) -> dict:
    """{statement label: [[distinct_rows_in, distinct_groups], ...]} of the
    statements that ran a distinct aggregation, the trees and the records
    paired in the order they began."""
    out = {}
    pairs = zip(sorted(trees, key=lambda t: t[0]["startNs"]), sorted(records, key=lambda r: r.start))
    for tree, record in pairs:
        spans = [s["attributes"] for s in distinct(tree)]
        if spans:
            out[record.statement.label] = [[a.get("distinct_rows_in"), a.get("distinct_groups")] for a in spans]
    return out


def read(run):
    trees = st.window_trees(run)
    if not trees:
        return None
    share = of(trees)
    if share is not None:
        run.notes["distinct_by_statement"] = by_statement(trees, run.records)
    return share
