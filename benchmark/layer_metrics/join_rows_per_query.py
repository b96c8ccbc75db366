"""Layer: planner. The rows the window's statements feed their joins: the
mean, over the window's `statement` roots, of `probe_rows + build_rows`
summed over their `op:JoinNode` and `op:SemiJoinNode` spans. What the planner
decides sets it (which predicates reach a join's inputs, the order of the
joins); the join programs' time follows it. None, never 0, where no such span
states `probe_rows`. Beside it, under `notes`, `join_rows_by_template` (the
mean a statement, by template) and `derived_predicates` (the window's sum of
the `optimizer` spans' attribute; left out on a program whose spans do not
state it, one older than `optimizer.derive_join_disjuncts`)."""

from benchmark.layer_metrics import _statements as st

JOINS = ("op:JoinNode", "op:SemiJoinNode")


def _stated(span) -> bool:
    return span["name"] in JOINS and "probe_rows" in span["attributes"]


def rows(tree) -> int:
    """probe_rows + build_rows over the tree's joins."""
    return sum(int(s["attributes"]["probe_rows"]) + int(s["attributes"].get("build_rows", 0))
               for s in tree if _stated(s))


def of(trees):
    if not any(_stated(s) for t in trees for s in t):
        return None
    return sum(rows(t) for t in trees) / len(trees)


def by_template(trees, records) -> dict:
    """{template: mean join rows a statement}, the trees and the records
    paired in the order they began."""
    seen: dict = {}
    pairs = zip(sorted(trees, key=lambda t: t[0]["startNs"]), sorted(records, key=lambda r: r.start))
    for tree, record in pairs:
        seen.setdefault(record.statement.template, []).append(rows(tree))
    return {name: sum(v) / len(v) for name, v in sorted(seen.items())}


def derived(trees):
    """The `optimizer` spans' `derived_predicates`, summed; None where no
    span states it."""
    values = [s["attributes"]["derived_predicates"] for t in trees for s in t
              if s["name"] == "optimizer" and "derived_predicates" in s["attributes"]]
    return sum(values) if values else None


def read(run):
    trees = st.window_trees(run)
    if not trees:
        return None
    mean = of(trees)
    if mean is not None:
        run.notes["join_rows_by_template"] = by_template(trees, run.records)
        total = derived(trees)
        if total is not None:
            run.notes["derived_predicates"] = total
    return mean
