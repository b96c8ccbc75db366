"""Layer: executor. What the window's statements spend in the joins that pad
or mark rows and drop none: the `op:JoinNode` spans whose `kind` is LEFT or
FULL and the `op:SemiJoinNode` spans (an EXISTS, an IN, and their negations),
as a share of the `statement` spans' time. A span counts with its `sync:` and
`compact` children (the host waiting for the join's own programs) and without
its `op:` children (the inputs are other operators' time). None, never 0, on a
program whose join spans state no `kind` (every commit before PR 36): an inner
join's time cannot be told from an outer one's there."""

from benchmark.layer_metrics import _statements as st

OUTER_KINDS = ("LEFT", "FULL")


def own_seconds(tree, span) -> float:
    """The span less its `op:` children: its self time and the children that
    are its own (they run one after another on the executor's thread)."""
    own = [c for c in st.children(tree, span) if not c["name"].startswith("op:")]
    return st.self_seconds(tree, span) + sum(st.seconds(c) for c in own)


def picked(span) -> bool:
    if span["endNs"] is None:
        return False
    if span["name"] == "op:SemiJoinNode":
        return True
    return span["name"] == "op:JoinNode" and span["attributes"].get("kind") in OUTER_KINDS


def of(trees):
    joins = [s for t in trees for s in t if s["name"] == "op:JoinNode"]
    if not any("kind" in s["attributes"] for s in joins):
        return None
    return st.share_of_statements(
        trees, lambda t: sum(own_seconds(t, s) for s in t if picked(s)))


def read(run):
    trees = st.window_trees(run)
    return None if trees is None else of(trees)
