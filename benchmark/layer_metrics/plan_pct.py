"""Layer: planner. Self time of the spans `parse`, `planner` and `optimizer`
as a share of the `statement` spans' time, over the window's statements: what
the host spends before the first operator is dispatched. None where no
statement has such a span: a runner that plans outside them (the distributed
runner's `plan_distributed`) has nothing here to read, and that is not 0."""

from benchmark.layer_metrics import _statements as st

PLANNING = ("parse", "planner", "optimizer")


def of(trees):
    if not any(s["name"] in PLANNING for t in trees for s in t):
        return None
    return st.share_of_statements(
        trees, lambda t: sum(st.self_seconds(t, s) for s in st.children(t, t[0])
                             if s["name"] in PLANNING))


def read(run):
    trees = st.window_trees(run)
    return None if trees is None else of(trees)
