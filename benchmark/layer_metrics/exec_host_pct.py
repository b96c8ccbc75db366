"""Layer: executor. Self time of `execution` and of the `op:<PlanNode>` and
`compact` spans under it, as a share of the `statement` spans' time. Their
`sync:<site>` children (the host waiting for the device) are taken out as
children, and `drain` is no part of `execution`: what is left is the host
working, in Python between launches, while the device may wait for it."""

from benchmark.layer_metrics import _statements as st


def host_seconds(tree) -> float:
    total = 0.0
    for root in (s for s in st.children(tree, tree[0]) if s["name"] == "execution"):
        todo = [root]
        while todo:
            span = todo.pop()
            total += st.self_seconds(tree, span)
            todo += [c for c in st.children(tree, span)
                     if c["name"].startswith("op:") or c["name"] == "compact"]
    return total


def of(trees):
    return st.share_of_statements(trees, host_seconds)


def read(run):
    trees = st.window_trees(run)
    return None if trees is None else of(trees)
