"""Layer: protocol front. The spans `queue` (creation to a pool thread with the
resource group's slot) and `admit`, as a share of the `statement` spans' time:
the part of the server's clock in which the statement waits to be run."""

from benchmark.layer_metrics import _statements as st

WAITING = ("queue", "admit")


def of(trees):
    return st.share_of_statements(
        trees, lambda t: sum(st.seconds(s) for s in st.children(t, t[0]) if s["name"] in WAITING))


def read(run):
    trees = st.window_trees(run)
    return None if trees is None else of(trees)
