"""Layer: executor. Device-to-host reads on the operator path per statement of
the window: the `host_syncs` the program rolls up on each `statement` span
(one per `sync:<site>` span in its tree)."""

from benchmark.layer_metrics import _statements as st


def of(trees):
    if not trees or any("host_syncs" not in t[0]["attributes"] for t in trees):
        return None
    return sum(t[0]["attributes"]["host_syncs"] for t in trees) / len(trees)


def read(run):
    trees = st.window_trees(run)
    return None if trees is None else of(trees)
