"""Layer: kernels. Over the window's `compact` spans: the rows a compaction
kept (`live_rows`) as a share of the rows it sorted to keep them
(`capacity_in`). None where no statement compacted."""

from benchmark.layer_metrics import _statements as st


def of(trees):
    spans = [s["attributes"] for t in trees for s in t if s["name"] == "compact"]
    sorted_rows = sum(a["capacity_in"] for a in spans)
    if not sorted_rows:
        return None
    return 100.0 * sum(a["live_rows"] for a in spans) / sorted_rows


def read(run):
    trees = st.window_trees(run)
    return None if trees is None else of(trees)
