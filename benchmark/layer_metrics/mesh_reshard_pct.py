"""Layer: executor. The spans `mesh:load_scan` (the scanned table's pages
concatenated on device 0) and `mesh:shard` (padded and put on the mesh) as a
share of the `statement` spans' time: what a statement on the mesh tier pays to
bring a resident table to its shards, before the one program runs. The bytes
a statement moved (`mesh:shard.h2d_bytes`) go beside it, under the result
line's `notes`. None where no statement has such a span."""

from benchmark.layer_metrics import _statements as st

RESHARD = ("mesh:load_scan", "mesh:shard")


def resharding(tree) -> list:
    return [s for s in tree if s["name"] in RESHARD and s["endNs"] is not None]


def of(trees):
    if not any(resharding(t) for t in trees):
        return None
    return st.share_of_statements(trees, lambda t: sum(st.seconds(s) for s in resharding(t)))


def h2d_bytes_per_statement(trees) -> float:
    moved = [s["attributes"].get("h2d_bytes", 0) for t in trees for s in resharding(t)]
    return sum(moved) / len(trees) if trees else None


def read(run):
    trees = st.window_trees(run)
    if trees is None:
        return None
    share = of(trees)
    if share is not None:
        run.notes["h2d_bytes_per_statement"] = h2d_bytes_per_statement(trees)
    return share
