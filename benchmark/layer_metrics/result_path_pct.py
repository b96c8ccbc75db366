"""Layer: protocol front. The answer's way back: the self time of the
`encode` span (the padded page walked into rows, `Page.to_pylist`), of every
`result_stream` span (a page of the protocol made into JSON and sent) and of
every `client_turn` span (the client reading a page and asking for the next),
as a share of the `statement` spans' time. It grows with the rows of an
answer, and with the pages they take. Beside it, under `notes`: pages and
rows per statement (the root's `pages` and `rows`), and the rows of the
padded pages `encode` walked over the rows it gave (`capacity` over `rows`,
where the spans state `capacity`: PR 40 and later). None where no statement
has an `encode` span."""

from benchmark.layer_metrics import _statements as st

WAY_BACK = ("encode", "result_stream", "client_turn")


def way_back(tree) -> list:
    return [s for s in tree if s["name"] in WAY_BACK and s["endNs"] is not None]


def of(trees):
    if not any(s["name"] == "encode" for t in trees for s in way_back(t)):
        return None
    return st.share_of_statements(trees, lambda t: sum(st.self_seconds(t, s) for s in way_back(t)))


def notes(trees) -> dict:
    roots = [t[0]["attributes"] for t in trees]
    out = {
        "pages_per_statement": sum(a.get("pages", 0) for a in roots) / len(trees),
        "rows_per_statement": sum(a.get("rows", 0) for a in roots) / len(trees),
    }
    encoded = [s["attributes"] for t in trees for s in t if s["name"] == "encode"]
    rows = sum(a.get("rows", 0) for a in encoded)
    if rows and all("capacity" in a for a in encoded):
        out["encode_capacity_per_row"] = sum(a["capacity"] for a in encoded) / rows
    return out


def read(run):
    trees = st.window_trees(run)
    if not trees:
        return None
    share = of(trees)
    if share is not None:
        run.notes["result_path"] = notes(trees)
    return share
