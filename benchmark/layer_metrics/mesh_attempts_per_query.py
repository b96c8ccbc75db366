"""Layer: executor. The `mesh:program` spans of the window's statements over
the statements: how often the mesh tier ran its one program to answer one
statement. 1.0 when every capacity held; each hundredth above it is a program
run twice (an overflow retry, or the re-run at the measured sizes that belongs
in a statement's first execution, which warm-up has made). How full the
narrowed pages were (`narrow_rows` over `narrow_capacity`, summed over those
spans) goes beside it, under the result line's `notes`. None where no
statement has such a span: another tier, or a program without the spans."""

from benchmark.layer_metrics import _statements as st

PROGRAM = "mesh:program"


def programs(tree) -> list:
    return [s for s in tree if s["name"] == PROGRAM and s["endNs"] is not None]


def of(trees):
    ran = sum(len(programs(t)) for t in trees)
    return ran / len(trees) if ran else None


def fill(trees):
    """Rows the narrowing points held over the capacity they ran at; None
    where the spans do not say (the program before the narrowing)."""
    spans = [s["attributes"] for t in trees for s in programs(t)]
    capacity = sum(a.get("narrow_capacity", 0) for a in spans)
    return sum(a.get("narrow_rows", 0) for a in spans) / capacity if capacity else None


def read(run):
    trees = st.window_trees(run)
    if not trees:
        return None
    per_query = of(trees)
    if per_query is not None and fill(trees) is not None:
        run.notes["narrow_rows_per_capacity"] = fill(trees)
    return per_query
