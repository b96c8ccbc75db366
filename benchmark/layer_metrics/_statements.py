"""Shared by the readers of the program's own spans (runtime/tracing.py,
`TRACER`): the finished `statement` trees that belong to the window's records.

A tree is the list of one statement's spans as `Span.to_dict()` gives them,
root first: `name`, `spanId`, `parentSpanId`, `startNs`, `endNs` (the
process's `perf_counter_ns`, the clock `harness.Record.start/end` is stamped
on), `attributes`. The root `statement` runs from the statement's creation in
the server to its last page sent.

Every reader gets None unless exactly one root per record of the window is
found: a ring that overflowed, or a clock that is not the harness's, must not
read as a small number. A program without such a tracer (the parent of the PR
that brought the spans) gives None too."""

import sys

STATEMENT = "statement"


def ring():
    """The tracer's finished trees, oldest first; None where the program
    keeps none."""
    try:
        from trino_tpu.runtime.tracing import TRACER

        finished = TRACER.finished
    except (ImportError, AttributeError):
        return None
    return [[span.to_dict() for span in tree] for tree in finished(STATEMENT)]


def select(trees, records):
    """The trees whose root began between the first record's start and the
    last record's end; None unless there is exactly one per record."""
    if trees is None or not records:
        return None
    first = min(r.start for r in records) * 1e9
    last = max(r.end for r in records) * 1e9
    mine = [t for t in trees if t and t[0]["name"] == STATEMENT and first <= t[0]["startNs"] <= last]
    print(f"statement spans: {len(mine)} roots in the window for {len(records)} records",
          file=sys.stderr)
    return mine if len(mine) == len(records) else None


def window_trees(run):
    """`select` over the live ring, once per run."""
    if not hasattr(run, "_statement_trees"):
        run._statement_trees = select(ring(), run.records)
    return run._statement_trees


def seconds(span) -> float:
    return (span["endNs"] - span["startNs"]) / 1e9


def children(tree, span):
    return [s for s in tree if s["parentSpanId"] == span["spanId"] and s["endNs"] is not None]


def self_seconds(tree, span) -> float:
    """The span less what its children cover (their union: a child on another
    thread may overlap its sibling)."""
    lo, hi = span["startNs"], span["endNs"]
    covered, reach = 0, lo
    for s, e in sorted((max(c["startNs"], lo), min(c["endNs"], hi)) for c in children(tree, span)):
        if e > reach:
            covered += e - max(s, reach)
            reach = e
    return (hi - lo - covered) / 1e9


def statement_seconds(trees) -> float:
    return sum(seconds(t[0]) for t in trees)


def share_of_statements(trees, picked) -> float:
    """100 x the seconds `picked(tree)` gives, over the statements' seconds."""
    total = statement_seconds(trees)
    if total <= 0:
        return None
    return 100.0 * sum(picked(t) for t in trees) / total
