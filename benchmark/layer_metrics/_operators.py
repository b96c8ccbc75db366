"""Shared by the readers of the join cell (`join_device_pct`, `group_device_pct`,
`join_roofline`, `group_roofline`): which device programs belong to the joins
and which to grouping and ordering, their seconds in the traced window, and the
least bytes an operator has to move, counted from what its span states.

The program names are the executor's jitted functions as the profiler names
them (`jit_<function>`); `tests/test_join_deployment.py` holds the program to
them. A program that lacks the spans or a trace without such programs gives
None, never 0."""

import re

from benchmark.layer_metrics import _statements as st

JOIN_PROGRAMS = ("jit__jit_join_match", "jit__jit_join_expand", "jit__jit_semijoin",
                 "jit__jit_left_join_residual", "jit__jit_full_join_tail")
GROUP_PROGRAMS = ("jit__group_sort_impl", "jit__aggregate_impl", "jit__direct_aggregate_impl",
                  "jit__presorted_group_impl", "jit__sort_impl")
JOIN_SPANS = ("op:JoinNode", "op:SemiJoinNode")
GROUP_SPANS = ("op:AggregationNode", "op:TopNNode", "op:SortNode")


CONTAINERS = re.compile(r"^%(while|conditional|call)\b")


def program_seconds(run, programs) -> float:
    """Seconds of the fullest device's operations, inside the traced window,
    that ran in one of `programs` (`op_seconds` keys are "<program> <operation>").
    A loop or a conditional is an operation too, and holds its body's
    operations' time again: they are left out, the bodies counted."""
    if run.trace is None:
        return None
    total = 0.0
    for key, seconds in run.trace.fullest.op_seconds.items():
        program, _, operation = key.partition(" ")
        if program in programs and not CONTAINERS.match(operation):
            total += seconds
    return total


def device_share(run, programs):
    """100 x the programs' seconds over the device's busy seconds."""
    seconds = program_seconds(run, programs)
    if seconds is None or not seconds or run.trace.fullest.busy_s <= 0:
        return None
    return 100.0 * seconds / run.trace.fullest.busy_s


def width(run, types) -> int:
    """Bytes of one row of columns of SQL `types` ({type: count} or a list),
    by the widths of benchmark/peaks.json; a type the table lacks (a
    semi-join's boolean) counts one byte, the least a value takes."""
    if isinstance(types, dict):
        types = [t for t, n in types.items() for _ in range(n)]
    return sum(run.type_bytes.get(re.sub(r"\(.*\)", "", t), 1) for t in types)


def join_bytes(run, a: dict) -> int:
    """The least a join moves: every probe row's key, every build row's key
    and carried columns, every row out whole."""
    key = width(run, a["key_types"])
    out = width(run, a["probe_types"]) + width(run, a["build_types"])
    return (a["probe_rows"] * key + a["build_rows"] * width(run, a["build_types"])
            + (a.get("rows_out") or 0) * out)


def group_bytes(run, a: dict) -> int:
    """The least a grouping or an ordering moves: every row in with its keys
    and aggregated (or carried) columns, every group (or row) out whole."""
    if "path" in a:   # an aggregation
        row = width(run, a["key_types"]) + width(run, a["agg_types"])
        return a["rows_in"] * row + (a["groups"] or 0) * row
    row = width(run, a["carried_types"])
    return (a["rows_in"] + a["rows_out"]) * row


def roofline(run, span_names, programs, count):
    """100 x (bytes of the window's operators / peak bytes a second) over the
    device seconds of their programs; None unless both are there."""
    seconds = program_seconds(run, programs)
    if not seconds:
        return None
    trees = st.window_trees(run)
    if trees is None:
        return None
    try:
        total = sum(count(run, s["attributes"]) for t in trees for s in t if s["name"] in span_names)
    except KeyError:   # a program whose spans do not state rows and types
        return None
    if not total:
        return None
    return 100.0 * (total / run.peaks["hbm_bytes_per_s"]) / seconds
