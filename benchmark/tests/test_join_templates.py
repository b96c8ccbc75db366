"""The join templates (q03, q05, q10, q18): their domains are the
specification's, and no tuple the tests' seeds draw has rows that tie on the
specification's ORDER BY, where the row-for-row comparison would not be
decided (`assumed.order_of_equal_rows` of configs/tpch_joins_1chip.json holds
the same for every tuple at SF3)."""

import numpy as np
import pytest

from benchmark import harness
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped
from benchmark.tests.conftest import SCALE
from benchmark.traffic import Traffic, load_mix, load_template

TEMPLATES = ["q03", "q05", "q10", "q18"]
SEEDS = [1, 5, 6, 7, 2**31 + 7, 2**31 + 11, 2**31 + 12, 4_000_000_000]   # those of test_correct.py


@pytest.fixture(scope="module")
def host():
    _, config = harness.find_cell("resident_join_stream")
    traffic = Traffic(load_mix("join_stream"), 1, config["schema"])
    return harness.host_for(traffic, {**config, "scale_factor": SCALE})


def test_domains_are_the_specifications():
    sizes = {name: int(np.prod([len(v) for v in load_template(name).DOMAIN.values()]))
             for name in TEMPLATES}
    assert sizes == {"q03": 155, "q05": 25, "q10": 24, "q18": 4}  # cl. 2.4.3.3, 2.4.5.3, 2.4.10.3, 2.4.18.3
    assert load_template("q10").DOMAIN["month"][0] == "1993-02"
    assert load_template("q10").DOMAIN["month"][-1] == "1995-01"
    for name in TEMPLATES:
        assert "ORDER BY" in load_template(name).SQL and not hasattr(load_template(name), "SCANS")


@pytest.mark.parametrize("seed", SEEDS)
def test_no_drawn_tuple_ties_on_the_specifications_order(host, seed):
    traffic = Traffic(load_mix("join_stream"), seed, "memory.default")
    assert len(traffic.statements) == 8
    for statement in traffic.statements:
        module = traffic.templates[statement.template]
        assert not module.ties(host, statement.params), statement.label


def test_adjacent_ties_sees_a_tie_among_the_kept_and_at_the_cut():
    a, b = np.array([9, 7, 7, 3, 3]), np.array([1, 2, 2, 4, 5])
    assert grouped.adjacent_ties(3, a, b)          # rows 1 and 2 are kept and equal
    assert not grouped.adjacent_ties(1, a, b)      # row 0 kept, row 1 cut: they differ
    assert grouped.adjacent_ties(4, a) and not grouped.adjacent_ties(4, a, np.arange(5))  # equal on one key only
    assert grouped.adjacent_ties(2, np.array([5, 4, 4]))   # the last kept and the first cut


def test_the_runner_refuses_a_program_without_the_sort_family(monkeypatch, capsys):
    """The parent of PR 34 would compile the cell's statements for hours: the
    runner ends at once with its own code instead, and says why."""
    from benchmark.runners import local_memory_joins as runner
    from trino_tpu.ops import kernels

    monkeypatch.delattr(kernels, "sort_perm")
    with pytest.raises(SystemExit) as refused:
        runner.start({"name": "tpch_joins_1chip", "scale_factor": SCALE})
    assert refused.value.code == runner.REFUSED == 4
    assert "not run" in capsys.readouterr().out


def test_grouped_totals_in_both_arithmetics():
    units = np.array([2**24 + 1, 1, 5], dtype=np.int64)
    group = np.array([0, 0, 1])
    assert grouped.totals(units, group, 2, ref.EXACT).tolist() == [2**24 + 2, 5]
    assert grouped.totals(units, group, 2, ref.FLOAT32).tolist() != [2**24 + 2, 5]  # float32 drops the 1
    assert grouped.iso(9204) == "1995-03-15"


def test_the_readers_bytes_by_hand():
    """`join_bytes` and `group_bytes` on spans written out by hand (PERF.md section 3)."""
    import types

    from benchmark.layer_metrics import _operators as ops

    _, widths = harness.peaks_for("TPU v5 lite")
    run = types.SimpleNamespace(type_bytes=widths)
    join = {"probe_rows": 1000, "build_rows": 100, "rows_out": 500, "key_types": ["bigint"],
            "probe_types": {"bigint": 2, "date": 1}, "build_types": {"bigint": 1, "varchar(25)": 1}}
    # probe keys 1000 x 8; build rows 100 x (8 + 4); rows out 500 x (8 + 8 + 4 + 8 + 4)
    assert ops.join_bytes(run, join) == 8000 + 1200 + 16000
    assert ops.width(run, {"boolean": 2, "bigint": 1}) == 10  # a type the table lacks: one byte
    grouping = {"path": "sort", "rows_in": 1000, "groups": 10, "key_types": ["bigint", "date"],
                "agg_types": {"decimal(12,2)": 1}}
    assert ops.group_bytes(run, grouping) == 1000 * 20 + 10 * 20
    ordering = {"rows_in": 100, "rows_out": 10, "carried_types": {"bigint": 1, "decimal(18,4)": 1}}
    assert ops.group_bytes(run, ordering) == 110 * 16
    assert ops.group_bytes(run, {**grouping, "path": "direct", "groups": None}) == 1000 * 20


def test_the_readers_find_nothing_without_a_trace_or_without_the_spans():
    import types

    from benchmark.layer_metrics import _operators as ops
    from benchmark.layer_metrics import group_device_pct, join_device_pct, join_roofline

    untraced = types.SimpleNamespace(trace=None)
    assert join_device_pct.read(untraced) is None and group_device_pct.read(untraced) is None
    assert join_roofline.read(untraced) is None
    device = types.SimpleNamespace(busy_s=2.0, op_seconds={
        "jit__jit_join_match %sort.1": 0.5, "jit__jit_join_expand %fusion": 0.25,
        "jit__group_sort_impl %sort.2": 0.25, "jit__group_sort_impl %while.7": 0.25,
        "jit__group_sort_impl %conditional": 0.25, "jit__jit_filter %fusion": 1.0})
    traced = types.SimpleNamespace(trace=types.SimpleNamespace(fullest=device))
    assert join_device_pct.read(traced) == 37.5 and group_device_pct.read(traced) == 12.5
    # a program whose spans state no rows (the parent of PR 34): nothing, not 0
    tree = [{"name": "statement", "attributes": {}}, {"name": "op:JoinNode", "attributes": {"launches": 1}}]
    traced._statement_trees, traced.type_bytes = [tree], {"bigint": 8}
    traced.peaks = {"hbm_bytes_per_s": 8.19e11}
    assert ops.roofline(traced, ops.JOIN_SPANS, ops.JOIN_PROGRAMS, ops.join_bytes) is None
    tree[1]["attributes"] = {"probe_rows": 10**9, "build_rows": 0, "rows_out": 0, "key_types": ["bigint"],
                             "probe_types": {}, "build_types": {}}
    # 8e9 bytes at 819 GB/s are 9.77 ms of the join programs' 750 ms
    assert abs(ops.roofline(traced, ops.JOIN_SPANS, ops.JOIN_PROGRAMS, ops.join_bytes) - 100 * 8e9 / 8.19e11 / 0.75) < 1e-9
