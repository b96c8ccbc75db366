"""The readers of the program's own spans (layer_metrics/_statements.py and the
five metrics over it): on trees written out by hand, on a small ring recorded
on the chip and kept beside this file, and in a traced run of the harness on
the CPU."""

import io
import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import harness, trace
from benchmark.layer_metrics import (
    _statements,
    compact_live_pct,
    exec_host_pct,
    host_syncs_per_query,
    mesh_reshard_pct,
    plan_pct,
    queue_wait_pct,
)
from benchmark.tests.conftest import SCALE

MS = 1_000_000
READERS = {
    "plan_pct": plan_pct, "exec_host_pct": exec_host_pct,
    "host_syncs_per_query": host_syncs_per_query, "compact_live_pct": compact_live_pct,
    "queue_wait_pct": queue_wait_pct,
}


def span(name, span_id, parent, start_ms, end_ms, **attributes):
    return {"name": name, "spanId": span_id, "parentSpanId": parent,
            "startNs": start_ms * MS, "endNs": end_ms * MS, "attributes": attributes}


def by_hand(at_ms=0, syncs=2):
    """One statement of 100 ms: queue 2, admit 1, parse 1, planner 3 and
    optimizer 2 ms; `execution` 70 ms holding a filter (its 10 ms of self time
    and a scan of 5), the aggregation's sync of 30 ms and its compaction of 4
    (32768 rows sorted, 512 kept); drain 10, encode 1, the last page 5; the
    POST's own page overlaps the queue on another thread."""
    tree = [
        span("statement", "s", None, 0, 100, query_id="q_1", host_syncs=syncs, launches=3),
        span("queue", "q", "s", 0, 2),
        span("result_stream", "r0", "s", 1, 3, rows=0),
        span("admit", "a", "s", 2, 3),
        span("parse", "p", "s", 4, 5),
        span("planner", "pl", "s", 5, 8),
        span("optimizer", "o", "s", 8, 10),
        span("execution", "e", "s", 10, 80),
        span("op:AggregationNode", "agg", "e", 11, 79, launches=1),
        span("op:FilterNode", "f", "agg", 12, 27, launches=1),
        span("op:TableScanNode", "t", "f", 13, 18, launches=1),
        span("sync:compact", "y", "agg", 30, 60, value=512),
        span("compact", "c", "agg", 60, 64, capacity_in=32768, live_rows=512, capacity_out=512,
             columns=2),
        span("drain", "d", "s", 80, 90),
        span("encode", "n", "s", 90, 91, rows=1),
        span("result_stream", "r1", "s", 94, 99, rows=1),
    ]
    for s in tree:
        s["startNs"] += at_ms * MS
        s["endNs"] += at_ms * MS
    return tree


def records(*intervals_ms):
    return [SimpleNamespace(start=a / 1e3, end=b / 1e3) for a, b in intervals_ms]


def test_self_time_takes_out_what_children_cover():
    tree = by_hand()
    by_id = {s["spanId"]: s for s in tree}
    assert _statements.self_seconds(tree, by_id["e"]) == pytest.approx(0.002)     # 70 - 68
    assert _statements.self_seconds(tree, by_id["agg"]) == pytest.approx(0.019)   # 68 - 15 - 30 - 4
    assert _statements.self_seconds(tree, by_id["f"]) == pytest.approx(0.010)     # 15 - 5
    # queue 0-2, the POST's page 1-3 and admit 2-3 overlap: their union is 3 ms
    covered = 3 + 1 + 3 + 2 + 70 + 10 + 1 + 5
    assert _statements.self_seconds(tree, tree[0]) == pytest.approx((100 - covered) / 1e3)


BY_HAND = {
    "plan_pct": 6.0,                   # parse 1 + planner 3 + optimizer 2 of 100 ms
    "exec_host_pct": 40.0,             # execution 2 + aggregation 19 + filter 10 + scan 5 + compact 4
    "host_syncs_per_query": 2.0,
    "compact_live_pct": 100.0 * 512 / 32768,
    "queue_wait_pct": 3.0,             # queue 2 + admit 1
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_by_hand(metric):
    assert READERS[metric].of([by_hand()]) == pytest.approx(BY_HAND[metric])
    # two statements, the second with three syncs: shares stay, the count is a mean
    two = [by_hand(), by_hand(at_ms=200, syncs=3)]
    want = 2.5 if metric == "host_syncs_per_query" else BY_HAND[metric]
    assert READERS[metric].of(two) == pytest.approx(want)


def test_no_compaction_reads_as_nothing():
    tree = [s for s in by_hand() if s["name"] != "compact"]
    assert compact_live_pct.of([tree]) is None


def mesh_by_hand():
    """One statement of 200 ms on the mesh tier with two scans: the first
    table's pages concatenated in 30 ms and put on the mesh in 20 (1000 bytes),
    the second's in 5 and 5 (500 bytes); the program 100, the gather 10."""
    return [
        span("statement", "s", None, 0, 200, query_id="q_2", host_syncs=0, launches=9),
        span("mesh:load_scan", "l0", "s", 10, 40, table="default.lineitem", rows=64, bytes=900),
        span("mesh:shard", "h0", "s", 40, 60, h2d_bytes=1000),
        span("mesh:load_scan", "l1", "s", 60, 65, table="default.part", rows=8, bytes=400),
        span("mesh:shard", "h1", "s", 65, 70, h2d_bytes=500),
        span("mesh:program", "m", "s", 70, 170, attempt=0, cached=True),
        span("mesh:gather", "g", "s", 170, 180, rows=1),
    ]


def test_mesh_reshard_by_hand():
    assert mesh_reshard_pct.of([mesh_by_hand()]) == pytest.approx(30.0)   # 30 + 20 + 5 + 5 of 200 ms
    assert mesh_reshard_pct.h2d_bytes_per_statement([mesh_by_hand()]) == 1500
    # beside a statement of another tier the share is over both, the bytes a mean
    both = [mesh_by_hand(), by_hand(at_ms=300)]
    assert mesh_reshard_pct.of(both) == pytest.approx(20.0)
    assert mesh_reshard_pct.h2d_bytes_per_statement(both) == 750
    assert mesh_reshard_pct.of([by_hand()]) is None     # no statement ran on the mesh tier
    assert plan_pct.of([mesh_by_hand()]) is None        # the distributed runner plans outside any span
    assert plan_pct.of(both) == pytest.approx(2.0)      # parse 1 + planner 3 + optimizer 2 of 300 ms


def test_off_tier_is_read_statement_by_statement_from_the_spans():
    from benchmark.runners import mesh_memory

    on = mesh_by_hand()
    retries_ran_out = [s for s in mesh_by_hand() if s["name"] != "mesh:gather"]   # the staged tier answered
    assert mesh_memory.off_tier([on, on], 2) == (0, None)
    off, what = mesh_memory.off_tier([on, retries_ran_out, by_hand()], 3)
    assert off == 2 and "q_2: not on tier ici" in what and "mesh:program" in what
    off, what = mesh_memory.off_tier([on], 3)   # two statements whose trees the ring has lost
    assert off == 2 and "no tree" in what


def test_mesh_reshard_on_the_recorded_ring():
    recorded = json.loads((Path(__file__).parent / "recorded_mesh.json").read_text())
    trees, want = recorded["trees"], recorded["by_hand"]
    assert [t[0]["name"] for t in trees] == ["statement"] * 6
    assert mesh_reshard_pct.of(trees) == pytest.approx(want["mesh_reshard_pct"], rel=1e-9)
    assert mesh_reshard_pct.h2d_bytes_per_statement(trees) == want["h2d_bytes_per_statement"]
    assert plan_pct.of(trees) is None and exec_host_pct.of(trees) == 0.0


def test_select_wants_one_root_per_record_of_the_window():
    ring = [by_hand(at_ms=-500), by_hand(at_ms=10), by_hand(at_ms=200)]   # the first is warm-up's
    window = records((5, 120), (190, 310))
    assert _statements.select(ring, window) == ring[1:]
    assert _statements.select(ring[:2], window) is None           # a root is missing
    assert _statements.select(ring + [by_hand(at_ms=250)], window) is None   # one too many
    assert _statements.select(None, window) is None               # the program keeps no ring
    assert _statements.select(ring, []) is None
    # a clock that is not the harness's puts every root outside the window
    assert _statements.select([by_hand(at_ms=10**9), by_hand(at_ms=10**9 + 200)], window) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_gives_none_when_a_root_is_missing(metric, monkeypatch):
    monkeypatch.setattr(_statements, "ring", lambda: [by_hand(at_ms=10)])
    run = SimpleNamespace(records=records((5, 120), (190, 310)))
    assert READERS[metric].read(run) is None
    whole = SimpleNamespace(records=records((5, 120)))
    assert READERS[metric].read(whole) == pytest.approx(BY_HAND[metric])


def test_a_program_without_the_tracer_reads_as_nothing(monkeypatch):
    import trino_tpu.runtime.tracing as tracing

    monkeypatch.setattr(tracing, "TRACER", object())   # the parent's has no `finished`
    assert _statements.ring() is None


# ------------------------------------------------ the ring recorded on the chip

RECORDED = json.loads((Path(__file__).parent / "recorded_statements.json").read_text())


def test_recorded_ring_holds_one_statement_of_each_template():
    trees = RECORDED["trees"]
    assert [t[0]["name"] for t in trees] == ["statement"] * 3
    for tree in trees:
        ids = {s["spanId"] for s in tree}
        assert all(s["parentSpanId"] in ids for s in tree[1:])
        names = [s["name"] for s in _statements.children(tree, tree[0]) if s["name"] != "result_stream"]
        assert names == ["queue", "admit", "parse", "planner", "optimizer", "execution", "drain",
                         "encode"]


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_on_the_recorded_ring(metric):
    want = RECORDED["by_hand"][metric]
    assert READERS[metric].of(RECORDED["trees"]) == pytest.approx(want, rel=1e-6)


# ------------------------------------------------- a traced run of the harness


@pytest.fixture
def traced_on_the_cpu(monkeypatch):
    """A traced run needs a /device:TPU plane and the chip's peaks. On the CPU
    the trace has neither: one operation of a microsecond is put at the
    window's start, and the v5e's peaks stand in."""
    load = trace.load

    def with_a_device(path):
        events = load(path)
        start = next(s for _, _, name, s, _ in events if name == trace.WINDOW_SPAN)
        return events + [("/device:TPU:0", trace.MODULES_LINE, "jit_stand_in", start, 1e3),
                         ("/device:TPU:0", trace.OPS_LINE, "stand_in.1", start, 1e3)]

    monkeypatch.setattr(trace, "load", with_a_device)
    monkeypatch.setattr(harness, "peaks_for", lambda kind, real=harness.peaks_for: real("TPU v5 lite"))


@pytest.mark.parametrize("cell", [w["name"] for w in harness.manifest()["workloads"]])
def test_a_traced_run_reports_the_span_metrics(cell, traced_on_the_cpu, capfd):
    out = io.StringIO()
    rc = harness.run(cell, 2**31 + 21, 2.0, True, time.perf_counter(), need_chips=False,
                     config_overrides={"scale_factor": SCALE}, out=out)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    mine = {m["name"] for m in harness.metrics_of(cell, "per_layer")
            if m["name"].split(".")[0] in set(READERS) | {"mesh_reshard_pct"}}
    assert mine and mine <= set(line["metrics"])
    for name in mine:
        assert line["metrics"][name]["value"] >= 0
    assert f"{line['attempted']} roots in the window for {line['attempted']} records" in capfd.readouterr().err
    if harness.find_cell(cell)[0]["chips"] > 1:   # the mesh tier's spans, and the bytes beside them
        assert 0 < line["metrics"]["mesh_reshard_pct.mesh"]["value"] < 100
        assert line["notes"]["h2d_bytes_per_statement"] > 0
    else:
        assert not any(name.startswith("mesh_reshard_pct") for name in line["metrics"])
    # the program's spans lie in the profiler's trace: the longest gap names one
    assert "trino:" in line["breakdown"]["idle_gaps"][-1][0]
