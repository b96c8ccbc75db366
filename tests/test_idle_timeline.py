"""Every idle second of the device gets the name of a host span (ISSUE 38).

In the program: the mesh tier plans and lowers under spans (`parse`,
`planner`, `optimizer`, `fragment`, `mesh:lower`, `sync:mesh_measured`), and
the protocol front times the client's turn between two pages (`client_turn`).
In the benchmark: `layer_metrics/_idle.py` joins `TRACER`'s ring to the device
trace's clock and puts each piece of each idle gap down to the innermost open
span; driven here on synthetic events and trees."""

import json
import threading
import time
import urllib.request

import jax
import pytest

from benchmark.layer_metrics import _idle, plan_pct
from trino_tpu.runtime.tracing import STATEMENT, TRACER

N = 4
MS = 1_000_000
Q14 = """SELECT 100.00 * sum(CASE WHEN p_type LIKE 'PROMO%'
                         THEN l_extendedprice * (1 - l_discount) ELSE 0 END)
       / sum(l_extendedprice * (1 - l_discount)) AS promo_revenue
FROM {schema}.lineitem, {schema}.part
WHERE l_partkey = p_partkey AND l_shipdate >= DATE '1995-09-01'
  AND l_shipdate < DATE '1995-09-01' + INTERVAL '1' MONTH"""
GROUPED = "SELECT l_returnflag, count(*) FROM memory.default.lineitem GROUP BY l_returnflag"


def closed_tree(query_id, timeout=5.0):
    """The statement's spans once all have ended: a page's `result_stream`
    ends on its HTTP thread, which may come to it after the root has closed."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        tree = TRACER.spans(query_id)
        if tree and all(s.end_ns is not None for s in tree):
            return tree
        time.sleep(0.005)
    raise AssertionError(
        f"spans of {query_id} never closed: {[s.name for s in tree if s.end_ns is None]}")


# ------------------------------------------------- (a) the mesh tier's spans


@pytest.fixture(scope="module")
def dist():
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.parallel.runner import DistributedQueryRunner
    from trino_tpu.runtime import LocalQueryRunner

    if len(jax.devices()) < N:
        pytest.skip(f"need {N} devices")
    runner = DistributedQueryRunner.tpch(0.01, n_workers=N)
    runner.catalogs.register("memory", MemoryConnector())
    local = LocalQueryRunner.tpch(scale=0.01)
    local.register_catalog("memory", runner.catalogs.get("memory"))
    for table in ("lineitem", "part"):
        local.execute(f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.sf0_01.{table}")
    return runner


@pytest.fixture(scope="module")
def mesh_trees(dist):
    """The trees of a statement's first (nothing kept) and second execution
    on the mesh tier, under a QueryManager's root."""
    from trino_tpu.runtime import capstore
    from trino_tpu.runtime.query_manager import QueryManager

    capstore.clear_memory()
    dist._mesh_runner = None
    qm = QueryManager(dist.execute)
    trees = []
    for _ in range(2):
        q = qm.submit(GROUPED)
        assert q.wait_done(300) and q.error is None, q.error
        assert dist.last_tier == "ici"
        qm.close_statement(q)
        trees.append(closed_tree(q.query_id))
    return trees


@pytest.mark.parametrize("execution", [0, 1], ids=["first", "cached"])
def test_the_mesh_tier_plans_and_lowers_under_spans(mesh_trees, execution):
    tree = mesh_trees[execution]
    root = tree[0]
    assert root.name == STATEMENT
    mine = [s for s in tree if s.parent_id == root.span_id and s.name not in ("queue", "admit")]
    names = [s.name for s in mine]
    # a first execution may run its program again at the measured sizes: a
    # further `mesh:lower` and `mesh:program`, in that order
    assert names[:6] == ["parse", "planner", "optimizer", "fragment", "mesh:lower", "mesh:program"]
    assert names[-1] == "mesh:gather"
    assert names[6:-1] == ["mesh:lower", "mesh:program"] * ((len(names) - 7) // 2)
    assert all(s.end_ns is not None for s in tree)
    assert [a.end_ns <= b.start_ns for a, b in zip(mine, mine[1:])] == [True] * (len(mine) - 1)
    by_name = {s.name: s for s in mine[:6]}
    assert by_name["fragment"].attributes["fragments"] >= 2
    lower = by_name["mesh:lower"].attributes
    assert lower["cached"] is bool(execution) and lower["settled"] is bool(execution)
    assert lower["points"] >= 1
    # the scans' resharding lies inside the lowering: its shapes key the program
    inside = [s.name for s in tree if s.parent_id == by_name["mesh:lower"].span_id]
    assert inside == ["mesh:load_scan", "mesh:shard"]
    for program in (s for s in mine if s.name == "mesh:program"):
        reads = [s for s in tree if s.parent_id == program.span_id and s.name.startswith("sync:")]
        assert [s.name for s in reads] == ["sync:mesh_measured"]
    assert root.attributes["host_syncs"] == names.count("mesh:program")


@pytest.mark.parametrize("execution", [0, 1], ids=["first", "cached"])
def test_plan_pct_reads_the_mesh_tiers_tree(mesh_trees, execution):
    tree = [s.to_dict() for s in mesh_trees[execution]]
    share = plan_pct.of([tree])
    assert share is not None and 0 < share < 100


def test_planning_outside_a_statement_keeps_no_tree(dist):
    before = TRACER.traces()
    subplan = dist.plan_distributed(GROUPED)
    assert len(subplan.fragments) >= 2
    assert TRACER.traces() == before


# ---------------------------------------------------- (b) the client's turn


@pytest.fixture(scope="module")
def server():
    from trino_tpu.runtime import LocalQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer

    srv = CoordinatorServer(LocalQueryRunner.tpch(scale=0.001))
    srv.start()
    yield srv
    srv.stop()


def test_a_client_turn_lies_between_two_pages(server):
    from trino_tpu.client.client import StatementClient
    from trino_tpu.server.coordinator import PAGE_ROWS

    res = StatementClient(f"http://{server.address}").execute("SELECT l_orderkey FROM lineitem")
    assert PAGE_ROWS < len(res.rows) <= 2 * PAGE_ROWS      # two pages of rows
    tree = closed_tree(res.query_id)
    root = tree[0]
    front = sorted(
        (s for s in tree if s.parent_id == root.span_id and s.name in ("result_stream", "client_turn")),
        key=lambda s: s.start_ns,
    )
    names = [s.name for s in front]
    # the POST's page, then the pages of rows; a turn after every page but the last
    assert names[0] == "result_stream" and names[-1] == "result_stream"
    assert names == ["result_stream", "client_turn"] * (len(names) // 2) + ["result_stream"]
    for page, turn, after in zip(front[0::2], front[1::2], front[2::2]):
        # the turn begins as the page goes out and ends when the next request is in
        assert page.start_ns <= turn.start_ns <= page.end_ns
        assert turn.start_ns <= turn.end_ns <= after.start_ns
        assert turn.attributes["token"] == page.attributes["token"]
    assert [s.attributes["rows"] for s in front[0::2]][-2:] == [PAGE_ROWS, len(res.rows) - PAGE_ROWS]


def _post(server, sql):
    request = urllib.request.Request(
        f"http://{server.address}/v1/statement", data=sql.encode(), method="POST",
        headers={"X-Trino-User": "test"},
    )
    return json.loads(urllib.request.urlopen(request).read())


@pytest.mark.parametrize("how", ["canceled", "expired"])
def test_a_statement_left_after_its_first_page_leaves_no_span_open(how):
    from trino_tpu.runtime import LocalQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer

    gate = threading.Event()
    runner = LocalQueryRunner.tpch(scale=0.001)
    execute = runner.execute

    def gated(sql, *args, **kwargs):
        gate.wait(30)
        return execute(sql, *args, **kwargs)

    runner.execute = gated
    srv = CoordinatorServer(runner)
    if how == "expired":
        srv.manager._max_history = 1
    srv.start()
    try:
        first = _post(srv, "SELECT 1")
        assert "nextUri" in first           # the page is out, the client's turn is open
        q = srv.manager.get(first["id"])
        turn = q._client_turn
        assert turn is not None and turn.name == "client_turn" and turn.end_ns is None
        if how == "canceled":
            request = urllib.request.Request(first["nextUri"], method="DELETE",
                                             headers={"X-Trino-User": "test"})
            urllib.request.urlopen(request).read()
            gate.set()
        else:
            gate.set()
            assert q.wait_done(30)
            second = srv.manager.submit("SELECT 2")     # pushes the first off the history
            assert second.wait_done(30)
        tree = closed_tree(first["id"])       # no span of the statement is left open
        assert tree[0].attributes.get(how) is True
        assert turn.end_ns is not None and turn.end_ns <= tree[0].end_ns
        assert q._client_turn is None
    finally:
        gate.set()
        srv.stop()


# ------------------------------------------------ (c) the reader, by hand


def span(name, span_id, parent, start_ms, end_ms, **attributes):
    return {"name": name, "spanId": span_id, "parentSpanId": parent,
            "startNs": int(start_ms * MS), "endNs": int(end_ms * MS), "attributes": attributes}


def statement(at_ms, query_id="q_1"):
    """A statement of 100 ms on the ring's clock: bare for 4 ms, planning 6,
    `execution` 10 to 80 with an operator that reads a count from 30 to 60, a
    page sent from 80 to 82, the client's turn to 85, `drain` 85 to 95,
    `encode` to 96, bare again to 98, the last page to 100."""
    i = query_id
    tree = [
        span("statement", i, None, 0, 100, query_id=query_id),
        span("parse", i + "p", i, 4, 5),
        span("planner", i + "l", i, 5, 8),
        span("optimizer", i + "o", i, 8, 10),
        span("execution", i + "e", i, 10, 80),
        span("op:AggregationNode", i + "a", i + "e", 11, 79),
        span("sync:num_groups", i + "y", i + "a", 30, 60),
        span("result_stream", i + "r0", i, 80, 82, token=0),
        span("client_turn", i + "c", i, 82, 85, token=0),
        span("drain", i + "d", i, 85, 95),
        span("encode", i + "n", i, 95, 96),
        span("result_stream", i + "r1", i, 98, 100, token=1),
    ]
    for s in tree:
        s["startNs"] += int(at_ms * MS)
        s["endNs"] += int(at_ms * MS)
    return tree


OFFSET = 7_000_000_123_456_789      # the profiler's clock less the ring's


def on_profiler(ms):
    return ms * MS + OFFSET


def laid(trees, stmt_spans, gaps_ms, offset=OFFSET):
    """`_idle.summarise` over `_idle.lay`, as `_idle.of` calls them."""
    stmts = [(t, on_profiler(a), on_profiler(b), True) for t, a, b in stmt_spans]
    spans = _idle.flatten(trees, offset, stmts)
    gaps = [(on_profiler(a), on_profiler(b)) for a, b in gaps_ms]
    pieces = _idle.lay(gaps, spans)
    return pieces, _idle.summarise(pieces, spans, offset, stmts, on_profiler(0))


CLOCK_CASES = {
    # name: (pairs, jitter of each pair in us, what `offset_ns` is)
    "recovered": (12, 0.3, "offset"),
    "eight pairs are enough": (8, 0.3, "offset"),
    "too few pairs": (7, 0.3, None),
    "a wide residual": (12, 400.0, None),
}


@pytest.mark.parametrize("case", list(CLOCK_CASES))
def test_the_clock_offset_is_recovered_or_refused(case):
    pairs, jitter_us, expected = CLOCK_CASES[case]
    roots = [1_000 * MS + i * 150 * MS for i in range(40)]           # the ring's roots, perf_counter
    # the annotation begins a few hundred ns before the span's own stamp;
    # the harness's `stmt:` span 300 us before the server's root
    events = [r + OFFSET - 400 + int(((i * 7919) % 1000 / 1000 - 0.5) * 2 * jitter_us * 1e3)
              for i, r in enumerate(roots[:pairs])]
    stmt_starts = [r + OFFSET - 300_000 for r in roots]
    record_starts = [r - 300_000 - 2_000 for r in roots]             # stamped 2 us before the span
    found = _idle.clock(stmt_starts, record_starts, events, roots)
    assert found["pairs"] == pairs
    if expected is None:
        assert found["offset_ns"] is None
    else:
        assert abs(found["offset_ns"] - OFFSET) <= 1_000             # to 1 us
        assert found["iqr_us"] <= 1.0


def test_an_event_far_from_every_root_is_no_pair():
    roots = [1_000 * MS + i * 150 * MS for i in range(10)]
    events = [r + OFFSET + 5 * MS for r in roots]                    # 5 ms off: another statement's
    found = _idle.clock([roots[0] + OFFSET], [roots[0]], events, roots)
    assert found == {"pairs": 0, "offset_ns": None, "iqr_us": None}
    assert _idle.clock([], [], events, roots) is None


def by_class_ms(summary):
    return {k: round(v * 1e3, 6) for k, v in summary["idle_by_class"].items()}


def test_the_classes_partition_the_idle_seconds_exactly():
    # one gap over the whole statement and 10 ms either side of the client's span
    tree = statement(1_000)
    pieces, summary = laid([tree], [("q14", 990, 1_110)], [(980, 1_120)])
    assert sum(by_class_ms(summary).values()) == pytest.approx(140.0)
    assert sum((hi - lo) for lo, hi, _ in pieces) == 140 * MS
    assert by_class_ms(summary) == {
        "wait": 40.0,        # the read 30 to 60, the drain 85 to 95
        "work": 51.0,        # planning 6, execution and its operator 40, the pages 4, encode 1
        "unspanned": 6.0,    # 0 to 4 and 96 to 98
        "client": 23.0,      # the turn 3; the client's side 10 before and 10 after the root
        "none": 20.0,        # 10 before the `stmt:` span and 10 after it
    }
    names = {e[0]: e for e in summary["idle_by_span"]}
    assert names["sync:num_groups"][1:] == ["wait", pytest.approx(0.030), 1]
    assert names["drain"][1:] == ["wait", pytest.approx(0.010), 1]
    assert names["op:AggregationNode"][1:] == ["work", pytest.approx(0.038), 2]
    assert names["execution"][1:] == ["work", pytest.approx(0.002), 2]
    assert names["client_turn"][1] == "client" and names["stmt:q14"][1] == "client"
    assert names["statement"][1:] == ["unspanned", pytest.approx(0.006), 2]
    assert names["(nothing in flight)"][1:] == ["none", pytest.approx(0.020), 2]


def test_only_the_idle_gaps_are_laid():
    # the device is busy from 1,020 to 1,050 (inside the read) and idle in two gaps
    _, summary = laid([statement(1_000)], [("q14", 990, 1_110)], [(1_000, 1_020), (1_050, 1_100)])
    got = by_class_ms(summary)
    assert sum(got.values()) == pytest.approx(70.0)
    assert got["wait"] == 20.0 and got["unspanned"] == 6.0 and got["none"] == 0.0


def test_a_span_on_a_second_thread_that_started_later_wins():
    tree = statement(1_000)
    # another thread sends a page from 40 to 50, inside the read of 30 to 60
    tree.append(span("result_stream", "q_1x", "q_1", 1_040, 1_050, token=0))
    pieces, summary = laid([tree], [("q14", 990, 1_110)], [(1_030, 1_060)])
    assert [(round((lo - OFFSET) / MS), round((hi - OFFSET) / MS)) for lo, hi, _ in pieces] == [
        (1_030, 1_040), (1_040, 1_050), (1_050, 1_060)]
    assert by_class_ms(summary)["wait"] == 20.0 and by_class_ms(summary)["work"] == 10.0


def test_a_stats_feedback_tree_that_overlaps_the_next_statement_is_attributed():
    first, second = statement(1_000, "q_1"), statement(1_101, "q_2")
    feedback = [span("stats_feedback", "f", None, 1_100.5, 1_104, query_id="q_1", deferred=True)]
    _, summary = laid([first, second, feedback], [("q14", 995, 1_100.2), ("q06", 1_100.4, 1_205)],
                      [(1_100, 1_105)])
    names = {e[0]: e for e in summary["idle_by_span"]}
    # 1,100.5 to 1,104 is the feedback's, though the next statement's root is open from 1,101
    assert names["stats_feedback"][1:] == ["work", pytest.approx(0.0035), 1]
    assert names["statement"][1:] == ["unspanned", pytest.approx(0.001), 1]     # 1,104 to 1,105
    assert by_class_ms(summary)["none"] == pytest.approx(0.2)
    assert by_class_ms(summary)["client"] == pytest.approx(0.3)


def test_the_bare_root_is_unspanned_and_says_between_which_spans():
    _, summary = laid([statement(1_000)], [("q14", 999, 1_101)], [(1_000, 1_100)])
    assert summary["idle_unspanned_between"] == [
        ["(start)", "parse", pytest.approx(0.004), 1],
        ["encode", "result_stream", pytest.approx(0.002), 1],
    ]


def test_a_gap_outside_every_stmt_span_is_none():
    _, summary = laid([statement(1_000)], [("q14", 999, 1_101)], [(900, 950), (1_200, 1_300)])
    assert by_class_ms(summary) == {"wait": 0.0, "work": 0.0, "unspanned": 0.0, "client": 0.0,
                                    "none": 150.0}


def test_the_longest_pieces_carry_their_statement_and_their_chain():
    trees = [statement(1_000, "q_1"), statement(2_000, "q_2")]
    _, summary = laid(trees, [("q14", 999, 1_101), ("q01", 1_999, 2_101)],
                      [(1_000, 1_100), (2_030, 2_055)])
    longest = summary["idle_longest"]
    assert len(longest) == 5 and longest == sorted(longest, key=lambda p: -p["seconds"])
    assert longest[0] == {
        "seconds": pytest.approx(0.030), "at_s": pytest.approx(1.030), "template": "q14",
        "query_id": "q_1",
        "spans": ["statement", "execution", "op:AggregationNode", "sync:num_groups"],
    }
    assert longest[1]["query_id"] == "q_2" and longest[1]["template"] == "q01"
    assert longest[1]["seconds"] == pytest.approx(0.025)


def test_gaps_are_the_window_less_the_busy_intervals():
    assert _idle.gaps_of((0, 100), [[0, 10], [40, 50], [90, 100]]) == [(10, 40), (50, 90)]
    assert _idle.gaps_of((0, 100), []) == [(0, 100)]


def test_the_readers_give_none_without_a_trace_or_a_clock():
    from types import SimpleNamespace

    from benchmark.layer_metrics import idle_host_wait_pct, idle_unspanned_pct

    run = SimpleNamespace(trace=None, records=[], notes={})
    assert idle_host_wait_pct.read(run) is None and idle_unspanned_pct.read(run) is None
    assert run.notes == {}


# ------------------------------------- (d) the wait list against the program


def test_every_name_of_the_wait_list_is_a_span_the_program_opens(dist, mesh_trees):
    from trino_tpu.runtime import LocalQueryRunner

    local = LocalQueryRunner.tpch(scale=0.01)
    res = local.execute(Q14.format(schema="tpch.sf0_01"))
    opened = {s.name for s in TRACER.spans(res.trace_id)} | {s.name for t in mesh_trees for s in t}
    for name in _idle.WAIT:
        assert name in opened, name
    reads = {n for n in opened if n.startswith(_idle.WAIT_PREFIX)}
    assert "sync:mesh_measured" in reads and len(reads) >= 2, reads
    assert _idle.CLIENT not in opened          # no protocol front here: test (b) holds it
    assert {_idle.class_of(n) for n in reads | set(_idle.WAIT)} == {"wait"}
    assert _idle.class_of("mesh:lower") == "work" and _idle.class_of("mesh:program") == "work"


# --------------------------- the two clocks joined in a real run, on the CPU


def test_a_traced_run_of_the_harness_names_its_idle_seconds(monkeypatch):
    """The stream cell at SF0.01, traced, as benchmark/tests drives the
    harness on the CPU: one stand-in operation of a microsecond is the device,
    so nearly the whole window is idle, and every host event is kept (a
    statement here is shorter than the 20 ms `trace.load` keeps on the chip).
    Counts and the partition only: a CPU run gives no device number."""
    import io

    from benchmark import harness, trace

    load = trace.load

    def with_a_device(path):
        events = load(path)
        start = next(s for _, _, name, s, _ in events if name == trace.WINDOW_SPAN)
        return events + [("/device:TPU:0", trace.MODULES_LINE, "jit_stand_in", start, 1e3),
                         ("/device:TPU:0", trace.OPS_LINE, "stand_in.1", start, 1e3)]

    monkeypatch.setattr(trace, "LONG_HOST_EVENT_NS", 0.0)
    monkeypatch.setattr(trace, "load", with_a_device)
    monkeypatch.setattr(harness, "peaks_for", lambda kind, real=harness.peaks_for: real("TPU v5 lite"))
    out = io.StringIO()
    rc = harness.run("resident_analytic_stream", 2**31 + 38, 2.0, True, time.perf_counter(),
                     need_chips=False, config_overrides={"scale_factor": 0.01}, out=out)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True
    notes, metrics = line["notes"], line["metrics"]
    assert notes["idle_clock"]["pairs"] >= 8 and notes["idle_clock"]["iqr_us"] <= 100
    wait = metrics["idle_host_wait_pct.stream"]["value"]
    unspanned = metrics["idle_unspanned_pct.stream"]["value"]
    assert 0 < wait < 100 and 0 <= unspanned < 100
    by_class = notes["idle_by_class"]
    window = line["device"]["window_s"]
    assert wait == pytest.approx(100 * by_class["wait"] / window)
    assert unspanned == pytest.approx(100 * by_class["unspanned"] / window)
    # the classes add up to the idle seconds: the device's metric, and the breakdown's templates
    idle = metrics["device_idle_pct.stream"]["value"] / 100 * window
    assert sum(by_class.values()) == pytest.approx(idle, rel=0.01)
    by_template = sum(seconds for label, seconds in line["breakdown"]["idle_gaps"][:-1])
    assert sum(by_class.values()) == pytest.approx(by_template, rel=0.01)
    names = {name: kind for name, kind, _, _ in notes["idle_by_span"]}
    assert len(notes["idle_by_span"]) == 15 and set(names.values()) <= set(_idle.CLASSES)
    assert names.get("client_turn", "client") == "client"
    assert len(notes["idle_longest"]) == 5
    assert all(p["spans"] and p["template"] in ("q01", "q06", "q14") for p in notes["idle_longest"])
