"""One statement, one timeline (runtime/tracing.py): a coordinator over HTTP at
SF0.01, and the tree of spans each statement leaves in ``TRACER``.

The names are the contract the benchmark's readers and PERF.md use: root
``statement`` (trace id = query id) with ``queue``, ``admit``, ``parse``,
``planner``, ``optimizer``, ``execution`` (``op:<PlanNode>``, ``sync:<site>``,
``compact`` beneath), ``drain``, ``encode`` and ``result_stream``."""

import json
import threading
import time
import urllib.request

import pytest

from trino_tpu.runtime.tracing import (
    DROPPED_COUNTER,
    STATEMENT,
    STATS_FEEDBACK,
    TRACER,
    Tracer,
    children,
)

Q06 = (
    "SELECT sum(l_extendedprice * l_discount) FROM lineitem "
    "WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01' "
    "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"
)
# q06's filter where the consumer does need dense rows: a sort-path GROUP BY
Q06_GROUPED = (
    Q06.replace("SELECT sum(", "SELECT l_orderkey, sum(") + " GROUP BY l_orderkey"
)
Q01 = (
    "SELECT l_returnflag, l_linestatus, sum(l_quantity), sum(l_extendedprice), "
    "avg(l_discount), count(*) FROM lineitem WHERE l_shipdate <= DATE '1998-09-02' "
    "GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus"
)
IN_ROOT = [
    "queue", "admit", "parse", "planner", "optimizer", "execution", "drain",
    "encode",
]


@pytest.fixture(scope="module")
def server():
    from trino_tpu.runtime import LocalQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer

    srv = CoordinatorServer(LocalQueryRunner.tpch(scale=0.01))
    srv.start()
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def client(server):
    from trino_tpu.client.client import StatementClient

    c = StatementClient(f"http://{server.address}")
    for sql in (Q06, Q06_GROUPED, Q01):  # compiled before any test looks at a clock
        c.execute(sql)
    return c


def finished_tree(query_id, timeout=5.0):
    """The statement's spans once its root has closed: the server ends it
    just after the client has the last page."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        tree = TRACER.spans(query_id)
        if tree and tree[0].end_ns is not None:
            return tree
        time.sleep(0.005)
    raise AssertionError(f"the root of {query_id} never closed")


def covered_ns(tree, span):
    """Nanoseconds of ``span`` that its children cover (their union: a child
    on another thread may overlap its sibling). A layer's self time is its
    span less this."""
    lo, hi = span.start_ns, span.end_ns
    total, reach = 0, lo
    for s, e in sorted(
        (max(c.start_ns, lo), min(c.end_ns, hi)) for c in children(tree, span)
    ):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


def programs_launched():
    from trino_tpu.runtime.metrics import REGISTRY

    return REGISTRY.counter("trino_tpu_device_programs_total").value


@pytest.fixture(scope="module")
def q06(client):
    before = programs_launched()
    res = client.execute(Q06)
    tree = finished_tree(res.query_id)
    return res, tree, programs_launched() - before


@pytest.fixture(scope="module")
def grouped(client):
    res = client.execute(Q06_GROUPED)
    return res, finished_tree(res.query_id)


class TestOneTree:
    def test_root_is_the_statement_and_carries_the_query_id(self, q06):
        res, tree, _ = q06
        root = tree[0]
        assert root.name == STATEMENT and root.parent_id is None
        assert root.trace_id == res.query_id == root.attributes["query_id"]
        assert all(s.trace_id == res.query_id for s in tree)
        assert [t for t in TRACER.finished() if t[0] is root]
        ids = {s.span_id for s in tree}
        assert len(ids) == len(tree) and all(s.parent_id in ids for s in tree[1:])

    def test_children_in_order(self, q06):
        _, tree, _ = q06
        root = tree[0]
        mine = [s for s in tree if s.parent_id == root.span_id]
        front = ("result_stream", "client_turn")
        assert [s.name for s in mine if s.name not in front] == IN_ROOT
        # the pages: the POST's answer first (the client's turn opens as it
        # goes out), the one with the rows last
        assert [s.name for s in mine if s.name in front][:2] == list(front)
        last = mine[-1]
        assert last.name == "result_stream" and last.attributes["rows"] == 1
        assert last.attributes["bytes"] > 0 and "token" in last.attributes
        encode = next(s for s in mine if s.name == "encode")
        assert last.start_ns >= encode.end_ns and encode.attributes["rows"] == 1

    def test_every_child_lies_inside_its_parent(self, q06):
        _, tree, _ = q06
        by_id = {s.span_id: s for s in tree}
        for s in tree[1:]:
            parent = by_id[s.parent_id]
            assert s.end_ns is not None, s.name
            assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns, (
                s.name, parent.name
            )

    def test_self_times_sum_to_the_roots(self, client):
        tree = finished_tree(client.execute(Q01).query_id)
        root = tree[0]
        # the POST's own page is sent from its HTTP thread while the pool
        # thread queues, admits and plans: it overlaps those siblings (the
        # root's self time takes the union out), so it is left out of the sum,
        # and so is the client's turn after it (tests/test_idle_timeline.py)
        first_page = next(s for s in tree if s.name == "result_stream")
        total = sum(
            (s.end_ns - s.start_ns) - covered_ns(tree, s)
            for s in tree if s is not first_page and s.name != "client_turn"
        )
        assert total == pytest.approx(root.end_ns - root.start_ns, rel=0.05)

    def test_operators_hang_under_execution_or_their_consumer(self, q06):
        _, tree, _ = q06
        by_id = {s.span_id: s for s in tree}
        ops = [s for s in tree if s.name.startswith("op:")]
        assert {s.name for s in ops} >= {
            "op:AggregationNode", "op:FilterNode", "op:TableScanNode",
        }
        for s in ops:
            parent = by_id[s.parent_id].name
            assert parent == "execution" or parent.startswith("op:")
        for s in tree:
            if s.name.startswith("sync:") or s.name == "compact":
                assert by_id[s.parent_id].name.startswith("op:")

    def test_counts_on_the_root(self, q06):
        res, tree, launched = q06
        root = tree[0].attributes
        # a global sum reduces under the mask: nothing is read back
        assert root["host_syncs"] == 0
        assert not [s for s in tree if s.name.startswith("sync:")]
        assert root["launches"] == launched > 0
        assert root["launches"] == sum(
            s.attributes.get("launches", 0) for s in tree[1:]
        )
        assert root["rows"] == len(res.rows) == 1 and root["pages"] == 1

    def test_host_syncs_on_the_root(self, grouped):
        res, tree = grouped
        syncs = [s for s in tree if s.name.startswith("sync:")]
        assert tree[0].attributes["host_syncs"] == len(syncs) >= 1
        assert "sync:compact" in {s.name for s in syncs}
        assert all(isinstance(s.attributes["value"], int) for s in syncs)
        assert tree[0].attributes["rows"] == len(res.rows) > 1

    def test_a_selective_filter_leaves_a_compact_span(self, grouped):
        _, tree = grouped
        compact = [s.attributes for s in tree if s.name == "compact"]
        assert compact, [s.name for s in tree]
        for a in compact:
            assert a["live_rows"] <= a["capacity_out"] < a["capacity_in"]
            assert a["live_rows"] * 4 <= a["capacity_in"] and a["columns"] >= 1
            # under a sixteenth of the page is kept: positions and a gather
            assert a["capacity_out"] * 16 <= a["capacity_in"]
            assert a["path"] == "index"
            # one row in 32 or 64 of 65,536: the columns' words travel as one matrix
            assert a["gather"] == "packed" and a["words"] >= 2

    def test_a_dense_compaction_states_its_gather_too(self, client):
        """About a row in eleven kept: the positions are sorted, the columns
        follow in one packed gather (the span says so), and the counter of
        compactions ticks."""
        from trino_tpu.runtime import executor as E
        from trino_tpu.runtime.metrics import REGISTRY

        def ticks():
            return REGISTRY.counter(E.COMPACTIONS_COUNTER, {"path": "sort"}).value

        before = ticks()
        res = client.execute(
            "SELECT l_orderkey, sum(l_quantity) FROM lineitem WHERE l_discount = 0.05 GROUP BY l_orderkey"
        )
        compact = [s.attributes for s in finished_tree(res.query_id) if s.name == "compact"]
        assert compact and all(a["capacity_out"] * 16 > a["capacity_in"] for a in compact)
        assert all((a["path"], a["gather"]) == ("sort", "packed") and a["words"] >= 2 for a in compact)
        assert ticks() == before + len(compact)

    def test_a_global_sum_under_a_selective_filter_does_not_compact(self, q06):
        _, tree, _ = q06
        assert not [s for s in tree if s.name == "compact"]
        assert tree[0].attributes["host_syncs"] == 0

    def test_a_scan_that_keeps_its_rows_does_not_compact(self, client):
        tree = finished_tree(client.execute(Q01).query_id)
        assert not [s for s in tree if s.name == "compact"]


class TestClocksAndStats:
    def test_spans_are_on_perf_counter_and_keep_a_true_wall_clock(self, client):
        t0, w0 = time.perf_counter_ns(), time.time_ns()
        res = client.execute("SELECT count(*) FROM nation")
        t1, w1 = time.perf_counter_ns(), time.time_ns()
        tree = finished_tree(res.query_id)
        # the last page's span may end after the client has the page
        spans = [s for s in tree if s.name != "result_stream"][1:]
        assert all(t0 <= s.start_ns <= s.end_ns <= t1 for s in spans)
        assert t0 <= tree[0].start_ns <= t1
        for d in TRACER.trace(res.query_id)[:3]:
            assert d["startNs"] == next(
                s.start_ns for s in tree if s.span_id == d["spanId"]
            )
            assert w0 - 5_000_000 <= d["startTimeUnixNano"] <= w1 + 5_000_000

    def test_protocol_stats_carry_trinos_split(self, client):
        res = client.execute(Q06)
        for key in ("elapsedTimeMillis", "queuedTimeMillis", "planningTimeMillis"):
            assert isinstance(res.stats[key], int), key
        assert res.stats["queuedTimeMillis"] <= res.stats["elapsedTimeMillis"]

    def test_query_stats_are_fed_from_the_tree(self, server, client):
        res = client.execute(Q06)
        tree = finished_tree(res.query_id)
        info = json.loads(urllib.request.urlopen(
            f"http://{server.address}/v1/query/{res.query_id}"
        ).read())
        qs = info["queryStats"]
        by_name = {s.name: (s.end_ns - s.start_ns) / 1e9 for s in tree}
        assert qs["dispatchTime"] == pytest.approx(by_name["execution"], abs=1e-5)
        assert qs["drainTime"] == pytest.approx(by_name["drain"], abs=1e-5)
        assert qs["analysisTime"] == pytest.approx(by_name["planner"], abs=1e-5)
        assert qs["planningTime"] == pytest.approx(
            by_name["parse"] + by_name["planner"] + by_name["optimizer"], abs=1e-5
        )
        assert qs["queuedTime"] == pytest.approx(
            by_name["queue"] + by_name["admit"], abs=1e-5
        )
        # the drain is not the device's busy time, a compile is no analysis
        assert qs["deviceBusyTime"] == 0.0 and qs["compileTime"] >= 0.0
        assert qs["cpuTime"] <= qs["elapsedTime"] + 0.05
        names = [n["name"] for n in info["operatorTree"]]
        assert names == [STATEMENT]


class TestConcurrency:
    def test_three_clients_never_cross_trees(self, server):
        from trino_tpu.client.client import StatementClient

        ids, errors = [], []

        def loop(sql):
            try:
                c = StatementClient(f"http://{server.address}")
                for _ in range(4):
                    ids.append(c.execute(sql).query_id)
            except Exception as e:  # noqa: BLE001 — reported by the assert below
                errors.append(e)

        threads = [
            threading.Thread(target=loop, args=(sql,))
            for sql in (Q06, Q01, "SELECT count(*) FROM orders")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors and len(ids) == 12
        seen = set()
        for qid in ids:
            tree = finished_tree(qid)
            assert tree[0].attributes["query_id"] == qid
            mine = {s.span_id for s in tree}
            assert all(s.trace_id == qid and s.parent_id in mine for s in tree[1:])
            assert sum(s.name == "execution" for s in tree) == 1
            assert not (mine & seen)
            seen |= mine


class TestRootLifetime:
    def test_cancel_closes_the_root(self):
        from trino_tpu.runtime import LocalQueryRunner
        from trino_tpu.runtime.query_manager import QueryManager

        gate = threading.Event()
        runner = LocalQueryRunner.tpch(scale=0.001)

        def slow(sql):
            gate.wait(10)
            return runner.execute(sql)

        qm = QueryManager(slow)
        q = qm.submit("SELECT 1")
        assert q.trace_id == q.query_id and q.stats.root.end_ns is None
        qm.cancel(q.query_id)
        gate.set()
        assert q.stats.root.end_ns is not None
        assert q.stats.root.attributes["canceled"] is True

    def test_expiry_from_the_history_closes_the_root(self):
        from trino_tpu.runtime import LocalQueryRunner
        from trino_tpu.runtime.query_manager import QueryManager

        qm = QueryManager(LocalQueryRunner.tpch(scale=0.001).execute, max_history=1)
        first = qm.submit("SELECT 1")
        assert first.wait_done(30)
        assert first.stats.root.end_ns is None  # no client fetched its page
        assert first.stats.exec_secs > 0 and first.stats.queued_secs >= 0
        second = qm.submit("SELECT 2")
        assert second.wait_done(30)
        deadline = time.monotonic() + 5
        while first.stats.root.end_ns is None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert first.stats.root.attributes.get("expired") is True

    def test_a_runner_without_a_manager_opens_its_own_root(self):
        from trino_tpu.runtime import LocalQueryRunner

        res = LocalQueryRunner.tpch(scale=0.001).execute("SELECT count(*) FROM nation")
        tree = TRACER.spans(res.trace_id)
        root = tree[0]
        assert root.name == STATEMENT and root.end_ns is not None
        # nobody offered a place to run the statistics feedback later: it is
        # inline, the root's last child (tests/test_feedback_deferral.py)
        assert [s.name for s in tree if s.parent_id == root.span_id] == (
            IN_ROOT[2:] + [STATS_FEEDBACK]
        )
        assert root.attributes["host_syncs"] == sum(
            s.name.startswith("sync:") for s in tree
        )


class TestMeshTier:
    def test_mesh_spans_hang_under_the_statements_root(self):
        from trino_tpu.parallel.mesh_runner import MeshQueryRunner

        runner = MeshQueryRunner.tpch(scale=0.001, n_devices=4)
        with TRACER.span(STATEMENT) as root:
            res = runner.execute(
                "SELECT l_returnflag, count(*) FROM lineitem GROUP BY l_returnflag"
            )
        assert len(res.rows) == 3
        tree = TRACER.spans(root.trace_id)
        mine = [s for s in tree if s.parent_id == root.span_id]
        assert [s.name for s in mine] == [
            "parse", "planner", "optimizer", "fragment", "mesh:lower",
            "mesh:program", "mesh:gather",
        ]
        lower, ran = mine[4], mine[5]
        # the resharding lies inside the lowering, the one read inside the program
        inside = [s for s in tree if s.parent_id == lower.span_id]
        assert [s.name for s in inside] == ["mesh:load_scan", "mesh:shard"]
        assert "sync:mesh_measured" in [
            s.name for s in tree if s.parent_id == ran.span_id
        ]
        load, shard = (s.attributes for s in inside)
        program, gather = ran.attributes, mine[6].attributes
        assert load["rows"] > 0 and load["bytes"] > 0
        assert shard["h2d_bytes"] >= load["bytes"]
        assert program["attempt"] == 0 and gather["rows"] == 3
        # without a root of the caller's the runner opens the statement's
        res = runner.execute("SELECT count(*) FROM lineitem")
        own = TRACER.finished()[-1]
        assert own[0].name == STATEMENT
        assert "mesh:program" in [s.name for s in own]


class TestRing:
    def test_the_ring_drops_the_oldest_and_counts_it(self):
        from trino_tpu.runtime.metrics import REGISTRY

        counter = REGISTRY.counter(DROPPED_COUNTER)
        before = counter.value
        tr = Tracer(max_traces=4)
        roots = []
        for i in range(6):
            with tr.span(STATEMENT, n=i) as root:
                with tr.span("execution"):
                    pass
            roots.append(root)
        assert tr.dropped == 2 and counter.value - before == 2
        assert tr.traces() == [r.trace_id for r in roots[2:]]
        assert [t[0].attributes["n"] for t in tr.finished()] == [2, 3, 4, 5]
        assert tr.trace(roots[0].trace_id) == []

    def test_the_process_ring_holds_a_windows_statements(self):
        assert TRACER._max_traces >= 4096

    def test_ids_come_from_a_counter(self):
        tr = Tracer()
        with tr.span("a") as a:
            with tr.span("b") as b:
                pass
        assert int(b.span_id, 16) == int(a.span_id, 16) + 1
        assert len(a.span_id) == 16 and len(a.trace_id) == 32

    def test_a_phase_before_its_statement_is_kept_in_no_tree(self):
        from trino_tpu.runtime.hostprof import phase_span
        from trino_tpu.runtime.observability import RECORDER

        before = len(TRACER.traces())
        with phase_span(RECORDER, "accept", path="/v1/statement") as attributes:
            attributes["query_id"] = "q_x"
            assert TRACER.current().name == "accept"
        assert len(TRACER.traces()) == before and TRACER.current() is None


class TestProfilerTimeline:
    def test_the_same_spans_lie_in_the_profilers_trace(self, client, tmp_path):
        import glob

        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            res = client.execute(Q06_GROUPED)  # it syncs and compacts
            tree = finished_tree(res.query_id)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
        host = next(
            p for p in jax.profiler.ProfileData.from_file(path).planes
            if p.name == "/host:CPU"
        )
        events = [
            (e.name, e.start_ns, e.duration_ns)
            for line in host.lines for e in line.events
            if e.name.startswith("trino:")
        ]
        names = {name for name, _, _ in events}
        assert "trino:statement" in names and "trino:execution" in names
        assert any(n.startswith("trino:op:") for n in names)
        assert any(n.startswith("trino:sync:") for n in names)
        # same intervals on the two clocks: place both against `execution`
        origin = next(s for s in tree if s.name == "execution")
        at = next(start for name, start, _ in events if name == "trino:execution")
        for span in (s for s in tree if s.name.startswith(("op:", "sync:", "compact"))):
            offset = span.start_ns - origin.start_ns
            match = [
                (start, dur) for name, start, dur in events
                if name == "trino:" + span.name
                and abs((start - at) - offset) < 200_000
            ]
            assert match, span.name
            assert match[0][1] == pytest.approx(span.end_ns - span.start_ns, abs=200_000)
        root = tree[0]
        whole = [dur for name, _, dur in events if name == "trino:statement"]
        assert any(
            dur == pytest.approx(root.end_ns - root.start_ns, abs=500_000) for dur in whole
        )


class TestFlightRecorderSink:
    def test_an_export_taken_mid_statement_validates_clean(self, server, client):
        from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

        taken = []

        class MidStatement:
            def query_state_change(self, event):
                # PLANNING is announced inside the open `admit` span
                if TRACER.current() is not None and TRACER.current().name == "admit":
                    taken.append(RECORDER.chrome_trace())

        listener = MidStatement()
        RECORDER.clear()
        RECORDER.enable()
        server.manager.add_listener(listener)
        try:
            client.execute("SELECT count(*) FROM region")
            res = client.execute("SELECT count(*) FROM region")
            finished_tree(res.query_id)
            after = RECORDER.chrome_trace()
        finally:
            server.manager._listeners.remove(listener)
            RECORDER.disable()
        assert len(taken) == 2
        for export in taken:
            assert validate_chrome_trace(export) == []
        # the second export holds the first statement whole, none of the second
        names = [e["name"] for e in taken[1]["traceEvents"] if e.get("ph") == "X"]
        assert names.count("proto_admit") == 1 and names.count("execution") == 1
        assert validate_chrome_trace(after) == []
        done = [e for e in after["traceEvents"] if e.get("ph") == "X"]
        cats = {e["name"]: e["cat"] for e in done}
        assert cats["proto_queue"] == cats["proto_result_stream"] == "protocol"
        assert cats["execution"] == "query" and cats["statement"] == "trace"
        assert any(e["cat"] == "operator" and e["name"].startswith("op:") for e in done)
        assert not [e for e in after["traceEvents"] if e["name"].startswith("proto_")
                    and e.get("ph") != "X"]


def test_cost_of_one_span_with_no_profiler_session(capsys):
    """Printed, not bounded tightly: PERF.md quotes the figure (a statement
    opens about 35 spans)."""
    tr = Tracer()
    n = 20000
    with tr.span(STATEMENT):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with tr.span("op:x"):
                pass
        per_span = (time.perf_counter_ns() - t0) / n
    with capsys.disabled():
        print(f"\ncost of one span, no profiler session: {per_span:.0f} ns")
    assert per_span < 100_000
