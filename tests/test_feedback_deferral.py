"""The statistics feedback runs once the statement is FINISHED (served path).

``LocalQueryRunner`` hands ``statstore.Feedback`` to the place its caller
offers (``QueryManager``: ``deferring_feedback``) and the manager sends it to
its pool when the statement's root closes; a caller that offers none gets it
inline. Readers of what the feedback writes join it first. A coordinator over
HTTP at SF0.01, as tests/test_statement_timeline.py.
"""

import json
import threading
import time
import urllib.request

import pytest

from trino_tpu.runtime import statstore
from trino_tpu.runtime.metrics import REGISTRY
from trino_tpu.runtime.query_manager import QueryState
from trino_tpu.runtime.tracing import STATEMENT, STATS_FEEDBACK, TRACER

SLEEP = 0.8
# distinct literals: each test's statement is its own in the rings
COUNT = "SELECT count(*) FROM lineitem WHERE l_quantity < {}"


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
    c.execute(COUNT.format(1))  # compiled before any test looks at a clock
    statstore.join_pending()
    return c


@pytest.fixture(autouse=True)
def nothing_pending():
    """The last test's statements have fed back before this one counts."""
    statstore.join_pending()


@pytest.fixture()
def slow_feedback(monkeypatch):
    """``observe_query`` takes SLEEP seconds; gives the calls it has begun."""
    real = statstore.observe_query
    begun = []

    def slow(*args, **kwargs):
        begun.append(kwargs.get("query_id"))
        time.sleep(SLEEP)
        return real(*args, **kwargs)

    monkeypatch.setattr(statstore, "observe_query", slow)
    yield begun
    statstore.join_pending()  # none left sleeping into the next test


def _ticks(path: str) -> float:
    return REGISTRY.counter(
        "trino_tpu_stats_feedback_total", labels={"path": path}
    ).value


def _wait(condition, seconds=10.0) -> bool:
    deadline = time.monotonic() + seconds
    while not condition() and time.monotonic() < deadline:
        time.sleep(0.005)
    return condition()


def _last_query(server, sql):
    return next(
        q for q in reversed(server.manager.list_queries()) if q.sql == sql
    )


def _feedback_roots(query_id):
    return [
        tree[0] for tree in TRACER.finished(STATS_FEEDBACK)
        if tree[0].attributes.get("query_id") == query_id
    ]


class TestServedPath:
    def test_the_client_has_its_rows_before_the_feedback_has_run(
        self, server, client, slow_feedback
    ):
        sql = COUNT.format(11)
        before = _ticks("deferred")
        t0 = time.perf_counter()
        res = client.execute(sql)
        took = time.perf_counter() - t0
        assert res.rows and took < SLEEP / 2
        q = _last_query(server, sql)
        assert q.state is QueryState.FINISHED
        # and then it runs, deferred, with nobody asking for it
        assert _wait(lambda: _ticks("deferred") == before + 1)
        assert slow_feedback == [q.query_id]

    def test_its_root_begins_after_the_statements_has_closed(self, server, client):
        sql = COUNT.format(12)
        client.execute(sql)
        q = _last_query(server, sql)
        # nobody joins: the manager's pool runs it, from the root's close
        assert _wait(lambda: _feedback_roots(q.query_id))
        (root,) = _feedback_roots(q.query_id)
        assert root.parent_id is None and root.trace_id != q.query_id
        assert root.attributes["deferred"] is True
        assert root.attributes["nodes"] == 4  # aggregate, project, filter, scan
        statement = q.stats.root
        assert statement.end_ns is not None
        assert root.start_ns >= statement.end_ns
        # the statement's own tree: one root, and no feedback in it
        tree = TRACER.spans(q.query_id)
        assert [s.name for s in tree if s.parent_id is None] == [STATEMENT]
        assert STATS_FEEDBACK not in [s.name for s in tree]
        assert sum(t[0].trace_id == q.query_id for t in TRACER.finished()) == 1

    def test_a_reader_of_operator_stats_joins_it(self, server, client, slow_feedback):
        sql = COUNT.format(13)
        client.execute(sql)
        q = _last_query(server, sql)
        rows = client.execute(
            "SELECT plan_node, actual_rows FROM system.runtime.operator_stats "
            f"WHERE query_id = '{q.query_id}'"
        ).rows
        assert sorted(k for k, _ in rows) == [
            "AggregationNode", "FilterNode", "ProjectNode", "TableScanNode",
        ]
        scanned = client.execute("SELECT count(*) FROM lineitem").rows[0][0]
        assert dict(map(tuple, rows))["TableScanNode"] == scanned
        assert slow_feedback.count(q.query_id) == 1  # joined, not run twice

    def test_a_reader_of_the_history_joins_it(self, server, client, slow_feedback):
        sql = COUNT.format(14)
        client.execute(sql)
        q = _last_query(server, sql)
        runs = client.execute(
            "SELECT count(*) FROM system.optimizer.stats_history"
        ).rows[0][0]
        assert runs > 0 and slow_feedback.count(q.query_id) == 1
        assert q.query_id not in [f.query_id for f in statstore._PENDING]

    def test_the_querys_plan_node_stats_join_it(self, server, client, slow_feedback):
        sql = COUNT.format(15)
        client.execute(sql)
        q = _last_query(server, sql)
        with urllib.request.urlopen(
            f"http://{server.address}/v1/query/{q.query_id}"
        ) as resp:
            info = json.loads(resp.read())
        nodes = info["queryStats"]["planNodeStats"]
        assert {v["kind"] for v in nodes.values()} == {
            "AggregationNode", "FilterNode", "ProjectNode", "TableScanNode",
        }
        assert q.query_stats["planNodes"] == nodes

    def test_a_feedback_that_raises_fails_nothing(self, server, client, monkeypatch):
        def broken(*args, **kwargs):
            raise RuntimeError("feedback broke")

        monkeypatch.setattr(statstore, "observe_query", broken)
        sql = COUNT.format(16)
        before = _ticks("deferred")
        assert client.execute(sql).rows
        q = _last_query(server, sql)
        statstore.join_pending()
        assert q.state is QueryState.FINISHED and q.error is None
        assert _ticks("deferred") == before + 1 and not statstore._PENDING
        # and the next statement is served
        assert client.execute(COUNT.format(17)).rows

    def test_the_next_planning_sees_these_actuals_with_history_on(
        self, server, client, slow_feedback
    ):
        from trino_tpu.planner.plan import FilterNode, visit_plan
        from trino_tpu.planner.stats import make_estimator

        runner = server.runner
        runner.session.set("history_based_stats", True)
        try:
            sql = COUNT.format(18)
            actual = client.execute(sql).rows[0][0]
            q = _last_query(server, sql)
            # what the second statement's planner does: an estimator with
            # the history overlaid, which reads the history and so joins
            plan = runner.plan_sql(sql)
            assert slow_feedback.count(q.query_id) == 1
            filters = []
            visit_plan(
                plan.root,
                lambda n: filters.append(n) if isinstance(n, FilterNode) else None,
            )
            est = make_estimator(runner.metadata, plan.types, runner.session)
            assert est.rows(filters[0]) == float(actual)
            # served: the second statement itself, its feedback reading the
            # history from inside a feedback (no join there, no deadlock)
            assert client.execute(sql).rows[0][0] == actual
            statstore.join_pending()
        finally:
            runner.session.set("history_based_stats", False)

    def test_a_canceled_statement_still_feeds_back(self, server):
        """The root closed before the runner came back with the feedback:
        ``_run_admitted`` sends it to the pool itself."""
        mgr = server.manager
        ran = threading.Event()
        fb = statstore.Feedback(None, None, None, None, lambda: {}, "q_canceled")
        fb._run_once = ran.set

        def execute(sql, **kwargs):
            started.set()
            release.wait(10)
            statstore._feedback_tls.sink.append(fb)
            return real(sql, **kwargs)

        started, release = threading.Event(), threading.Event()
        real, mgr._executor_fn = mgr._executor_fn, execute
        try:
            q = mgr.submit("SELECT 1")
            assert started.wait(10)
            mgr.cancel(q.query_id)
            assert q.stats.root.end_ns is not None
            release.set()
            assert ran.wait(10)
            assert q.state is QueryState.CANCELED
            assert fb in q.feedback and q._feedback_sent == len(q.feedback)
        finally:
            mgr._executor_fn = real


class TestInline:
    def test_a_direct_execute_has_fed_back_when_it_returns(self):
        from trino_tpu.runtime import LocalQueryRunner

        runner = LocalQueryRunner.tpch(scale=0.01)
        inline, deferred = _ticks("inline"), _ticks("deferred")
        res = runner.execute(COUNT.format(21))
        assert _ticks("inline") == inline + 1 and _ticks("deferred") == deferred
        assert not statstore._PENDING
        mine = [
            r for r in statstore._OP_STATS if r["query_id"] == res.trace_id
        ]
        assert len(mine) == 4 == len(res.query_stats["planNodes"])
        span = next(
            s for s in TRACER.spans(res.trace_id) if s.name == STATS_FEEDBACK
        )
        assert span.attributes["deferred"] is False
        assert span.parent_id == TRACER.spans(res.trace_id)[0].span_id
        assert REGISTRY.histogram("trino_tpu_stats_feedback_seconds").count > 0

    def test_explain_analyze_is_inline_under_a_manager(self, client):
        inline, deferred = _ticks("inline"), _ticks("deferred")
        rows = client.execute("EXPLAIN ANALYZE " + COUNT.format(22)).rows
        assert "TableScan" in "\n".join(r[0] for r in rows)
        assert _ticks("inline") == inline + 1 and _ticks("deferred") == deferred

    def test_feedback_off_hands_nothing_over(self, server, client):
        server.runner.session.set("statistics_feedback", False)
        try:
            sql = COUNT.format(23)
            deferred = _ticks("deferred")
            client.execute(sql)
            q = _last_query(server, sql)
            assert q.feedback == [] and not statstore._PENDING
            assert _ticks("deferred") == deferred
            assert q.query_stats["planNodes"] == {}
        finally:
            server.runner.session.set("statistics_feedback", True)


class TestOrder:
    def _feedback(self, log, name, hold=None):
        def finalize():
            if hold is not None:
                hold.wait(10)
            log.append(name)
            return {}

        return statstore.Feedback(None, None, None, None, finalize, name)

    def test_handed_over_first_runs_first_whoever_asks(self):
        log = []
        hold = threading.Event()
        with statstore.deferring_feedback() as sink:
            a = self._feedback(log, "a", hold)
            b = self._feedback(log, "b")
            c = self._feedback(log, "c")
            for fb in (a, b, c):
                fb.defer()
        assert sink == [a, b, c] and list(statstore._PENDING) == sink
        # b is asked for first, from two threads: a runs before it, c after
        threads = [threading.Thread(target=b.run) for _ in range(2)]
        for t in threads:
            t.start()
        hold.set()
        for t in threads:
            t.join(10)
        assert log == ["a", "b"]
        statstore.join_pending()
        assert log == ["a", "b", "c"] and not statstore._PENDING
        b.run()  # done: a second run is nothing, and drains nothing
        assert log == ["a", "b", "c"]

    def test_a_feedback_joins_nothing(self):
        """A thread inside a feedback that reads the history (the overlay
        estimator does) must not wait for feedback: it is one."""
        log = []
        with statstore.deferring_feedback():
            later = self._feedback(log, "later")

            def finalize():
                statstore.load_history()  # a reader, from inside a feedback
                log.append("first")
                return {}

            first = statstore.Feedback(None, None, None, None, finalize, "first")
            first.defer()
            later.defer()
        first.run()
        assert log == ["first"]
        statstore.join_pending()
        assert log == ["first", "later"]

    def test_sixteen_threads_hand_over_run_and_read_at_once(self):
        """More threads than cores and a short switch interval: every
        feedback runs exactly once, a thread's own in the order it handed
        them over, and nobody waits for ever."""
        import sys

        ran, lock = [], threading.Lock()
        errors = []

        def work(t):
            try:
                mine = []
                for i in range(25):
                    def finalize(name=(t, i)):
                        with lock:
                            ran.append(name)
                        return {}

                    with statstore.deferring_feedback():
                        fb = statstore.Feedback(None, None, None, None, finalize, str((t, i)))
                        fb.defer()
                    mine.append(fb)
                    if i % 3 == 0:
                        statstore.operator_stats_log()  # a reader
                    elif i % 3 == 1:
                        mine[i // 2].run()  # the pool, late
                for fb in mine:
                    fb.run()
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work, args=(t,)) for t in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            sys.setswitchinterval(interval)
        assert not errors and not any(t.is_alive() for t in threads)
        assert sorted(ran) == [(t, i) for t in range(16) for i in range(25)]
        for t in range(16):
            assert [i for u, i in ran if u == t] == list(range(25))
        assert not statstore._PENDING

    def test_outside_a_scope_nothing_is_deferred(self):
        assert not statstore.feedback_is_deferred()
        with statstore.deferring_feedback():
            assert statstore.feedback_is_deferred()
        assert not statstore.feedback_is_deferred()
