"""Device batching plane (ISSUE 11, runtime/device_scheduler.py): ragged
multi-query packing, shared-scan elimination, priority admission, and the
bit-identity + failure-isolation contracts that gate it."""

import threading
import time

import pytest

from trino_tpu.runtime.device_scheduler import (
    SCHEDULER,
    _LaunchGate,
    current_priority,
    priority_scope,
)
from trino_tpu.runtime.local import LocalQueryRunner

Q1 = """
    SELECT l_returnflag, l_linestatus, sum(l_quantity), count(*)
    FROM lineitem WHERE l_shipdate <= DATE '1998-09-02'
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus"""
Q3 = """
    SELECT o_orderkey, sum(l_extendedprice)
    FROM lineitem JOIN orders ON l_orderkey = o_orderkey
    WHERE o_orderdate < DATE '1995-03-15'
    GROUP BY o_orderkey ORDER BY 2 DESC, 1 LIMIT 10"""
Q6 = """
    SELECT sum(l_extendedprice * l_discount)
    FROM lineitem
    WHERE l_shipdate >= DATE '1994-01-01'
      AND l_shipdate < DATE '1995-01-01'
      AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"""
Q13 = """
    SELECT c_custkey, count(o_orderkey)
    FROM customer LEFT JOIN orders ON c_custkey = o_custkey
    GROUP BY c_custkey ORDER BY 2 DESC, 1 LIMIT 10"""
MIX = [Q1, Q3, Q6, Q13]


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner.tpch(scale=0.01)


@pytest.fixture(scope="module")
def baselines(runner):
    """Serial, batching-off reference rows for every mix query."""
    return {sql: runner.execute(sql).rows for sql in MIX}


@pytest.fixture
def batching(runner):
    """device_batching=on for the duration of a test, stats reset."""
    runner.session.set("device_batching", True)
    SCHEDULER.reset_stats()
    try:
        yield runner
    finally:
        runner.session.properties.pop("device_batching", None)
        SCHEDULER.reset_stats()


# --------------------------------------------------------------------------- #
# off-path byte-identity (the default must not change at all)
# --------------------------------------------------------------------------- #


class TestDisabledPath:
    def test_off_attaches_nothing_and_never_consults_scheduler(
        self, runner, baselines, monkeypatch
    ):
        def boom(*a, **k):
            raise AssertionError("scheduler consulted with batching off")

        monkeypatch.setattr(SCHEDULER, "execute", boom)
        monkeypatch.setattr(SCHEDULER, "shared_scan", boom)
        assert runner.execute(Q1).rows == baselines[Q1]
        assert runner.execute(Q6).rows == baselines[Q6]

    def test_default_is_off(self, runner):
        assert bool(runner.session.get("device_batching")) is False

    def test_on_off_identical_single_query(self, batching, baselines):
        for sql in MIX:
            assert batching.execute(sql).rows == baselines[sql]


# --------------------------------------------------------------------------- #
# 16-client mixed replay: bit-identity, incl. under chaos
# --------------------------------------------------------------------------- #


def _replay(runner, baselines, n_clients=16, per_client=3):
    """The mixed replay (many clients, several templates) on raw threads;
    asserts every result equals its serial baseline."""
    errors = []
    barrier = threading.Barrier(n_clients)

    def client(cid):
        try:
            barrier.wait(timeout=60)
            for j in range(per_client):
                sql = MIX[(cid + j) % len(MIX)]
                rows = runner.execute(sql).rows
                if rows != baselines[sql]:
                    errors.append(f"client {cid} query {j} diverged")
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errors.append(f"client {cid}: {type(e).__name__}: {e}")

    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(n_clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:5]


class TestMixedReplayBitIdentity:
    def test_16_clients_bit_identical(self, batching, baselines):
        _replay(batching, baselines)
        # the plane actually engaged: scans were shared and/or lanes packed
        assert SCHEDULER.scan_shares > 0 or SCHEDULER.batched_launches > 0

    def test_16_clients_under_task_stall_chaos(self, batching, baselines):
        from trino_tpu.runtime.failure import ChaosInjector

        with ChaosInjector() as chaos:
            chaos.arm("task_stall", times=4, delay=0.05)
            _replay(batching, baselines, n_clients=8, per_client=2)

    def test_mid_batch_kill_fails_only_victim_lanes(self, baselines):
        """A low-memory kill landing while batched lanes are in flight must
        fail ONLY the victim's queries: survivors stay bit-identical and no
        query fails for any reason other than the administrative kill."""
        from trino_tpu.runtime.failure import ChaosInjector
        from trino_tpu.runtime.memory import (
            ClusterMemoryManager,
            MemoryPool,
            TotalReservationOnBlockedNodesLowMemoryKiller,
            memory_scope,
        )
        from trino_tpu.runtime.query_manager import QueryManager, QueryState

        runner = LocalQueryRunner.tpch(scale=0.01)
        runner.session.set("device_batching", True)
        probe = MemoryPool(0, name="batch_probe")
        with memory_scope("probe", probe):
            for sql in MIX:
                runner.execute(sql)
        pool = MemoryPool(
            3 * probe.peak_bytes, name="batch_kill", reserve_timeout=120
        )
        cm = ClusterMemoryManager(
            pool, killer=TotalReservationOnBlockedNodesLowMemoryKiller(),
            spill_after=0.0, kill_after=0.001,
        )
        mgr = QueryManager(runner.execute, max_workers=16, cluster_memory=cm)
        SCHEDULER.reset_stats()
        with ChaosInjector() as chaos:
            # phantom pool pressure on top of real overload: the killer
            # fires while batched lanes from many queries are in flight
            chaos.arm(
                "memory_pressure", times=2,
                bytes=2 * probe.peak_bytes, hold=0.05,
            )
            qs = [mgr.submit(MIX[i % len(MIX)]) for i in range(24)]
            for q in qs:
                assert q.wait_done(300), f"query {q.query_id} WEDGED"
        finished = [q for q in qs if q.state is QueryState.FINISHED]
        unexpected = [
            q for q in qs
            if q.state is not QueryState.FINISHED
            and q.error_type != "AdministrativelyKilled"
        ]
        assert not unexpected, (
            f"non-kill failures: {[(q.error_type, q.error) for q in unexpected]}"
        )
        assert finished, "everything was killed"
        for q in finished:
            assert q.rows == baselines[q.sql], f"survivor {q.query_id} diverged"
        assert pool.reserved_bytes == 0 and pool.revocable_bytes == 0


# --------------------------------------------------------------------------- #
# shared-scan elimination
# --------------------------------------------------------------------------- #


class TestSharedScans:
    def test_16_concurrent_overlapping_queries_one_leaf_scan(
        self, batching, baselines
    ):
        """16 concurrent identical queries -> their lineitem leaf scan
        executes a small constant number of times (the flight winner plus
        at most stragglers that missed the linger window), NOT 16."""
        batching.execute(Q1)  # warm compile so the burst overlaps
        SCHEDULER.reset_stats()
        errors = []
        barrier = threading.Barrier(16)

        def go(i):
            try:
                barrier.wait(timeout=60)
                if batching.execute(Q1).rows != baselines[Q1]:
                    errors.append(f"{i} diverged")
            except Exception as e:  # noqa: BLE001
                errors.append(f"{i}: {e}")

        threads = [threading.Thread(target=go, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:5]
        total = SCHEDULER.scan_executions + SCHEDULER.scan_shares
        assert total >= 16
        assert SCHEDULER.scan_shares >= 12, (
            f"shared-scan elimination barely engaged: "
            f"executions={SCHEDULER.scan_executions} "
            f"shares={SCHEDULER.scan_shares}"
        )
        assert SCHEDULER.scan_executions <= 4

    def test_never_shares_across_dml(self, baselines):
        """A post-INSERT arrival must never see the pre-INSERT page: the
        scan key carries the connector version token."""
        from trino_tpu.connectors.memory import MemoryConnector

        runner = LocalQueryRunner.tpch(scale=0.01)
        runner.register_catalog("mem", MemoryConnector())
        runner.execute("CREATE TABLE mem.default.kv (x bigint)")
        runner.execute("INSERT INTO mem.default.kv VALUES (1), (2)")
        runner.session.set("device_batching", True)
        q = "SELECT count(*) FROM mem.default.kv"
        assert runner.execute(q).rows == [(2,)]
        runner.execute("INSERT INTO mem.default.kv VALUES (3)")
        assert runner.execute(q).rows == [(3,)]

    def test_time_travel_pin_never_shares_with_current(self, tmp_path):
        """Regression (review finding): a FOR VERSION scan must key
        separately from a current-version scan of the same table — the
        pinned snapshot rides the shared-scan key."""
        from trino_tpu.connectors.iceberg_lite import IcebergLiteConnector
        from trino_tpu.fs import FileSystemManager, LocalFileSystem

        fsm = FileSystemManager()
        fsm.register("local", lambda: LocalFileSystem(str(tmp_path)))
        r = LocalQueryRunner.tpch(scale=0.01)
        r.register_catalog("berg", IcebergLiteConnector(fsm, "local://wh"))
        r.execute("CREATE TABLE berg.default.kv AS SELECT 1 AS x")
        r.execute("INSERT INTO berg.default.kv VALUES (2)")
        r.session.set("device_batching", True)
        SCHEDULER.reset_stats()
        cur = "SELECT count(*) FROM berg.default.kv"
        pin = "SELECT count(*) FROM berg.default.kv FOR VERSION AS OF 1"
        assert r.execute(cur).rows == [(2,)]
        # within the shared-scan TTL: the pinned read must NOT be served
        # the current scan's pages
        assert r.execute(pin).rows == [(1,)]
        assert r.execute(cur).rows == [(2,)]

    def test_scan_winner_failure_falls_back(self, batching, monkeypatch):
        """A dying scan winner publishes its error; the next arrival
        executes the scan itself instead of inheriting the failure or
        wedging. Exercised directly on the scheduler API with a pinned
        scan key."""
        from trino_tpu.runtime import device_scheduler as ds

        calls = {"n": 0}
        entry_key = ("t", "s", "l:x", "v", ("a",))
        monkeypatch.setattr(
            ds.DeviceScheduler, "_scan_key", lambda self, b, n: entry_key
        )

        class _Node:
            assignments = (("sym_a", "a"),)

        class _Rel:
            page = object()
            symbols = ("sym_a",)
            sorted_by = ()

        class _B:
            metadata = None
            scope = ""
            registry = ""

        def failing_inner(node):
            calls["n"] += 1
            raise RuntimeError("scan died")

        with pytest.raises(RuntimeError):
            SCHEDULER.shared_scan(_B(), None, _Node(), failing_inner)
        # the failed flight is not served to the next caller: it executes
        ok_rel = _Rel()

        def ok_inner(node):
            calls["n"] += 1
            return ok_rel

        assert SCHEDULER.shared_scan(_B(), None, _Node(), ok_inner) is ok_rel
        assert calls["n"] == 2


# --------------------------------------------------------------------------- #
# ragged multi-lane packing
# --------------------------------------------------------------------------- #


class TestRaggedPacking:
    def test_fte_partitions_pack_into_one_ragged_launch(self):
        """Concurrent FTE task attempts of one fragment (same program,
        DIFFERENT split data per partition) are the genuine ragged case:
        they pack into a multi-lane vmapped launch, bit-identical to the
        batching-off run."""
        from trino_tpu.parallel.runner import DistributedQueryRunner

        dr = DistributedQueryRunner.tpch(
            scale=0.01, n_workers=4, split_target_rows=4096
        )
        dr.session.set("retry_policy", "TASK")
        off = dr.execute(Q1).rows
        dr.session.set("device_batching", True)
        # a wide admission window: concurrent attempts must land in one
        # group even when this box's scheduler staggers their dispatch
        dr.session.set("batch_admit_window_ms", 100.0)
        packed = False
        for _ in range(3):  # dispatch timing on a 1-core box can drift
            SCHEDULER.reset_stats()
            on = dr.execute(Q1).rows
            assert on == off
            if SCHEDULER.batched_launches >= 1:
                packed = True
                break
        assert packed, (
            f"no ragged launch in 3 runs: singles={SCHEDULER.single_launches}"
        )

    def test_lane_occupancy_histogram_observes(self, batching, baselines):
        from trino_tpu.runtime.metrics import REGISTRY

        h = REGISTRY.histogram(
            "trino_tpu_batch_lane_occupancy", buckets=[1, 2, 4, 8, 16, 32]
        )
        before = h.count
        _replay(batching, baselines, n_clients=4, per_client=1)
        assert h.count > before

    def test_batched_launch_counts_strictly_fewer(self, runner, baselines):
        """The attribution metric: the same concurrent burst dispatches
        strictly fewer device programs with batching on."""
        from trino_tpu.runtime.device_scheduler import program_launches

        runner.execute(Q1)  # warm
        n0 = program_launches()
        _replay(runner, baselines, n_clients=8, per_client=1)
        off_launches = program_launches() - n0
        runner.session.set("device_batching", True)
        try:
            runner.execute(Q1)  # warm the batched path
            SCHEDULER.reset_stats()
            n1 = program_launches()
            _replay(runner, baselines, n_clients=8, per_client=1)
            on_launches = program_launches() - n1
        finally:
            runner.session.properties.pop("device_batching", None)
        assert on_launches < off_launches, (
            f"batching on dispatched {on_launches} programs vs "
            f"{off_launches} off"
        )


# --------------------------------------------------------------------------- #
# priority admission
# --------------------------------------------------------------------------- #


class TestPriorityAdmission:
    def test_gate_admits_highest_weight_first(self):
        gate = _LaunchGate()
        order = []
        gate.acquire(1.0)  # hold the gate
        ready = threading.Barrier(3)

        def waiter(name, weight):
            ready.wait(timeout=30)
            time.sleep({"low": 0.0, "high": 0.05}[name])  # low queues FIRST
            gate.acquire(weight)
            order.append(name)
            gate.release()

        ts = [
            threading.Thread(target=waiter, args=("low", 1.0)),
            threading.Thread(target=waiter, args=("high", 8.0)),
        ]
        for t in ts:
            t.start()
        ready.wait(timeout=30)
        time.sleep(0.3)  # both queued behind the held gate
        gate.release()
        for t in ts:
            t.join(30)
        assert order == ["high", "low"], order

    def test_priority_scope_rides_the_thread(self):
        assert current_priority() == 1.0
        with priority_scope(7):
            assert current_priority() == 7.0
            with priority_scope(2):
                assert current_priority() == 2.0
            assert current_priority() == 7.0
        assert current_priority() == 1.0

    def test_fair_executor_drains_heavier_group_first(self):
        """Regression (ISSUE 11 satellite): the per-query FIFO used to
        ignore resource-group weight when popping — with equal accumulated
        usage, the weight-4 query's task must pop BEFORE the weight-1
        query's even though it was submitted later."""
        from trino_tpu.server.worker import FairTaskExecutor

        ex = FairTaskExecutor(n_threads=1)
        try:
            done = threading.Event()

            def prime():
                time.sleep(0.05)

            # both queries accrue ~equal usage so the weighted key decides
            for q, w in (("qa", 1.0), ("qb", 4.0)):
                fin = threading.Event()

                def task(fin=fin):
                    prime()
                    fin.set()

                ex.submit(q, f"{q}_prime", task, weight=w)
                assert fin.wait(30)
            blocker_go = threading.Event()
            blocked = threading.Event()

            def blocker():
                blocked.set()
                blocker_go.wait(30)

            ex.submit("qc", "qc_block", blocker)
            assert blocked.wait(30)
            order = []

            def mk(name):
                def run():
                    order.append(name)
                    if len(order) == 2:
                        done.set()
                return run

            # qa submitted FIRST; qb's weight must still pop it first
            ex.submit("qa", "qa_t", mk("qa"), weight=1.0)
            ex.submit("qb", "qb_t", mk("qb"), weight=4.0)
            blocker_go.set()
            assert done.wait(30)
            assert order == ["qb", "qa"], order
        finally:
            ex.stop()

    def test_task_descriptor_carries_priority(self):
        from trino_tpu.server.worker import (
            TaskDescriptor,
            decode_task,
            encode_task,
        )

        desc = TaskDescriptor(root=None, types={}, priority=4.0)
        assert decode_task(encode_task(desc)).priority == 4.0
        # default stays off the wire and decodes to 1.0
        d2 = decode_task(encode_task(TaskDescriptor(root=None, types={})))
        assert d2.priority == 1.0


# --------------------------------------------------------------------------- #
# knobs
# --------------------------------------------------------------------------- #


class TestKnobs:
    def test_declared_in_registry(self):
        from trino_tpu.knobs import SESSION_PROPERTIES

        names = {p.name for p in SESSION_PROPERTIES}
        assert {
            "device_batching", "batch_max_lanes", "batch_admit_window_ms",
        } <= names

    def test_batching_knobs_do_not_split_cache_keys(self, runner):
        from trino_tpu.metadata import Session
        from trino_tpu.runtime.cachestore import session_props_key

        a = Session(catalog="tpch", schema="sf0_01")
        b = Session(catalog="tpch", schema="sf0_01")
        b.set("device_batching", True)
        b.set("batch_max_lanes", 4)
        assert session_props_key(a) == session_props_key(b)

    def test_plan_flight_shares_and_gates(self, batching, baselines):
        """Concurrent identical statements share one planning pass; the
        plan-cache correctness gates (nondeterministic text) bypass it."""
        batching.execute(Q6)  # prime
        SCHEDULER.reset_stats()
        errors = []
        barrier = threading.Barrier(8)

        def go(i):
            try:
                barrier.wait(timeout=60)
                if batching.execute(Q6).rows != baselines[Q6]:
                    errors.append(f"{i} diverged")
            except Exception as e:  # noqa: BLE001
                errors.append(f"{i}: {e}")

        threads = [threading.Thread(target=go, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        assert SCHEDULER.plans_shared > 0
        # nondeterministic text must never ride a shared plan
        n0 = SCHEDULER.plans_shared
        r1 = batching.execute("SELECT random() < 2 FROM nation LIMIT 1")
        r2 = batching.execute("SELECT random() < 2 FROM nation LIMIT 1")
        assert r1.rows == r2.rows == [(True,)]
        assert SCHEDULER.plans_shared == n0

    def test_plan_flight_never_keys_execute_text(self, batching):
        """Regression (review finding): re-PREPAREing a name with a new
        body and EXECUTE-ing within the linger window must never serve the
        OLD body's plan — EXECUTE text never keys a plan flight."""
        batching.execute("PREPARE pf FROM SELECT count(*) FROM nation")
        r1 = batching.execute("EXECUTE pf")
        batching.execute("PREPARE pf FROM SELECT count(*) FROM region")
        r2 = batching.execute("EXECUTE pf")
        assert r1.rows == [(25,)]
        assert r2.rows == [(5,)]

    def test_max_lanes_one_still_correct(self, runner, baselines):
        runner.session.set("device_batching", True)
        runner.session.set("batch_max_lanes", 1)
        try:
            assert runner.execute(Q1).rows == baselines[Q1]
        finally:
            runner.session.properties.pop("device_batching", None)
            runner.session.properties.pop("batch_max_lanes", None)
