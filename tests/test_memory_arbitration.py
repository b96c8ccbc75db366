"""Cluster memory arbitration (runtime/memory.py): context accounting +
rollback, blocking pool reservations (backpressure), revocable spill, the
low-memory killer, resource-group soft memory limits, the system tables, and
the overload chaos suite (N >> pool concurrent queries: killer fires,
survivors bit-identical, zero wedges)."""

import threading
import time

import numpy as np
import pytest

from trino_tpu.runtime.failure import ChaosInjector
from trino_tpu.runtime.local import LocalQueryRunner
from trino_tpu.runtime.memory import (
    AggregatedMemoryContext,
    ClusterMemoryManager,
    ExceededMemoryLimitError,
    MemoryPool,
    NoneLowMemoryKiller,
    QueryKilledError,
    QueryMemoryInfo,
    TotalReservationLowMemoryKiller,
    TotalReservationOnBlockedNodesLowMemoryKiller,
    memory_scope,
    page_bytes,
    parse_bytes,
)
from trino_tpu.runtime.observability import RECORDER
from trino_tpu.runtime.query_manager import QueryManager, QueryState

SCALE = 0.001

# the sustained-concurrency mix (Q1/Q3/Q6/Q13 shapes): deterministic orders
# so solo-vs-overload results compare bit-identically
Q1 = """
SELECT l_returnflag, l_linestatus, sum(l_quantity), count(*)
FROM lineitem WHERE l_shipdate <= DATE '1998-09-02'
GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus
"""
Q3 = """
SELECT o_orderkey, sum(l_extendedprice)
FROM lineitem JOIN orders ON l_orderkey = o_orderkey
WHERE o_orderdate < DATE '1995-03-15'
GROUP BY o_orderkey ORDER BY 2 DESC, 1 LIMIT 10
"""
Q6 = """
SELECT sum(l_extendedprice * l_discount)
FROM lineitem
WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01'
  AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24
"""
Q13 = """
SELECT c_custkey, count(o_orderkey)
FROM customer LEFT JOIN orders ON c_custkey = o_custkey
GROUP BY c_custkey ORDER BY 2 DESC, 1 LIMIT 10
"""
MIX = [Q1, Q3, Q6, Q13]


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner.tpch(scale=SCALE)


@pytest.fixture(scope="module")
def solo(runner):
    """Solo baselines + the per-query pool peak, measured on an unbounded
    accounting pool — the overload pool is sized from these."""
    baselines = {}
    peaks = []
    for i, sql in enumerate(MIX):
        probe = MemoryPool(0, name=f"probe{i}")
        with memory_scope(f"probe{i}", probe):
            res = runner.execute(sql)
        baselines[sql] = res.rows
        peaks.append(probe.peak_bytes)
    assert min(peaks) > 0, "accounting recorded nothing"
    return baselines, max(peaks)


# --------------------------------------------------------------------------- #
# contexts (satellite: rollback regression, concurrency, peaks, page_bytes)
# --------------------------------------------------------------------------- #


class TestMemoryContexts:
    def test_limit_exceed_rolls_back(self):
        # regression: the old _update mutated _bytes before raising, leaving
        # the query (and the child local) permanently inflated — spill/retry
        # paths then saw phantom usage
        root = AggregatedMemoryContext(limit_bytes=1000)
        a = root.new_local("op_a")
        a.set_bytes(800)
        b = root.new_local("op_b")
        with pytest.raises(ExceededMemoryLimitError):
            b.set_bytes(500)
        assert root.reserved_bytes == 800
        assert b.get_bytes() == 0
        # usage is true, so a smaller reservation still fits
        b.set_bytes(150)
        assert root.reserved_bytes == 950

    def test_limit_exceed_rolls_back_pool(self):
        pool = MemoryPool(0, name="p")
        root = AggregatedMemoryContext(limit_bytes=100, pool=pool, owner="q")
        with pytest.raises(ExceededMemoryLimitError):
            root.new_local("op").set_bytes(200)
        assert pool.reserved_bytes == 0

    def test_concurrent_reservations(self):
        root = AggregatedMemoryContext()
        pool = MemoryPool(0, name="c")
        attached = AggregatedMemoryContext(pool=pool, owner="q")
        n_threads, n_iters = 8, 200

        def work(ctx):
            local = ctx.new_local("op")
            for i in range(n_iters):
                local.add_bytes(7)
            local.add_bytes(-3 * n_iters)

        threads = [
            threading.Thread(target=work, args=(ctx,))
            for ctx in (root, attached)
            for _ in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        expected = n_threads * n_iters * 4
        assert root.reserved_bytes == expected
        assert attached.reserved_bytes == expected
        assert pool.reserved_bytes == expected
        assert root.peak_bytes >= expected

    def test_peak_tracking(self):
        root = AggregatedMemoryContext()
        a = root.new_local("a")
        a.set_bytes(900)
        a.set_bytes(100)
        assert root.reserved_bytes == 100
        assert root.peak_bytes == 900

    def test_revocable_separate_and_exempt_from_limit(self):
        root = AggregatedMemoryContext(limit_bytes=100)
        r = root.new_local("parked", revocable=True)
        r.set_bytes(1_000_000)  # revocable is not charged to the query limit
        assert root.revocable_bytes == 1_000_000
        assert root.reserved_bytes == 0
        assert root.total_bytes == 1_000_000

    def test_close_frees_pool(self):
        pool = MemoryPool(0, name="f")
        ctx = AggregatedMemoryContext(pool=pool, owner="q")
        ctx.new_local("a").set_bytes(500)
        ctx.new_local("b", revocable=True).set_bytes(300)
        assert pool.reserved_bytes == 500 and pool.revocable_bytes == 300
        ctx.close()
        assert pool.reserved_bytes == 0 and pool.revocable_bytes == 0

    def test_page_bytes_plain(self):
        from trino_tpu.spi.page import Column, Page
        from trino_tpu.spi.types import BIGINT

        import jax.numpy as jnp

        col = Column.from_numpy(BIGINT, np.arange(100), capacity=128)
        page = Page((col,), jnp.asarray(np.arange(128) < 100))
        # 128*8 data + 128 valid + 128 active
        assert page_bytes(page) == 128 * 8 + 128 + 128

    def test_page_bytes_dictionary_encoded(self):
        from trino_tpu.spi.page import Column, Page

        import jax.numpy as jnp

        col = Column.from_strings(["aa", "bb", "aa", None], capacity=8)
        page = Page((col,), jnp.asarray(np.arange(8) < 4))
        n = page_bytes(page)
        # int32 codes + valid + active + the host dictionary values
        assert n >= 8 * 4 + 8 + 8 + len("aa") + len("bb")
        # two columns SHARING one dictionary count it once
        col2 = Column.from_strings(
            ["aa", "bb", "bb", None], capacity=8, dictionary=col.dictionary
        )
        page2 = Page((col, col2), jnp.asarray(np.arange(8) < 4))
        assert page_bytes(page2) == n + 8 * 4 + 8

    def test_page_bytes_zero_row_page(self):
        from trino_tpu.spi.page import Column, Page
        from trino_tpu.spi.types import BIGINT

        import jax.numpy as jnp

        col = Column.from_numpy(BIGINT, np.zeros(0, dtype=np.int64),
                                capacity=1)
        page = Page((col,), jnp.zeros((1,), dtype=jnp.bool_))
        assert page_bytes(page) == 8 + 1 + 1

    def test_parse_bytes(self):
        assert parse_bytes("512MB") == 512 << 20
        assert parse_bytes("2GB") == 2 << 30
        assert parse_bytes("4096") == 4096
        assert parse_bytes("1.5kB") == 1536
        assert parse_bytes("") == 0
        assert parse_bytes("nonsense") == 0

    def test_query_max_memory_env_is_late_bound(self, monkeypatch):
        # the env default must take effect even when set AFTER import
        # (monkeypatch/embedding apps), like the pool-size knob
        from trino_tpu.metadata import Session

        s = Session()
        assert s.get("query_max_memory_bytes") == 0
        monkeypatch.setenv("TRINO_TPU_QUERY_MAX_MEMORY", "64MB")
        assert s.get("query_max_memory_bytes") == 64 << 20
        s.set("query_max_memory_bytes", 123)  # explicit SET wins over env
        assert s.get("query_max_memory_bytes") == 123

    def test_page_bytes_dictionary_size_memoized(self):
        from trino_tpu.spi.page import Column, Page

        import jax.numpy as jnp

        col = Column.from_strings(["xx", "yyy"], capacity=4)
        page = Page((col,), jnp.asarray(np.arange(4) < 2))
        n1 = page_bytes(page)
        assert col.dictionary._host_bytes == len("xx") + len("yyy")
        assert page_bytes(page) == n1  # cached sweep, same answer


# --------------------------------------------------------------------------- #
# the pool: blocking, dooming, revoking
# --------------------------------------------------------------------------- #


class TestMemoryPool:
    def test_blocking_reserve_unblocks_on_peer_free(self):
        pool = MemoryPool(1000, name="b", reserve_timeout=10)
        pool.reserve("qa", 800)
        granted = threading.Event()

        def blocked():
            pool.reserve("qb", 600)  # blocks: 800 + 600 > 1000
            granted.set()

        t = threading.Thread(target=blocked)
        t.start()
        time.sleep(0.1)
        assert not granted.is_set()
        assert pool.snapshot()["blockedReservations"] == 1
        pool.reserve("qa", -700)  # peer releases
        assert granted.is_set() or granted.wait(5)
        t.join()
        assert pool.reserved_bytes == 100 + 600

    def test_blocking_reserve_times_out(self):
        pool = MemoryPool(100, name="t")
        pool.reserve("qa", 100)
        t0 = time.monotonic()
        with pytest.raises(ExceededMemoryLimitError, match="exhausted"):
            pool.reserve("qb", 50, timeout=0.2)
        assert time.monotonic() - t0 >= 0.15
        assert pool.reserved_bytes == 100  # nothing booked for qb

    def test_doom_aborts_blocked_reservation(self):
        pool = MemoryPool(100, name="d", reserve_timeout=10)
        pool.reserve("qa", 100)
        failed = []

        def blocked():
            try:
                pool.reserve("qb", 50)
            except QueryKilledError as e:
                failed.append(str(e))

        t = threading.Thread(target=blocked)
        t.start()
        time.sleep(0.05)
        pool.doom("qb", "killed by test")
        t.join(5)
        assert failed == ["killed by test"]
        # new reservations are refused until the owner is freed
        with pytest.raises(QueryKilledError):
            pool.reserve("qb", 1)
        pool.free_owner("qb")
        pool.reserve("qa", -100)
        pool.reserve("qb", 1)  # re-admitted after the sweep

    def test_revocable_never_blocks(self):
        pool = MemoryPool(100, name="r")
        pool.reserve("qa", 90)
        pool.reserve("qa", 500, revocable=True)  # overcommits, returns at once
        assert pool.revocable_bytes == 500

    def test_request_revoke_frees_via_revoker(self):
        pool = MemoryPool(1000, name="rv")
        ctx = AggregatedMemoryContext(pool=pool, owner="qa")
        parked = ctx.new_local("parked", revocable=True)
        parked.set_bytes(600)

        class Revoker:
            def revoke(self, nbytes):
                freed = parked.get_bytes()
                parked.set_bytes(0)
                return freed

        rv = Revoker()
        pool.add_revoker(rv)
        freed = pool.request_revoke(100)
        assert freed == 600
        assert pool.revocable_bytes == 0

    def test_free_owner_sweeps_everything(self):
        pool = MemoryPool(0, name="s")
        pool.reserve("qa", 100)
        pool.reserve("qa", 50, revocable=True)
        assert pool.free_owner("qa") == 150
        assert pool.reserved_bytes == 0 and pool.revocable_bytes == 0

    def test_memory_pressure_chaos_blocks_then_completes(self):
        # the memory_pressure site at pool level: phantom pressure fills the
        # pool, the real reservation BLOCKS (flight span), the phantom
        # releases, the reservation is granted — backpressure, not failure
        pool = MemoryPool(1000, name="chaos", reserve_timeout=10)
        RECORDER.clear()
        RECORDER.enable()
        try:
            with ChaosInjector() as chaos:
                chaos.arm("memory_pressure", times=1, bytes=1000, hold=0.2)
                t0 = time.monotonic()
                pool.reserve("qa", 500)
                waited = time.monotonic() - t0
        finally:
            RECORDER.disable()
        assert chaos.fired.get("memory_pressure") == 1
        assert waited >= 0.1, "reservation did not block under pressure"
        assert pool.reserved_bytes == 500
        events = RECORDER.events()
        RECORDER.clear()
        b = [e for e in events
             if e["name"] == "memory_reserve_blocked" and e["ph"] == "B"]
        e_ = [e for e in events
              if e["name"] == "memory_reserve_blocked" and e["ph"] == "E"]
        assert len(b) == 1 and len(e_) == 1, "blocked span missing/unpaired"
        assert e_[0]["args"]["outcome"] == "granted"


# --------------------------------------------------------------------------- #
# killer policies + cluster manager
# --------------------------------------------------------------------------- #


def _info(owner, user=0, revocable=0, blocked=0, seq=0, doomed=False,
          system=False):
    return QueryMemoryInfo(owner, user, revocable, blocked, seq, doomed, system)


class TestLowMemoryKiller:
    def test_total_reservation_picks_biggest(self):
        k = TotalReservationLowMemoryKiller()
        assert k.choose_victim(
            [_info("a", 100), _info("b", 900), _info("c", 500)]
        ) == "b"

    def test_tie_breaks_to_youngest(self):
        k = TotalReservationLowMemoryKiller()
        assert k.choose_victim(
            [_info("old", 500, seq=1), _info("young", 500, seq=9)]
        ) == "young"

    def test_blocked_nodes_variant_needs_blocked(self):
        k = TotalReservationOnBlockedNodesLowMemoryKiller()
        infos = [_info("a", 900), _info("b", 100)]
        assert k.choose_victim(infos) is None  # nothing blocked: no kill
        infos.append(_info("c", 0, blocked=1))
        assert k.choose_victim(infos) == "a"

    def test_excludes_system_doomed_and_empty(self):
        k = TotalReservationLowMemoryKiller()
        assert k.choose_victim([
            _info("_chaos_pressure", 9999, system=True),
            _info("dying", 5000, doomed=True),
            _info("waiting", 0, blocked=1),
            _info("real", 10),
        ]) == "real"

    def test_none_killer(self):
        assert NoneLowMemoryKiller().choose_victim([_info("a", 1)]) is None


class TestClusterMemoryManager:
    def test_escalation_revoke_then_kill(self):
        # single-threaded: the blocked reserver itself drives the arbiter —
        # first the revoker spills, later the killer sheds the biggest query
        pool = MemoryPool(1000, name="esc", reserve_timeout=10)
        killed = []
        cm = ClusterMemoryManager(
            pool,
            kill_fn=lambda q, r: (killed.append((q, r)), pool.free_owner(q)),
            spill_after=0.0, kill_after=0.05,
        )
        ctx_a = AggregatedMemoryContext(pool=pool, owner="qa")
        parked = ctx_a.new_local("parked", revocable=True)
        parked.set_bytes(600)

        class Revoker:
            def revoke(self, nbytes):
                freed = parked.get_bytes()
                parked.set_bytes(0)
                return freed

        rv = Revoker()
        pool.add_revoker(rv)
        # blocks (600 revocable + 700 > 1000) -> arbiter revokes qa -> fits
        AggregatedMemoryContext(pool=pool, owner="qb").new_local("op").set_bytes(700)
        assert pool.revocable_bytes == 0 and not killed
        # blocks (700 + 700 > 1000), nothing left to revoke -> killer sheds
        # the biggest holder (qb)
        AggregatedMemoryContext(pool=pool, owner="qc").new_local("op").set_bytes(700)
        assert [q for q, _ in killed] == ["qb"]
        assert "low-memory killer" in killed[0][1]
        assert cm.kills_total == 1
        assert pool.reserved_bytes == 700  # qc granted after the kill

    def test_killer_skips_unkillable_owners(self):
        # a shared process pool can hold owners kill_fn cannot act on (e.g.
        # worker TASK ids): kill_fn raising must mark them unkillable — not
        # doom them — and the next poke picks the next-biggest real query
        pool = MemoryPool(1000, name="uk", reserve_timeout=10)
        killed = []

        def kill_fn(owner, reason):
            if owner.startswith("task"):
                raise KeyError(owner)  # not a query this manager tracks
            killed.append(owner)
            pool.free_owner(owner)

        ClusterMemoryManager(
            pool, kill_fn=kill_fn, spill_after=0.0, kill_after=0.02
        )
        pool.reserve("task1", 600)  # biggest owner, but not a query
        pool.reserve("qa", 350)
        # qb blocks: the killer tries task1 (biggest), learns it is
        # unkillable, then sheds qa — and task1 is never doomed
        AggregatedMemoryContext(pool=pool, owner="qb").new_local(
            "op"
        ).set_bytes(300)
        assert killed == ["qa"]
        assert pool.reserved_bytes == 600 + 300
        pool.reserve("task1", 1)  # not doomed: still reserves fine

    def test_pool_listeners_do_not_pin_managers(self):
        # bound-method listeners are held weakly: the process default pool
        # outlives any one QueryManager and must not leak dead ones
        import gc
        import weakref

        pool = MemoryPool(0, name="wl")

        class Owner:
            def __init__(self):
                self.calls = []

            def on_change(self, owner, delta, revocable):
                self.calls.append(delta)

        o = Owner()
        pool.add_listener(o.on_change)
        pool.reserve("q", 10)
        assert o.calls == [10]
        ref = weakref.ref(o)
        del o
        gc.collect()
        assert ref() is None, "pool listener pinned its owner"
        pool.reserve("q", 5)  # dead listener pruned without error


# --------------------------------------------------------------------------- #
# resource groups: soft memory limit
# --------------------------------------------------------------------------- #


class TestResourceGroupSoftMemory:
    def make(self, soft=1000):
        from trino_tpu.runtime.resource_groups import (
            ResourceGroupManager,
            ResourceGroupSpec,
            SelectorSpec,
        )

        spec = ResourceGroupSpec(
            name="g", hard_concurrency_limit=4, max_queued=10,
            soft_memory_limit_bytes=soft,
        )
        return ResourceGroupManager([spec], [SelectorSpec(group=("g",))])

    def test_over_memory_stops_dequeue_release_restarts(self):
        m = self.make(soft=1000)
        t1 = m.submit("u")
        assert t1.admitted
        m.note_memory("g", 1500)  # over the share: queue, don't run
        t2 = m.submit("u")
        assert not t2.admitted
        m.note_memory("g", -600)  # 900 < 1000: dequeue restarts on release
        assert t2.event.wait(1) and t2.admitted
        m.finish(t2)
        m.finish(t1)
        assert m.info()["subGroups"][0]["memoryUsageBytes"] == 900

    def test_from_config_parses_soft_limit(self):
        from trino_tpu.runtime.resource_groups import ResourceGroupManager

        m = ResourceGroupManager.from_config({
            "rootGroups": [{
                "name": "etl", "hardConcurrencyLimit": 2,
                "softMemoryLimit": "1MB",
            }],
            "selectors": [{"group": "etl"}],
        })
        t = m.submit("u")
        assert t.admitted
        m.note_memory("etl", 1 << 20)
        assert not m.submit("u").admitted  # memory-parked at exactly the limit
        m.finish(t)

    def test_flat_info_rows(self):
        m = self.make()
        t = m.submit("u")
        rows = {r["id"]: r for r in m.flat_info()}
        assert rows["g"]["running"] == 1
        assert rows["g"]["softMemoryLimitBytes"] == 1000
        m.finish(t)


# --------------------------------------------------------------------------- #
# revocable spiller integration
# --------------------------------------------------------------------------- #


def _make_page(rows=100, cap=128):
    import jax.numpy as jnp

    from trino_tpu.spi.page import Column, Page
    from trino_tpu.spi.types import BIGINT

    col = Column.from_numpy(BIGINT, np.arange(rows), capacity=cap)
    return Page((col,), jnp.asarray(np.arange(cap) < rows))


class TestRevocableSpiller:
    def test_parked_pages_revoke_under_pressure(self):
        from trino_tpu.runtime.spiller import Spiller, _SpilledPage

        page = _make_page()
        need = page_bytes(page)
        pool = MemoryPool(need + 64, name="park", reserve_timeout=5)
        ctx = AggregatedMemoryContext(pool=pool, owner="qa")
        sp = Spiller(0, memory=ctx)
        try:
            entries = sp.maybe_spill([page])
            assert pool.revocable_bytes == need
            ClusterMemoryManager(pool, kill_fn=None, spill_after=0.0,
                                 kill_after=99.0)
            # qb's blocked reservation triggers the revoke escalation: qa's
            # parked page spills to host instead of qb failing
            AggregatedMemoryContext(pool=pool, owner="qb").new_local(
                "op"
            ).set_bytes(need)
            assert pool.revocable_bytes == 0
            assert sp.spill_count == 1 and sp.revoked_bytes == need
            assert isinstance(entries[0], _SpilledPage)
            loaded = Spiller.load(entries[0])
            assert np.array_equal(
                np.asarray(loaded.columns[0].data)[:100], np.arange(100)
            )
        finally:
            sp.detach()


# --------------------------------------------------------------------------- #
# acceptance: blocking backpressure end to end
# --------------------------------------------------------------------------- #


class TestBackpressureEndToEnd:
    def test_query_blocks_then_completes(self, runner, solo):
        baselines, peak = solo
        pool = MemoryPool(max(2 * peak, 4096), name="bp", reserve_timeout=30)
        cm = ClusterMemoryManager(pool, killer=NoneLowMemoryKiller())
        mgr = QueryManager(runner.execute, max_workers=2, cluster_memory=cm)
        RECORDER.clear()
        RECORDER.enable()
        try:
            with ChaosInjector() as chaos:
                chaos.arm(
                    "memory_pressure", times=1,
                    bytes=pool.max_bytes, hold=0.3,
                )
                q = mgr.submit(Q6)
                assert q.wait_done(120), "query wedged under memory pressure"
        finally:
            RECORDER.disable()
        assert chaos.fired.get("memory_pressure") == 1
        assert q.state is QueryState.FINISHED, (q.error_type, q.error)
        assert q.rows == baselines[Q6]
        events = RECORDER.events()
        RECORDER.clear()
        b = [e for e in events
             if e["name"] == "memory_reserve_blocked" and e["ph"] == "B"]
        e_ = [e for e in events
              if e["name"] == "memory_reserve_blocked" and e["ph"] == "E"]
        assert b, "no memory_reserve_blocked span: the query never blocked"
        assert len(b) == len(e_), "blocked spans unpaired"
        assert any(
            (ev.get("args") or {}).get("outcome") == "granted" for ev in e_
        ), "no blocked reservation was granted after the peer released"


# --------------------------------------------------------------------------- #
# acceptance: overload chaos — killer fires, survivors bit-identical, no wedge
# --------------------------------------------------------------------------- #


class TestOverloadChaos:
    N_QUERIES = 32

    def test_overload_survives(self, runner, solo):
        baselines, peak = solo
        # a pool sized for ~4 complete queries, hit with 32 concurrent;
        # near-zero escalation delays so the killer fires on the first
        # arbiter poke of any blocked reservation — warm-cache queries are
        # fast enough that realistic delays would let the pool drain
        # kill-free on a lucky schedule (the production defaults stay 0.05/
        # 0.25 s; the test pins the escalation ORDER, not its tempo)
        pool = MemoryPool(4 * peak, name="overload", reserve_timeout=120)
        cm = ClusterMemoryManager(
            pool, killer=TotalReservationOnBlockedNodesLowMemoryKiller(),
            spill_after=0.0, kill_after=0.001,
        )
        mgr = QueryManager(runner.execute, max_workers=16, cluster_memory=cm)
        # how far 32 tiny queries overlap depends on how fast they run (with
        # their programs loaded from the persistent compile cache the pool
        # drained kill-free on every second schedule), so a phantom peer
        # holds all but one query's worth of the pool for the first half
        # second: whoever gets in wedges the rest, whatever the tempo
        with ChaosInjector() as chaos:
            chaos.arm(
                "memory_pressure", times=1,
                bytes=pool.max_bytes - peak, hold=0.5,
            )
            qs = [mgr.submit(MIX[i % len(MIX)]) for i in range(self.N_QUERIES)]
            for q in qs:
                assert q.wait_done(300), f"query {q.query_id} WEDGED: {q.state}"
        finished = [q for q in qs if q.state is QueryState.FINISHED]
        killed = [q for q in qs if q.error_type == "AdministrativelyKilled"]
        unexpected = [
            q for q in qs
            if q.state is not QueryState.FINISHED
            and q.error_type != "AdministrativelyKilled"
        ]
        assert not unexpected, (
            f"non-kill failures under overload: "
            f"{[(q.error_type, q.error) for q in unexpected]}"
        )
        # the killer fired (32 queries cannot fit a 4-query pool) ...
        assert cm.kills_total >= 1 and killed
        # ... with the low-memory reason on every victim
        for q in killed:
            assert "low-memory killer" in (q.error or ""), q.error
        # ... and the survivors' results are BIT-IDENTICAL to their solo runs
        assert finished, "everything was killed — the pool never drained"
        for q in finished:
            assert q.rows == baselines[q.sql], f"survivor {q.query_id} diverged"
        # the pool drained completely: nothing leaked past free_owner (the
        # phantom peer lets go on its own timer, half a second in)
        released = time.monotonic() + 5
        while pool.reserved_bytes and time.monotonic() < released:
            time.sleep(0.01)
        assert pool.reserved_bytes == 0 and pool.revocable_bytes == 0


# --------------------------------------------------------------------------- #
# system tables
# --------------------------------------------------------------------------- #


class TestSystemTables:
    def test_memory_pool_and_resource_groups_tables(self, runner):
        from trino_tpu.runtime.resource_groups import ResourceGroupManager

        pool = MemoryPool(1 << 30, name="general")
        mgr = QueryManager(
            runner.execute, memory_pool=pool,
            resource_groups=ResourceGroupManager.default(8),
        )
        warm = mgr.submit("SELECT count(*) FROM nation")
        assert warm.wait_done(120) and warm.state is QueryState.FINISHED

        q = mgr.submit(
            "SELECT node_id, pool, max_bytes, reserved_bytes, "
            "revocable_bytes, blocked_queries, low_memory_kills "
            "FROM system.runtime.memory_pool"
        )
        assert q.wait_done(120) and q.state is QueryState.FINISHED, q.error
        rows = {r[0]: r for r in q.rows}
        assert "local" in rows
        local = rows["local"]
        assert local[1] == "general" and local[2] == 1 << 30
        assert isinstance(local[3], int) and local[3] >= 0
        assert local[6] == 0  # no kills

        g = mgr.submit(
            "SELECT id, hard_concurrency_limit, max_queued, running, queued, "
            "memory_usage_bytes FROM system.runtime.resource_groups"
        )
        assert g.wait_done(120) and g.state is QueryState.FINISHED, g.error
        by_id = {r[0]: r for r in g.rows}
        assert "global" in by_id
        # the scan itself runs in the global group
        assert any(r[3] >= 1 for r in g.rows)
        assert all(isinstance(r[5], int) for r in g.rows)

    def test_memory_pool_table_shows_announced_workers(self, runner):
        from trino_tpu.runtime.nodes import InternalNodeManager

        pool = MemoryPool(1 << 20, name="general")
        mgr = QueryManager(runner.execute, memory_pool=pool)
        nodes = InternalNodeManager()
        ctx = runner.metadata.system_context
        prev = ctx.node_manager
        ctx.node_manager = nodes
        try:
            nodes.announce(
                "w1", "http://w1:8080",
                memory={"maxBytes": 4096, "reservedBytes": 1234,
                        "revocableBytes": 5, "peakBytes": 2000,
                        "blockedQueries": 1},
            )
            q = mgr.submit(
                "SELECT node_id, max_bytes, reserved_bytes, blocked_queries "
                "FROM system.runtime.memory_pool WHERE node_id = 'w1'"
            )
            assert q.wait_done(120) and q.state is QueryState.FINISHED, q.error
            assert q.rows == [("w1", 4096, 1234, 1)]
        finally:
            ctx.node_manager = prev
