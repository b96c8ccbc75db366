"""Planner-connected single-program ICI execution (parallel/mesh_runner.py).

The round-2 unification: real SQL plans from the fragmenter execute as ONE
shard_map program over the 8-device mesh — REPARTITION as all_to_all,
GATHER/BROADCAST as all_gather — parity-checked against single-device
execution (the DistributedQueryRunner-vs-local model of SURVEY.md §4).
"""

import numpy as np
import pytest

import jax

from trino_tpu.runtime import LocalQueryRunner


N_DEV = 8
SCALE = 0.001


@pytest.fixture(scope="module")
def mesh_runner():
    from trino_tpu.parallel.mesh_runner import MeshQueryRunner

    if len(jax.devices()) < N_DEV:
        pytest.skip(f"need {N_DEV} devices")
    return MeshQueryRunner.tpch(scale=SCALE, n_devices=N_DEV)


@pytest.fixture(scope="module")
def local():
    return LocalQueryRunner.tpch(scale=SCALE)


def check(mesh_runner, local, sql, sort=False):
    got = mesh_runner.execute(sql).rows
    want = local.execute(sql).rows
    if sort:
        got, want = sorted(got), sorted(want)
    assert got == want


class TestMeshParity:
    def test_global_agg(self, mesh_runner, local):
        check(mesh_runner, local, "SELECT count(*), sum(l_quantity) FROM lineitem")

    def test_q6_filter_agg(self, mesh_runner, local):
        check(
            mesh_runner,
            local,
            """SELECT sum(l_extendedprice * l_discount) FROM lineitem
               WHERE l_shipdate >= DATE '1994-01-01'
                 AND l_shipdate < DATE '1995-01-01'
                 AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24""",
        )

    def test_q1_groupby_repartition(self, mesh_runner, local):
        check(
            mesh_runner,
            local,
            """SELECT l_returnflag, l_linestatus, sum(l_quantity), count(*),
                      avg(l_extendedprice)
               FROM lineitem WHERE l_shipdate <= DATE '1998-09-02'
               GROUP BY l_returnflag, l_linestatus
               ORDER BY l_returnflag, l_linestatus""",
        )

    def test_high_cardinality_groupby(self, mesh_runner, local):
        # forces the sort-based path per shard + all_to_all of partials
        check(
            mesh_runner,
            local,
            """SELECT l_orderkey, count(*) FROM lineitem
               GROUP BY l_orderkey ORDER BY l_orderkey LIMIT 50""",
        )

    def test_join_repartitioned(self, mesh_runner, local):
        check(
            mesh_runner,
            local,
            "SELECT count(*) FROM lineitem JOIN orders ON l_orderkey = o_orderkey",
        )

    def test_q3_two_joins_topn(self, mesh_runner, local):
        check(
            mesh_runner,
            local,
            """SELECT o_orderkey, sum(l_extendedprice * (1 - l_discount)) AS rev
               FROM customer JOIN orders ON c_custkey = o_custkey
               JOIN lineitem ON l_orderkey = o_orderkey
               WHERE c_mktsegment = 'BUILDING'
                 AND o_orderdate < DATE '1995-03-15'
               GROUP BY o_orderkey ORDER BY rev DESC LIMIT 10""",
        )

    def test_left_join(self, mesh_runner, local):
        check(
            mesh_runner,
            local,
            """SELECT count(*), count(l_orderkey) FROM orders
               LEFT JOIN lineitem ON o_orderkey = l_orderkey
                 AND l_quantity > 45""",
        )

    def test_semi_join(self, mesh_runner, local):
        check(
            mesh_runner,
            local,
            """SELECT count(*) FROM orders WHERE o_orderkey IN
               (SELECT l_orderkey FROM lineitem WHERE l_quantity > 45)""",
        )

    def test_distributed_runner_uses_mesh(self):
        """DistributedQueryRunner's tier-1 path gives the same results."""
        from trino_tpu.parallel.runner import DistributedQueryRunner

        if len(jax.devices()) < 4:
            pytest.skip("need 4 devices")
        r = DistributedQueryRunner.tpch(scale=SCALE, n_workers=4)
        assert bool(r.session.get("use_ici_exchange"))
        got = r.execute(
            "SELECT l_returnflag, count(*) FROM lineitem "
            "GROUP BY l_returnflag ORDER BY l_returnflag"
        ).rows
        local = LocalQueryRunner.tpch(scale=SCALE)
        want = local.execute(
            "SELECT l_returnflag, count(*) FROM lineitem "
            "GROUP BY l_returnflag ORDER BY l_returnflag"
        ).rows
        assert got == want


class TestMeshLoweringGuards:
    def test_cross_join_falls_back_correctly(self):
        # cross joins get no exchange: SPMD execution would pair only same-
        # shard blocks — the runner must detect this and use the staged path
        from trino_tpu.parallel.runner import DistributedQueryRunner

        if len(jax.devices()) < 4:
            pytest.skip("need 4 devices")
        r = DistributedQueryRunner.tpch(scale=SCALE, n_workers=4)
        assert r.execute("SELECT count(*) FROM nation CROSS JOIN region").rows == [
            (25 * 5,)
        ]

    def test_scan_union_values_falls_back_correctly(self):
        from trino_tpu.parallel.runner import DistributedQueryRunner

        if len(jax.devices()) < 4:
            pytest.skip("need 4 devices")
        r = DistributedQueryRunner.tpch(scale=SCALE, n_workers=4)
        got = r.execute(
            "SELECT count(*) FROM "
            "(SELECT n_name, x FROM nation CROSS JOIN (VALUES (1)) t(x)) u"
        ).rows
        assert got == [(25,)]

    def test_mesh_rejects_cross_join(self, mesh_runner):
        from trino_tpu.parallel.mesh_runner import MeshLoweringError

        with pytest.raises(MeshLoweringError):
            mesh_runner.execute("SELECT count(*) FROM nation CROSS JOIN region")

    def test_program_cache_reused(self, mesh_runner, local):
        sql = "SELECT count(*) FROM lineitem JOIN orders ON l_orderkey = o_orderkey"
        mesh_runner.execute(sql)
        before = len(mesh_runner._program_cache)
        got = mesh_runner.execute(sql).rows
        assert len(mesh_runner._program_cache) == before
        assert got == local.execute(sql).rows


class TestMeshStringKeys:
    def test_string_key_join_across_dictionaries(self):
        """Repartition must route the same string to the same shard even when
        the two join sides carry different dictionaries (codes are local)."""
        from trino_tpu.parallel.runner import DistributedQueryRunner

        if len(jax.devices()) < 8:
            pytest.skip("need 8 devices")
        r = DistributedQueryRunner.tpch(scale=SCALE, n_workers=8)
        r.session.set("join_distribution_type", "PARTITIONED")
        try:
            got = r.execute(
                "SELECT t.k, s.v FROM (VALUES ('apple'), ('banana'), ('cherry'), "
                "('fig')) t(k) JOIN (VALUES ('banana', 1), ('cherry', 2), "
                "('grape', 3)) s(k, v) ON t.k = s.k ORDER BY t.k"
            ).rows
        finally:
            r.session.properties.pop("join_distribution_type", None)
        assert got == [("banana", 1), ("cherry", 2)]


class TestMeshCapacityRetry:
    def test_join_overflow_retries(self, mesh_runner, local):
        # 1:N expansion beyond probe capacity: initial static capacity
        # overflows, the runner must retry with a doubled factor — same result
        mesh_runner.session.properties["mesh_join_capacity_factor"] = 0.01
        try:
            check(
                mesh_runner,
                local,
                "SELECT count(*) FROM orders JOIN lineitem ON o_orderkey = l_orderkey",
            )
        finally:
            mesh_runner.session.properties.pop("mesh_join_capacity_factor")


class TestDistributedSort:
    """Range-shuffle + per-shard sort + merge gather (the dist-sort path;
    ref docs admin/dist-sort.md, operator/MergeOperator.java)."""

    def test_order_by_full_table(self, mesh_runner, local):
        check(
            mesh_runner, local,
            "SELECT l_orderkey, l_linenumber, l_quantity FROM lineitem "
            "ORDER BY l_quantity, l_orderkey, l_linenumber",
        )

    def test_order_by_desc_with_nulls(self, mesh_runner, local):
        check(
            mesh_runner, local,
            "SELECT o_orderkey, o_totalprice FROM orders "
            "ORDER BY o_totalprice DESC, o_orderkey",
        )

    def test_order_by_string_key(self, mesh_runner, local):
        check(
            mesh_runner, local,
            "SELECT c_name, c_custkey FROM customer ORDER BY c_name",
        )

    def test_order_by_after_join(self, mesh_runner, local):
        check(
            mesh_runner, local,
            "SELECT o_orderkey, o_totalprice, c_name FROM orders "
            "JOIN customer ON o_custkey = c_custkey "
            "ORDER BY o_totalprice DESC, o_orderkey LIMIT 1000",
        )

    def test_plan_uses_range_partitioning(self, mesh_runner):
        from trino_tpu.planner.fragmenter import Partitioning

        subplan = mesh_runner.plan_distributed(
            "SELECT l_orderkey FROM lineitem ORDER BY l_orderkey"
        )
        parts = [f.partitioning for f in subplan.fragments]
        assert Partitioning.FIXED_RANGE in parts


class TestTierObservability:
    """Which queries lower to the single-program ICI tier vs fall back, and
    why — the round-2 review asked for exactly this tracking."""

    def test_tpch_ladder_tiers(self):
        from trino_tpu.parallel.runner import DistributedQueryRunner

        r = DistributedQueryRunner.tpch(scale=SCALE, n_workers=8)
        lowered = {}
        for name, sql in {
            "q6": "SELECT sum(l_extendedprice * l_discount) FROM lineitem "
                  "WHERE l_discount BETWEEN 0.05 AND 0.07",
            "q1": "SELECT l_returnflag, count(*) FROM lineitem GROUP BY 1",
            "join": "SELECT count(*) FROM lineitem JOIN orders "
                    "ON l_orderkey = o_orderkey",
            "cross": "SELECT count(*) FROM nation, region",
        }.items():
            r.execute(sql)
            lowered[name] = (r.last_tier, r.last_tier_reason)
        assert lowered["q6"][0] == "ici"
        assert lowered["q1"][0] == "ici"
        assert lowered["join"][0] == "ici"
        # cross joins are a documented mesh rejection — staged, with a reason
        assert lowered["cross"][0] == "staged"
        assert "cross" in (lowered["cross"][1] or "")


# --------------------------------------------------------------------------- #
# a table's shards stay on the mesh between statements (`_ShardStore`)
# --------------------------------------------------------------------------- #

STORE_DEV = 4
T = "memory.default.t"
SUMS = f"SELECT count(*), sum(k), sum(q) FROM {T}"


def hits(result):
    from trino_tpu.parallel import mesh_runner as mr
    from trino_tpu.runtime.metrics import REGISTRY

    return REGISTRY.counter(mr.COLUMNS_COUNTER, {"result": result}).value


def mesh_over(connector):
    """A mesh runner over ``connector`` as catalog `memory`, four devices."""
    from trino_tpu.metadata import Session
    from trino_tpu.parallel.mesh_runner import MeshQueryRunner

    if len(jax.devices()) < STORE_DEV:
        pytest.skip(f"need {STORE_DEV} devices")
    mesh = MeshQueryRunner(Session(catalog="memory", schema="default"), n_devices=STORE_DEV)
    mesh.catalogs.register("memory", connector)
    return mesh


@pytest.fixture()
def stored():
    """(mesh runner, loader, connector): the loader (one chip) writes the
    tables of the memory connector that the mesh runner scans, as the
    benchmark's runner `mesh_memory` has it."""
    from trino_tpu.connectors.memory import MemoryConnector

    memory = MemoryConnector()
    loader = LocalQueryRunner.tpch(scale=SCALE)
    loader.register_catalog("memory", memory)
    loader.execute(
        f"CREATE TABLE {T} AS SELECT l_orderkey AS k, l_quantity AS q, "
        "l_extendedprice AS p, l_returnflag AS f FROM lineitem"
    )
    loader.execute("CREATE TABLE memory.default.u AS SELECT o_orderkey AS k, o_totalprice AS p FROM orders")
    return mesh_over(memory), loader, memory


def traced(mesh, sql):
    """(rows, the attributes of the statement's `mesh:load_scan` spans, those
    of its `mesh:shard` spans)."""
    from trino_tpu.runtime.tracing import STATEMENT, TRACER

    with TRACER.span(STATEMENT) as root:
        rows = mesh.execute(sql).rows
    tree = TRACER.spans(root.trace_id)
    return (
        rows,
        [s.attributes for s in tree if s.name == "mesh:load_scan"],
        [s.attributes for s in tree if s.name == "mesh:shard"],
    )


class TestShardsStayOnTheMesh:
    def test_a_second_statement_moves_no_byte(self, stored):
        mesh, loader, _ = stored
        before = hits("hit"), hits("miss")
        first, (load1,), (shard1,) = traced(mesh, SUMS)
        second, (load2,), (shard2,) = traced(mesh, SUMS)
        assert first == second == loader.execute(SUMS).rows
        assert (shard1["cached"], shard1["put"]) == (0, 2) and shard1["h2d_bytes"] >= load1["bytes"] > 0
        assert (shard2["cached"], shard2["put"], shard2["h2d_bytes"]) == (2, 0, 0)
        assert load2["cached"] == 2 and (load2["rows"], load2["bytes"]) == (load1["rows"], load1["bytes"])
        assert (hits("hit") - before[0], hits("miss") - before[1]) == (2, 2)

    def test_statements_share_the_columns_they_have_in_common(self, stored):
        mesh, loader, _ = stored
        one = f"SELECT sum(k), sum(q) FROM {T} WHERE q < 30"
        other = f"SELECT sum(p), max(k) FROM {T} WHERE q > 10"
        (_, _, (shard1,)), (rows, _, (shard2,)) = traced(mesh, one), traced(mesh, other)
        assert (shard1["cached"], shard1["put"]) == (0, 2)
        assert (shard2["cached"], shard2["put"]) == (2, 1)   # k and q found, p put
        assert 0 < shard2["h2d_bytes"] < shard1["h2d_bytes"]
        assert rows == loader.execute(other).rows
        (table,) = mesh._shards.tables.values()
        assert len(table.columns) == 3    # the union of both scans, once each

    @pytest.mark.parametrize("write", ["insert", "delete", "drop_create", "ctas"])
    def test_a_write_is_read_by_the_next_statement(self, stored, write):
        mesh, loader, _ = stored
        assert traced(mesh, SUMS)[0] == loader.execute(SUMS).rows
        held = mesh._shards.device_bytes()
        old = [c.data for t in mesh._shards.tables.values() for c in t.columns.values()]
        for sql in {
            "insert": [f"INSERT INTO {T} SELECT k + 1000000, q, p, f FROM {T} WHERE k < 100"],
            "delete": [f"DELETE FROM {T} WHERE k >= 100"],
            "drop_create": [
                f"DROP TABLE {T}",
                f"CREATE TABLE {T} (k bigint, q decimal(12,2), p decimal(12,2), f varchar)",
                f"INSERT INTO {T} VALUES (7, 1.50, 2.25, 'A'), (8, 2.50, 3.25, 'N')",
            ],
            "ctas": [
                f"DROP TABLE {T}",
                f"CREATE TABLE {T} AS SELECT o_orderkey AS k, o_totalprice AS q FROM orders WHERE o_orderkey < 50",
            ],
        }[write]:
            loader.execute(sql)
        want = loader.execute(SUMS).rows
        stale, miss = hits("stale"), hits("miss")
        rows, _, (shard,) = traced(mesh, SUMS)
        assert rows == want
        assert (shard["cached"], shard["put"]) == (0, 2)
        assert (hits("stale") - stale, hits("miss") - miss) == (2, 2)
        # the old version's arrays are gone from the store: one table, the new one
        (table,) = mesh._shards.tables.values()
        assert not any(c.data is o for c in table.columns.values() for o in old)
        assert mesh._shards.device_bytes() == table.device_bytes > 0
        if write in ("drop_create", "ctas"):   # a smaller table: the store's bytes fall back
            assert mesh._shards.device_bytes() < held
        assert traced(mesh, SUMS)[2][0]["h2d_bytes"] == 0

    def test_a_write_after_the_sweep_is_caught_at_the_lookup(self, stored, monkeypatch):
        mesh, loader, _ = stored
        traced(mesh, SUMS)
        sweep = mesh._shards.drop_stale

        def sweep_then_write(metadata):
            sweep(metadata)
            loader.execute(f"DELETE FROM {T} WHERE k >= 100")

        monkeypatch.setattr(mesh._shards, "drop_stale", sweep_then_write)
        stale = hits("stale")
        rows, _, (shard,) = traced(mesh, SUMS)
        monkeypatch.undo()
        assert rows == loader.execute(SUMS).rows
        assert (shard["cached"], shard["put"], hits("stale") - stale) == (0, 2, 2)

    @pytest.mark.parametrize("answer", ["no_token", "bypass", "unhashable_handle"])
    def test_a_table_without_a_token_is_resharded_every_statement(self, stored, answer, monkeypatch):
        from trino_tpu.connectors.memory import MemoryConnector

        mesh, loader, memory = stored
        if answer == "no_token":
            monkeypatch.setattr(MemoryConnector, "cache_table_version", None)
        elif answer == "bypass":
            monkeypatch.setattr(memory, "cache_bypass", True, raising=False)
        else:
            absorb = lambda handle, domain: type(handle)(handle.catalog, handle.schema_table, {"pushed": [1]})
            monkeypatch.setattr(memory.metadata(), "apply_filter", absorb, raising=False)
        sql = f"SELECT count(*), sum(k) FROM {T} WHERE q < 30"
        want = loader.execute(sql).rows
        moved = []
        for _ in range(2):
            rows, _, (shard,) = traced(mesh, sql)
            assert rows == want and (shard["cached"], shard["put"]) == (0, 2)
            moved.append(shard["h2d_bytes"])
        assert moved[0] == moved[1] > 0
        assert not mesh._shards.tables

    def test_a_token_that_changes_under_the_load_keeps_nothing(self, stored, monkeypatch):
        from trino_tpu.connectors.memory import MemoryConnector

        mesh, loader, memory = stored
        ticks = iter(range(10**6))
        monkeypatch.setattr(
            MemoryConnector, "cache_table_version", lambda self, schema, table: f"moving-{next(ticks)}"
        )
        rows, _, (shard,) = traced(mesh, SUMS)
        assert rows == loader.execute(SUMS).rows and shard["put"] == 2
        assert not mesh._shards.tables and mesh._shards.device_bytes() == 0

    def test_a_write_under_a_partial_load_drops_what_was_kept(self, stored, monkeypatch):
        mesh, loader, memory = stored
        traced(mesh, f"SELECT sum(k) FROM {T}")
        load = mesh._load_columns

        def written_under(connector, handle, col_indexes):
            page = load(connector, handle, col_indexes)
            if len(col_indexes) == 1:    # the partial load of q: a writer gets in
                loader.execute(f"DELETE FROM {T} WHERE k >= 100")
            return page

        monkeypatch.setattr(mesh, "_load_columns", written_under)
        rows, (loaded,), (shard,) = traced(mesh, SUMS)
        # all of it loaded again after the write, the kept k not used
        assert (loaded["cached"], shard["cached"], shard["put"]) == (0, 0, 2)
        assert rows == loader.execute(SUMS).rows
        monkeypatch.undo()
        assert traced(mesh, SUMS)[0] == rows

    def test_the_budget_evicts_the_least_recently_used_table(self, stored):
        mesh, loader, _ = stored
        u = "SELECT count(*), sum(k), sum(p) FROM memory.default.u"
        three = f"SELECT count(*), sum(k), sum(p), sum(q) FROM {T}"
        traced(mesh, three)
        (t_key,) = mesh._shards.tables
        mesh._shards.budget = mesh._shards.device_bytes()    # room for t's three columns, and no more
        mesh._shards.clear()
        traced(mesh, SUMS)
        t_bytes = mesh._shards.device_bytes()
        traced(mesh, u)
        assert len(mesh._shards.tables) == 2    # two columns of each: both fit
        traced(mesh, SUMS)                        # t is the most recent now
        rows, _, (shard,) = traced(mesh, three)
        assert shard["put"] == 1    # p joins t: u, the least recently used, goes
        assert list(mesh._shards.tables) == [t_key]
        assert t_bytes < mesh._shards.device_bytes() == mesh._shards.budget
        # the evicted table is answered, and kept again at t's cost
        got, _, (again,) = traced(mesh, u)
        assert got == loader.execute(u).rows and (again["cached"], again["put"]) == (0, 2)
        # a table over the budget by itself is used and not kept
        mesh._shards.budget = 1
        rows, _, (over,) = traced(mesh, SUMS)
        assert rows == loader.execute(SUMS).rows and over["put"] == 2
        assert not mesh._shards.tables

    def test_the_program_donates_no_argument(self, stored):
        mesh, _, _ = stored
        subplan = mesh.plan_distributed(SUMS)
        specs, counts = mesh._shard_scans(subplan)
        pages = [s.page for s in specs]
        points = mesh._points(subplan)
        program = mesh._build_program(subplan, counts, [None] * len(points), 1.0)
        lowered = program.fn.lower(*pages)
        assert "donor" not in lowered.as_text() and "aliasing_output" not in lowered.as_text()
        args = jax.tree_util.tree_leaves(lowered.args_info)
        assert len(args) == len(jax.tree_util.tree_leaves(pages)) and not any(a.donated for a in args)
        out, _ = program.fn(*pages)
        jax.block_until_ready(out)
        # the store's arrays are alive after the program that read them
        for leaf in jax.tree_util.tree_leaves(pages):
            assert not leaf.is_deleted()
            np.asarray(leaf[:1])

    def test_the_page_a_hit_assembles_is_the_page_a_cold_scan_builds(self, stored):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, _, memory = stored
        sql = f"SELECT f, sum(q), max(k) FROM {T} GROUP BY f"
        subplan = mesh.plan_distributed(sql)
        (cold,), _ = mesh_over(memory)._shard_scans(subplan)    # another runner: an empty store
        mesh._shard_scans(subplan)
        (hit,), _ = mesh._shard_scans(subplan)
        assert hit.symbols == cold.symbols
        assert jax.tree_util.tree_structure(hit.page) == jax.tree_util.tree_structure(cold.page)
        sharding = NamedSharding(mesh.mesh, P(mesh.axis))
        for a, b in zip(jax.tree_util.tree_leaves(hit.page), jax.tree_util.tree_leaves(cold.page)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
            assert a.sharding.is_equivalent_to(sharding, a.ndim)
            assert [s.data.shape for s in a.addressable_shards] == [s.data.shape for s in b.addressable_shards]
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # dictionaries are the same objects, so the jitted program is found again
        assert [c.dictionary for c in hit.page.columns] == [c.dictionary for c in cold.page.columns]

    def test_a_put_that_runs_out_of_memory_empties_the_store_and_is_tried_again(self, stored, monkeypatch):
        from trino_tpu.parallel import mesh_runner as mr

        mesh, loader, _ = stored
        traced(mesh, "SELECT count(*), sum(k), sum(p) FROM memory.default.u")
        assert len(mesh._shards.tables) == 1
        put, failed = jax.device_put, []

        def full_once(tree, sharding):
            if not failed:
                failed.append(True)
                raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory while trying to allocate")
            return put(tree, sharding)

        monkeypatch.setattr(mr.jax, "device_put", full_once)
        rows, _, (shard,) = traced(mesh, SUMS)
        assert failed and rows == loader.execute(SUMS).rows and shard["put"] == 2
        assert [str(t.handle.schema_table) for t in mesh._shards.tables.values()] == ["default.t"]

    def test_two_statements_that_miss_the_same_columns_put_them_once(self, stored):
        import sys
        import threading

        mesh, loader, _ = stored
        want = loader.execute(SUMS).rows
        mesh.execute(SUMS)    # the program is compiled; the threads race for the shards alone
        mesh._shards.clear()
        miss, got, errors = hits("miss"), [], []

        def run():
            try:
                got.append(mesh.execute(SUMS).rows)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=run) for _ in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads) and not errors
        assert got == [want] * 6
        assert hits("miss") - miss == 2    # k and q, put by the one that came first
