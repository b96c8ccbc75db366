"""A scan packs its split pages when it concatenates them
(runtime/executor.py: `_concat_scan_pages`, `_pack_pages`): the live rows of
split 0, then of split 1, ..., in row order at the front of a page of the
connectors' capacity class, where every split page holds its rows as a prefix
and the class is smaller than the capacities together; else the plain
concatenation. TPC-H at SF0.01 cut into 18 (`lineitem`) and 5 (`orders`)
splits of 4,096 rows of capacity, as SF3 has 18 of 2,097,152."""

import contextlib
import math

import jax
import numpy as np
import pytest

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.metadata import Session
from trino_tpu.parallel import mesh_runner as mr
from trino_tpu.parallel.runner import DistributedQueryRunner
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime.executor import (
    _concat_pages,
    _concat_scan_pages,
    _load_splits,
    _round_capacity,
)
from trino_tpu.runtime.tracing import TRACER, children
from trino_tpu.spi.connector import SchemaTableName, TableHandle
from trino_tpu.spi.page import Page, capacity_class
from trino_tpu.spi.types import BIGINT

SCALE = 0.01
SPLIT_ROWS = 3400  # lineitem: 18 splits, orders: 5
SPLITS = {"lineitem": 18, "orders": 5}
ROWS = {"lineitem": 59957, "orders": 15000}


@contextlib.contextmanager
def scans():
    """{"packed": n, "plain": m} of the scans of several pages made under it,
    from their spans: `scan_pack` where the pages were packed; the read of the
    pages' counts (`sync:scan_pack`) with no `scan_pack` after it where they
    were concatenated as they were (pages with a nested column are, before
    any count is read: they leave no span)."""
    seen = {}
    with TRACER.span("test") as root:
        yield seen
    names = [s.name for s in TRACER.spans(root.trace_id)]
    packed = names.count("scan_pack")
    seen.update(packed=packed, plain=names.count("sync:scan_pack") - packed)


def newest_tree():
    return TRACER.finished("statement")[-1]


@pytest.fixture(scope="module")
def tpch():
    return TpchConnector(scale=SCALE, split_target_rows=SPLIT_ROWS)


@pytest.fixture(scope="module")
def runner(tpch):
    r = LocalQueryRunner.tpch(scale=SCALE)
    r.register_catalog("tpch", tpch)
    r.register_catalog("memory", MemoryConnector())
    return r


def split_pages(tpch, table):
    handle = TableHandle("tpch", SchemaTableName("sf0_01", table))
    splits = tpch.split_manager().get_splits(handle)
    meta = tpch.metadata().get_table_metadata(handle.schema_table)
    return _load_splits(
        tpch.page_source_provider(), splits, list(range(len(meta.columns))), Session()
    )


def live(page: Page):
    """Every column's (data, valid) of the live rows, in row order, on the host."""
    active = np.asarray(page.active)
    return [
        (np.asarray(c.data)[active], np.asarray(c.valid)[active]) for c in page.columns
    ]


@pytest.mark.parametrize("table", ["lineitem", "orders"])
def test_packed_scan_holds_the_plain_concatenations_rows_in_order(tpch, table):
    pages = split_pages(tpch, table)
    assert len(pages) == SPLITS[table]
    with scans() as seen:
        packed, plain = _concat_scan_pages(pages), _concat_pages(pages)
    assert seen == {"packed": 1, "plain": 0}
    # a prefix, in the connectors' class, and smaller than the padding kept
    active = np.asarray(packed.active)
    assert active[: ROWS[table]].all() and not active[ROWS[table]:].any()
    assert packed.capacity == capacity_class(ROWS[table]) < plain.capacity
    assert plain.capacity == sum(p.capacity for p in pages)
    for (got, got_valid), (want, want_valid), col in zip(live(packed), live(plain), packed.columns):
        assert np.array_equal(got, want) and np.array_equal(got_valid, want_valid)
        assert col.capacity == packed.capacity
    for got, want in zip(packed.columns, plain.columns):
        assert (got.type, got.dictionary) == (want.type, want.dictionary)


def test_capacity_class_is_the_connectors():
    assert [capacity_class(n) for n in (0, 1, 64, 65, 1 << 20, (1 << 20) + 1)] == [
        64, 64, 64, 128, 1 << 20, 2 << 20,
    ]
    # SF3's lineitem: a multiple of 2^20 and not _round_capacity's 33,554,432
    assert capacity_class(17_993_932) == 18_874_368 == 18 << 20
    assert _round_capacity(17_993_932) == 33_554_432


# ------------------------------------------------------------ which path


def two_pages(rows_a, rows_b, capacity, active_b=None):
    a = Page.from_arrays([BIGINT], [np.arange(rows_a)], capacity=capacity)
    b = Page.from_arrays([BIGINT], [100 + np.arange(rows_b)], capacity=capacity)
    if active_b is not None:
        b = Page(b.columns, jax.numpy.asarray(np.asarray(active_b, dtype=bool)))
    return [a, b]


@pytest.mark.parametrize("case,pages,path", [
    ("prefix-live pages", lambda: two_pages(3, 2, 64), "packed"),
    ("a page with a hole", lambda: two_pages(3, 3, 64, [True, False, True] + [False] * 61), "plain"),
    ("live rows at the back", lambda: two_pages(3, 2, 64, [False] * 62 + [True] * 2), "plain"),
    ("a class no smaller than the pages together", lambda: two_pages(64, 1, 64), "plain"),
    ("an empty page between", lambda: two_pages(0, 2, 64), "packed"),
    ("no live row at all", lambda: two_pages(0, 0, 64), "packed"),
])
def test_path_follows_what_the_pages_hold(case, pages, path):
    pages = pages()
    with scans() as seen:
        got, plain = _concat_scan_pages(pages), _concat_pages(pages)
    assert seen == {"packed": int(path == "packed"), "plain": int(path == "plain")}
    (rows, _), = live(got)
    (want, _), = live(plain)
    assert rows.tolist() == want.tolist()
    if path == "packed":
        assert got.capacity == capacity_class(len(want)) < plain.capacity
        assert np.asarray(got.active).tolist() == [True] * len(want) + [False] * (got.capacity - len(want))
    else:
        assert got.capacity == plain.capacity


def test_single_page_is_handed_back_untouched():
    (page, _) = two_pages(3, 2, 64)
    with scans() as seen:
        assert _concat_scan_pages([page]) is page
    assert seen == {"packed": 0, "plain": 0}


def test_fully_pruned_scan_concatenates_nothing(runner):
    with scans() as seen:
        assert runner.execute("SELECT count(*) FROM tpch.sf0_01.lineitem WHERE l_orderkey < 0").rows == [(0,)]
    assert seen == {"packed": 0, "plain": 0}


FEW = "FROM tpch.sf0_01.orders WHERE o_orderkey < {}"  # a prefix of the scan's page


@pytest.mark.parametrize("case,first,second,path", [
    ("nested column", "SELECT o_orderkey AS id, ARRAY[o_custkey, 2] AS v " + FEW.format(100),
     "SELECT o_orderkey, ARRAY[3, 4, o_custkey] " + FEW.format(40), "plain"),
    ("pages of a row each", "SELECT 1 AS id, 'pear' AS v", "SELECT 2, 'fig'", "plain"),
    ("multi-lane column",
     "SELECT o_orderkey AS id, CAST(o_totalprice AS decimal(38,2)) * 1000000000000.0 AS v " + FEW.format(100),
     "SELECT o_orderkey, CAST(o_totalprice AS decimal(38,2)) * -1000000000000.0 " + FEW.format(40), "packed"),
    ("dictionaries that differ", "SELECT o_orderkey AS id, o_orderpriority AS v " + FEW.format(100),
     "SELECT o_orderkey, o_clerk " + FEW.format(40), "packed"),
    ("a column of NULLs", "SELECT o_orderkey AS id, CAST(NULL AS BIGINT) AS v " + FEW.format(100),
     "SELECT o_orderkey, o_custkey " + FEW.format(40), "packed"),
])
def test_two_stored_pages_scan_as_the_rows_inserted(runner, case, first, second, path):
    runner.execute("DROP TABLE IF EXISTS memory.default.two")
    runner.execute(f"CREATE TABLE memory.default.two AS {first}")
    runner.execute(f"INSERT INTO memory.default.two {second}")
    with scans() as seen:
        got = runner.execute("SELECT id, v FROM memory.default.two").rows
    # a nested column is concatenated as it is before any count is read: no span
    read = case != "nested column"
    assert seen == {"packed": int(path == "packed"), "plain": int(path == "plain" and read)}
    # in insertion order, with no ORDER BY: as a UNION ALL (`_concat_union_pages`) gives them
    assert got == runner.execute(f"{first} UNION ALL {second}").rows
    assert len(got) > 1 and len({str(v) for _, v in got}) > 1


# ------------------------------------------------- the memory catalog's sink


@pytest.mark.parametrize("table,total", [("lineitem", "l_extendedprice"), ("orders", "o_totalprice")])
def test_ctas_stores_the_packed_page_and_answers_as_the_direct_scan(runner, tpch, table, total):
    with scans() as seen:
        created = runner.execute(f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.sf0_01.{table}")
        assert created.rows == [(ROWS[table],)]
    assert seen == {"packed": 1, "plain": 0}
    stored = runner.catalogs.get("memory").table(SchemaTableName("default", table))
    assert [p.capacity for p in stored.pages] == [capacity_class(ROWS[table])]
    assert stored.row_count() == ROWS[table]
    sql = f"SELECT count(*), sum({total}), min({total}), max({total}) FROM {{}}.{table}"
    with scans() as seen:
        assert runner.execute(sql.format("memory.default")).rows == runner.execute(sql.format("tpch.sf0_01")).rows
    # the stored page is one page (nothing to concatenate), the direct scan packs again
    assert seen == {"packed": 1, "plain": 0}
    # and in order: the generator's, by key
    key = {"lineitem": "l_orderkey, l_linenumber", "orders": "o_orderkey"}[table]
    stored_rows = runner.execute(f"SELECT {key} FROM memory.default.{table}").rows
    assert stored_rows == sorted(stored_rows) and len(stored_rows) == ROWS[table]


def test_scan_pack_span_and_sync_sit_under_the_scan_that_asked(runner):
    runner.execute("SELECT count(*) FROM tpch.sf0_01.orders")
    tree = newest_tree()
    (scan,) = [s for s in tree if s.name == "op:TableScanNode"]
    under = {s.name: s for s in children(tree, scan)}
    assert {"scan_pack", "sync:scan_pack"} <= set(under)
    assert under["scan_pack"].attributes == {
        "pages": 5, "capacity_in": 5 * 4096, "live_rows": 15000, "capacity_out": 16384,
    }
    assert under["sync:scan_pack"].attributes["value"] == 15000
    # the one read of the counts is a host sync of the statement, like the other sites
    assert tree[0].attributes["host_syncs"] >= 1


def test_insert_delete_update_and_rollback_hold_on_a_packed_table(runner):
    """`INSERT INTO` appends a second page, DELETE and UPDATE swap pages
    through `replace_pages` (a page with a hole is concatenated plainly), a
    rolled-back transaction restores the pages it found."""
    runner.execute("CREATE TABLE memory.default.o2 AS SELECT * FROM tpch.sf0_01.orders")
    count = lambda: runner.execute("SELECT count(*), sum(o_totalprice) FROM memory.default.o2").rows[0]
    n, total = count()
    assert n == 15000
    assert runner.execute("INSERT INTO memory.default.o2 SELECT * FROM tpch.sf0_01.orders").rows == [(15000,)]
    with scans() as seen:
        assert count() == (2 * n, 2 * total)
    # two pages of 16,384 hold 30,000 rows: their class is what they have together
    assert seen == {"packed": 0, "plain": 1}
    (deleted,), = runner.execute("DELETE FROM memory.default.o2 WHERE o_orderkey % 2 = 0").rows
    kept = runner.execute("SELECT count(*) FROM tpch.sf0_01.orders WHERE o_orderkey % 2 <> 0").rows[0][0]
    assert deleted == 2 * (n - kept)
    with scans() as seen:
        assert count()[0] == 2 * kept
    assert seen == {"packed": 0, "plain": 1}  # holes: not prefix-live
    runner.execute("START TRANSACTION")
    runner.execute("UPDATE memory.default.o2 SET o_totalprice = 0")
    assert count() == (2 * kept, 0)
    runner.execute("DELETE FROM memory.default.o2")
    assert count()[0] == 0
    runner.execute("ROLLBACK")
    n_after, total_after = count()
    assert n_after == 2 * kept and total_after > 0
    runner.execute("DROP TABLE memory.default.o2")


# ------------------------------------------------------------- the mesh tier


def test_mesh_shards_a_quarter_of_the_packed_capacity(tpch):
    """`_shard_scans` pads a scan's page to four shards of a power of two:
    of the packed page's capacity (18 splits of 4,096 hold 59,957 rows: 65,536,
    so 16,384 a shard), not of the 73,728 the splits have together (32,768)."""
    n = 4
    if len(jax.devices()) < n:
        pytest.skip(f"need {n} devices")
    dist = DistributedQueryRunner.tpch(SCALE, n_workers=n, split_target_rows=SPLIT_ROWS)
    mesh = mr.MeshQueryRunner(
        session=dist.session, n_devices=n, catalogs=dist.catalogs, metadata=dist.metadata
    )
    sql = "SELECT sum(l_quantity), count(*) FROM lineitem"
    with scans() as seen:
        specs, _ = mesh._shard_scans(dist.plan_distributed(sql))
    assert seen == {"packed": 1, "plain": 0}
    (spec,) = specs
    packed = capacity_class(ROWS["lineitem"])
    assert packed == 65536 < SPLITS["lineitem"] * 4096
    per_shard = _round_capacity(math.ceil(packed / n), base=8)
    assert spec.page.capacity == n * per_shard == 65536
    assert _round_capacity(math.ceil(SPLITS["lineitem"] * 4096 / n), base=8) == 2 * per_shard
    # the live rows are a prefix of the global page: the first shards hold them
    active = np.asarray(spec.page.active)
    assert active[: ROWS["lineitem"]].all() and not active[ROWS["lineitem"]:].any()
    local = LocalQueryRunner.tpch(scale=SCALE)
    assert dist.execute(sql).rows == local.execute(sql).rows
    assert dist.last_tier == "ici"
