"""The memory catalog keeps each table's row count (connectors/memory.py).

``_StoredTable.rows`` is set wherever ``pages`` changes (``insert``,
``replace_pages``: counted on the device when the rows change), so
``get_table_statistics`` reads no page: one case per writer, each compared
with a recount of the pages' masks, and each asked again with pages whose
mask cannot be copied to the host.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.spi.connector import ColumnMetadata, SchemaTableName, TableHandle
from trino_tpu.spi.page import Column, Page
from trino_tpu.spi.types import BIGINT

ACCT = SchemaTableName("default", "acct")
BUCKETED = SchemaTableName("default", "facts")


class _Unreadable:
    """Stands where a stored page stood: any look at its mask fails."""

    @property
    def active(self):
        raise AssertionError("statistics read a page's mask")

    def num_rows(self):
        raise AssertionError("statistics counted a page")


def _page(values, live=None) -> Page:
    values = np.asarray(values, np.int64)
    live = np.ones(len(values), bool) if live is None else np.asarray(live)
    return Page(
        (Column(BIGINT, jnp.asarray(values), jnp.asarray(np.ones(len(values), bool))),),
        jnp.asarray(live),
    )


def _ctas(runner, mc):
    return ACCT, 3


def _insert(runner, mc):
    runner.execute("INSERT INTO memory.default.acct SELECT 9, 900, 'x'")
    runner.execute(
        "INSERT INTO memory.default.acct SELECT 10, 1000, 'y' UNION ALL SELECT 11, 1100, 'z'"
    )
    return ACCT, 6


def _delete(runner, mc):
    runner.execute("DELETE FROM memory.default.acct WHERE bal > 150")
    return ACCT, 1


def _update(runner, mc):
    runner.execute("UPDATE memory.default.acct SET bal = bal + 1 WHERE id < 3")
    return ACCT, 3


def _merge(runner, mc):
    runner.execute(
        "CREATE TABLE memory.default.delta AS "
        "SELECT 2 AS id, 999 AS newbal UNION ALL SELECT 7, 700 UNION ALL SELECT 3, 0"
    )
    runner.execute(
        "MERGE INTO memory.default.acct a USING memory.default.delta d ON a.id = d.id "
        "WHEN MATCHED AND d.newbal = 0 THEN DELETE "
        "WHEN MATCHED THEN UPDATE SET bal = d.newbal "
        "WHEN NOT MATCHED THEN INSERT (id, bal, name) VALUES (d.id, d.newbal, 'new')"
    )
    return ACCT, 3  # 3 deleted, 7 inserted


def _bucketed_insert(runner, mc):
    mc.create_table(
        BUCKETED, [ColumnMetadata("k", BIGINT)], bucketed_by=["k"], bucket_count=4
    )
    # a sparse page, twice: the second insert concatenates into the buckets
    mc.insert(BUCKETED, _page(range(40), live=[i % 4 != 0 for i in range(40)]))
    mc.insert(BUCKETED, _page(range(100, 117)))
    return BUCKETED, 30 + 17


def _bucketed_replace(runner, mc):
    _bucketed_insert(runner, mc)
    # rows re-bucketed through insert: the count starts again from nothing
    mc.replace_pages(BUCKETED, [_page(range(9)), None, _page([50, 51], live=[True, False])])
    return BUCKETED, 10


def _replace_pages(runner, mc):
    stored = mc.table(ACCT).pages[0]
    dead = Page(stored.columns, jnp.zeros_like(stored.active))
    half = Page(stored.columns, stored.active & (jnp.arange(stored.capacity) < 2))
    mc.replace_pages(ACCT, [stored, dead, half])
    return ACCT, 5


def _replace_with_nothing(runner, mc):
    mc.replace_pages(ACCT, [])
    return ACCT, 0


def _drop_and_recreate(runner, mc):
    runner.execute("DROP TABLE memory.default.acct")
    assert mc.table(ACCT) is None
    runner.execute(
        "CREATE TABLE memory.default.acct AS SELECT n_nationkey AS id FROM nation"
    )
    return ACCT, 25


def _rollback(runner, mc):
    runner.execute("START TRANSACTION")
    runner.execute("INSERT INTO memory.default.acct SELECT 9, 900, 'x'")
    runner.execute("DELETE FROM memory.default.acct WHERE id = 1")
    assert mc.table(ACCT).row_count() == 3
    runner.execute("ROLLBACK")
    return ACCT, 3


@pytest.mark.parametrize(
    "write",
    [_ctas, _insert, _delete, _update, _merge, _bucketed_insert,
     _bucketed_replace, _replace_pages, _replace_with_nothing,
     _drop_and_recreate, _rollback],
    ids=lambda f: f.__name__.lstrip("_"),
)
def test_the_kept_count_is_a_recount_and_reads_no_page(write):
    runner = LocalQueryRunner.tpch(scale=0.0005)
    mc = MemoryConnector()
    runner.register_catalog("memory", mc)
    runner.execute(
        "CREATE TABLE memory.default.acct AS "
        "SELECT 1 AS id, 100 AS bal, 'a' AS name "
        "UNION ALL SELECT 2, 200, 'b' UNION ALL SELECT 3, 300, 'c'"
    )
    name, expected = write(runner, mc)
    table = mc.table(name)
    recount = sum(
        int(np.asarray(p.active).sum()) for p in table.pages if p is not None
    )
    assert table.row_count() == recount == expected
    # what the planner is given, from pages that cannot be looked at
    table.pages = [_Unreadable() for _ in table.pages]
    stats = mc.metadata().get_table_statistics(TableHandle("memory", name))
    assert stats.row_count == float(expected)
