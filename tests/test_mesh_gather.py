"""The mesh tier's answer comes home in one copy (parallel/mesh_runner.py,
`MeshQueryRunner.gather`): shard 0's block of every leaf of the root page,
taken where it lies on device 0, fetched by one `jax.device_get`; no global
array is sliced.

The statements are those of the benchmark's cell `mesh4_stream` (its
templates q01v, q06, q14v) over the memory catalog, as the runner
`mesh_memory` serves them, and a few whose answers hold NULLs, strings, no
row at all, or ARRAY, MAP and ROW values: four of the host devices
conftest.py gives, SF0.01."""

import jax
import pytest

from benchmark.traffic import load_template
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.parallel.runner import DistributedQueryRunner
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime.tracing import TRACER

N = 4
SCALE = 0.01


def cell_statement(template: str, **params) -> str:
    """A template of `mesh4_stream` over the memory catalog, at the first
    tuple of its domain where ``params`` gives none."""
    module = load_template(template)
    first = {name: values[0] for name, values in module.DOMAIN.items()}
    return module.SQL.format(schema="memory.default", **module.literals({**first, **params}))


STATEMENTS = {
    "q01v": cell_statement("q01v"),
    "q06": cell_statement("q06", year=1994, discount_cents=6, quantity=24),
    "q14v": cell_statement("q14v"),
    "nulls": "SELECT l_returnflag, sum(CASE WHEN l_quantity > 100 THEN l_quantity END), "
             "max(nullif(l_linenumber, 1)) FROM memory.default.lineitem "
             "WHERE l_orderkey < 3 GROUP BY l_returnflag ORDER BY l_returnflag",
    "strings": "SELECT p_type, p_brand, count(*) FROM memory.default.part "
               "WHERE p_size = 7 GROUP BY p_type, p_brand ORDER BY p_type, p_brand LIMIT 12",
    "empty": "SELECT l_orderkey, l_comment FROM memory.default.lineitem WHERE l_quantity > 1000",
}
# nested answers: the root page's `lengths`, `elem_valid` and `children`
# come home with its `data` and `valid`
NESTED = {
    "array": "SELECT n_nationkey, ARRAY[n_nationkey, n_regionkey] FROM nation WHERE n_nationkey < 4",
    "array with nulls, top-n": "SELECT n_nationkey, ARRAY[n_regionkey, NULL] FROM nation "
                               "ORDER BY n_nationkey DESC LIMIT 3",
    "map": "SELECT MAP(ARRAY[n_nationkey], ARRAY[n_name]) FROM nation WHERE n_regionkey = 2",
    "row": "SELECT ROW(n_nationkey, n_regionkey) FROM nation WHERE n_nationkey < 5",
}


@pytest.fixture(scope="module")
def runners():
    if len(jax.devices()) < N:
        pytest.skip(f"need {N} devices")
    dist = DistributedQueryRunner.tpch(SCALE, n_workers=N)
    dist.catalogs.register("memory", MemoryConnector())
    local = LocalQueryRunner.tpch(scale=SCALE)
    local.register_catalog("memory", dist.catalogs.get("memory"))
    for table in ("lineitem", "part"):
        local.execute(f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.sf0_01.{table}")
    return dist, local


def on_tier(dist, sql: str) -> list:
    rows = dist.execute(sql).rows
    assert (dist.last_tier, dist.last_tier_reason) == ("ici", None)
    return rows


def root_page(dist, sql: str):
    """The mesh runner and the root page of ``sql`` as `execute_subplan`
    hands it to the gather."""
    on_tier(dist, sql)
    mesh = dist._mesh_runner
    return mesh, mesh.execute_subplan(mesh.plan_distributed(sql))[1]


@pytest.mark.parametrize("name", list(STATEMENTS))
def test_the_mesh_tier_answers_as_one_chip_does(runners, name):
    dist, local = runners
    got = on_tier(dist, STATEMENTS[name])
    assert got == local.execute(STATEMENTS[name]).rows
    if name == "nulls":
        assert any(v is None for row in got for v in row) and got
    if name == "empty":
        assert got == []


@pytest.mark.parametrize("name", list(NESTED))
def test_a_nested_answer_comes_home_whole(runners, name):
    dist, local = runners
    got = on_tier(dist, NESTED[name])
    assert got and got == local.execute(NESTED[name]).rows


def test_one_copy_of_shard_zeros_block_and_no_slice(runners, monkeypatch):
    dist, _ = runners
    mesh, page = root_page(dist, STATEMENTS["q01v"])
    leaves = jax.tree_util.tree_leaves(page)
    copies, sliced = [], []
    array_type = type(leaves[0])
    device_get, getitem = jax.device_get, array_type.__getitem__

    def spy_device_get(x):
        copies.append(x)
        return device_get(x)

    def spy_getitem(self, index):
        sliced.append(self.shape)
        return getitem(self, index)

    monkeypatch.setattr(jax, "device_get", spy_device_get)
    monkeypatch.setattr(array_type, "__getitem__", spy_getitem)
    rows = mesh.gather(page)
    monkeypatch.undo()
    assert rows and sliced == []
    assert len(copies) == 1
    (blocks,) = copies
    first = mesh.mesh.devices.flat[0]
    assert len(blocks) == len(leaves) == 21  # Q1: ten columns of data and valid, and `active`
    for block, leaf in zip(blocks, leaves):
        assert block.devices() == {first}
        assert block.shape == (leaf.shape[0] // N,) + leaf.shape[1:]
        assert block.unsafe_buffer_pointer() == leaf.addressable_shards[0].data.unsafe_buffer_pointer()


@pytest.mark.parametrize("name", ["q01v", "strings", "empty"])
def test_the_gather_span_states_what_the_copy_carried(runners, name):
    dist, _ = runners
    mesh, page = root_page(dist, STATEMENTS[name])
    with TRACER.statement(STATEMENTS[name]):
        rows = mesh.gather(page)
    (span,) = [s for s in TRACER.finished("statement")[-1] if s.name == "mesh:gather"]
    leaves = jax.tree_util.tree_leaves(page)
    assert span.attributes == {
        "rows": len(rows),
        "arrays": len(leaves),
        "bytes": sum(leaf.nbytes // N for leaf in leaves),
    }
