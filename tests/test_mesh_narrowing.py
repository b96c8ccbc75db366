"""The mesh tier sizes what flows between its stages by what they hold
(parallel/mesh_runner.py on runtime/adaptive.py's narrowing executor).

The statements are those of the benchmark's cell `mesh4_stream`
(benchmark/mixes/mesh_stream.json: Q1 at DELTA = 90, Q14 at 1995-09-01, q06)
over the memory catalog, as the runner `mesh_memory` serves them: four of the
host devices conftest.py gives, SF0.01."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.parallel import mesh_runner as mr
from trino_tpu.parallel.runner import DistributedQueryRunner
from trino_tpu.planner.plan import TableScanNode, visit_plan
from trino_tpu.runtime import LocalQueryRunner, capstore
from trino_tpu.runtime.adaptive import AdaptiveQuery, settled_capacity
from trino_tpu.runtime.metrics import REGISTRY
from trino_tpu.runtime.tracing import TRACER

N = 4
SCALE = 0.01

Q1 = """SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty,
       sum(l_extendedprice) AS sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price,
       avg(l_discount) AS avg_disc, count(*) AS count_order
FROM memory.default.lineitem
WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '90' DAY
GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus"""
Q14 = """SELECT 100.00 * sum(CASE WHEN p_type LIKE 'PROMO%'
                         THEN l_extendedprice * (1 - l_discount) ELSE 0 END)
       / sum(l_extendedprice * (1 - l_discount)) AS promo_revenue
FROM memory.default.lineitem, memory.default.part
WHERE l_partkey = p_partkey AND l_shipdate >= DATE '1995-09-01'
  AND l_shipdate < DATE '1995-09-01' + INTERVAL '1' MONTH"""
Q6 = """SELECT sum(l_extendedprice * l_discount) AS revenue
FROM memory.default.lineitem
WHERE l_shipdate >= DATE '{date}' AND l_shipdate < DATE '{date}' + INTERVAL '1' YEAR
  AND l_discount BETWEEN {discount} - 0.01 AND {discount} + 0.01 AND l_quantity < {quantity}"""
MESH_STREAM = {
    "q01v": Q1,
    "q14v": Q14,
    "q06-1994": Q6.format(date="1994-01-01", discount="0.06", quantity=24),
    "q06-1996": Q6.format(date="1996-01-01", discount="0.03", quantity=25),
}
# what narrows at SF0.01, where a shard of lineitem has 16,384 rows of capacity
NARROWED = {
    "grouped aggregation": "SELECT l_suppkey, sum(l_quantity), count(*) FROM memory.default.lineitem "
                           "GROUP BY l_suppkey",
    "filter then join": Q14,
    "repartition": "SELECT l_orderkey, count(*) FROM memory.default.lineitem "
                   "WHERE l_shipdate < DATE '1992-04-01' GROUP BY l_orderkey",
}


@pytest.fixture(scope="module")
def runners():
    if len(jax.devices()) < N:
        pytest.skip(f"need {N} devices")
    dist = DistributedQueryRunner.tpch(SCALE, n_workers=N)
    dist.catalogs.register("memory", MemoryConnector())
    local = LocalQueryRunner.tpch(scale=SCALE)
    local.register_catalog("memory", dist.catalogs.get("memory"))
    for table in ("lineitem", "part", "orders"):
        local.execute(f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.sf0_01.{table}")
    return dist, local


def fresh_mesh(dist) -> mr.MeshQueryRunner:
    """A mesh runner of its own over the distributed runner's catalogs, with
    nothing settled: no program and no capacities kept."""
    capstore.clear_memory()
    return mr.MeshQueryRunner(
        session=dist.session, n_devices=N, catalogs=dist.catalogs, metadata=dist.metadata
    )


def programs():
    """The `mesh:program` spans of the newest statement tree."""
    tree = TRACER.finished("statement")[-1]
    return [s for s in tree if s.name == "mesh:program"]


def run_program(mesh, subplan, counts, pages, caps):
    program = mesh._build_program(subplan, counts, caps, 1.0)
    page, measured = program.fn(*pages)
    assert int(np.asarray(measured)[0]) == 0, "overflow"
    return program, np.asarray(measured), sorted_rows(page, mesh.n)


def sorted_rows(out_page, n):
    """Shard 0's block of the root page as sorted rows."""
    cap = out_page.capacity // n
    active = np.asarray(out_page.active[:cap])
    cols = [np.asarray(c.data[:cap])[active] for c in out_page.columns]
    return sorted(zip(*[c.tolist() for c in cols]))


@pytest.mark.parametrize("name", list(MESH_STREAM))
def test_mesh_stream_statements_answer_on_tier_ici(runners, name):
    dist, local = runners
    got = dist.execute(MESH_STREAM[name])
    assert (dist.last_tier, dist.last_tier_reason) == ("ici", None)
    assert got.rows == local.execute(MESH_STREAM[name]).rows


@pytest.mark.parametrize("what", list(NARROWED))
def test_narrowed_program_equals_the_unnarrowed_one(runners, what):
    dist, _ = runners
    mesh = fresh_mesh(dist)
    subplan = dist.plan_distributed(NARROWED[what])
    specs, counts = mesh._shard_scans(subplan)
    pages = [s.page for s in specs]
    points = mesh._points(subplan)
    wide, measured, want = run_program(mesh, subplan, counts, pages, [None] * len(points))
    k = len(wide.ordinals)
    caps = [None] * len(points)
    for o, a, unhinted in zip(wide.ordinals, measured[1 + k:], wide.unhinted):
        caps[o] = settled_capacity(a, unhinted)
    narrow, _, got = run_program(mesh, subplan, counts, pages, caps)
    assert got == want and want
    assert sum(narrow.ran) * 2 <= sum(wide.ran), (narrow.ran, wide.ran)


def test_forced_minimum_hints_overflow_retry_and_stay_exact(runners, monkeypatch):
    dist, local = runners
    mesh = fresh_mesh(dist)
    monkeypatch.setattr(
        mr.MeshQueryRunner, "_seed_capacities", lambda self, subplan, points: [8] * len(points)
    )
    sql = "SELECT count(*), sum(l_quantity) FROM memory.default.orders JOIN memory.default.lineitem " \
          "ON o_orderkey = l_orderkey"
    retries = REGISTRY.counter(mr.RETRIES_COUNTER)
    attempts = REGISTRY.counter(mr.ATTEMPTS_COUNTER)
    before = retries.value, attempts.value
    got = mesh.execute(sql)
    spans = programs()
    assert got.rows == local.execute(sql).rows
    assert len(spans) > 1 and spans[0].attributes["overflowed"] > 0
    assert spans[-1].attributes["overflowed"] == 0
    assert [s.attributes["attempt"] for s in spans] == list(range(len(spans)))
    for key in ("narrow_points", "narrow_rows", "narrow_capacity"):
        assert spans[-1].attributes[key] > 0
    assert retries.value - before[0] >= 1
    assert attempts.value - before[1] == len(spans)


@pytest.mark.parametrize("name,hints,arrays_within", [
    ("q01v", "unhinted", 1), ("q01v", "seeded", 1),
    ("q14v", "unhinted", 4), ("q14v", "seeded", 4), ("q14v", "settled", 1),
])
def test_no_page_outgrows_the_shard_it_scans(runners, name, hints, arrays_within):
    """Traced over abstract pages of 2^20 rows a shard (no compile): no page
    between two operators has more rows of capacity than lineitem's shard,
    whatever the hints, and no array inside the shard_map body more than
    ``arrays_within`` shards (a join's working set is twice its probe side and
    its build side: within one shard once the filter's output is narrowed, as
    the program a statement keeps has it). Q1's final fragment once had
    536,870,912 rows of capacity, 32 shards, to return four rows."""
    dist, _ = runners
    mesh = fresh_mesh(dist)
    subplan = dist.plan_distributed(MESH_STREAM[name])
    shard = {"lineitem": 1 << 20, "part": 1 << 16}
    pages, counts = [], {}
    for frag in subplan.fragments:
        scans = []
        visit_plan(frag.root, lambda n: scans.append(n) if isinstance(n, TableScanNode) else None)
        counts[frag.fragment_id] = len(scans)
        for node in scans:
            rows = N * shard[str(node.table.schema_table).split(".")[-1]]
            pages.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype), mesh._load_scan(node)
            ))
    points = mesh._points(subplan)
    caps = {
        "unhinted": [None] * len(points),
        "seeded": mesh._seed_capacities(subplan, points),
        # a month of lineitem: what the filter and the join settle at, a 64th of the shard
        "settled": [shard["lineitem"] >> 6 if type(p).__name__ in ("FilterNode", "JoinNode") else None
                    for p in points],
    }[hints]
    program = mesh._build_program(subplan, counts, caps, 1.0)
    jaxpr = jax.make_jaxpr(program.fn.__wrapped__)(*pages)
    (body,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "shard_map"]
    assert max(rows_of(body.params["jaxpr"])) <= arrays_within * shard["lineitem"]
    assert max(program.ran) <= shard["lineitem"]


def rows_of(jaxpr):
    """Leading dimension of every array a jaxpr makes, nested jaxprs too."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if shape:
                yield shape[0]
        for param in eqn.params.values():
            for inner in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from rows_of(inner)


def compile_requests() -> float:
    return REGISTRY.counter("trino_tpu_xla_compiles_total").value


@pytest.mark.parametrize("tier", ["mesh", "one chip"])
def test_second_execution_runs_the_settled_program(runners, tier):
    """Both traced tiers settle a statement's capacities in its first
    execution and keep them by the plan's fingerprint: the second execution
    is one attempt at those capacities and compiles nothing."""
    dist, local = runners
    capstore.clear_memory()
    if tier == "mesh":
        mesh = fresh_mesh(dist)
        first = mesh.execute(Q14).rows
        assert len(programs()) == 2        # the seeded program, then the one it keeps
        before = compile_requests()
        assert mesh.execute(Q14).rows == first
        (span,) = programs()
        assert span.attributes["cached"] and span.attributes["overflowed"] == 0
        assert compile_requests() == before
    else:
        plan = local.plan_sql(Q14)
        first = AdaptiveQuery(plan, local.metadata, local.session)
        first.tune()
        second = AdaptiveQuery(local.plan_sql(Q14), local.metadata, local.session)
        assert second.seeded_from_store
        second.tune()
        assert (second.compiles, second.attempts) == (1, 1)
        assert [second.caps.get(id(n)) for n in second._candidates] == \
            [first.caps.get(id(n)) for n in first._candidates]


def test_mesh_attempts_per_query_reader():
    """benchmark/layer_metrics/mesh_attempts_per_query.py on the tree the chip
    recorded (PR 28: one program a statement, no narrowing attributes yet) and
    on trees made by hand."""
    import sys

    repo = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo))
    from benchmark.layer_metrics import mesh_attempts_per_query as reader

    recorded = json.loads((repo / "benchmark/tests/recorded_mesh.json").read_text())
    assert reader.of(recorded["trees"]) == 1.0
    assert reader.fill(recorded["trees"]) is None

    def tree(*attempts):
        spans = [{"name": "statement", "endNs": 9, "attributes": {}}]
        for rows, capacity in attempts:
            spans.append({"name": "mesh:program", "endNs": 5,
                          "attributes": {"narrow_rows": rows, "narrow_capacity": capacity}})
        return spans

    trees = [tree((10, 100)), tree((90, 100), (60, 200)), tree((40, 400))]
    assert reader.of(trees) == pytest.approx(4 / 3)
    assert reader.fill(trees) == pytest.approx(200 / 800)
    assert reader.of([tree()]) is None      # no statement ran on the mesh tier
