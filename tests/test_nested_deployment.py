"""The nested-subquery deployment (`tpch_nested_1chip`: TPC-H Q2, Q11, Q15, Q16)
on the CPU at SF0.01: the statements in the specification's text against the
templates' plain reference through the served client, Q16's answer over
several protocol pages, the float32 control, an altered answer, what the
program had to learn for them (a WITH query's column aliases, a comparison of
decimals of different scales that keeps its digits), the span attributes and
the counter of the distinct path and of `encode`, the readers
`distinct_agg_pct` and `result_path_pct`, and the runner's refusal."""

import json
import math
import random
import time
import types
from decimal import Decimal

import numpy as np
import pytest

from benchmark import control, traffic
from benchmark import reference as ref
from benchmark.layer_metrics import distinct_agg_pct, result_path_pct
from benchmark.templates import q02, q11, q15, q16
from benchmark.traffic import Traffic, draw_params, load_mix
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.planner import logical_planner as LP
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime import executor as E
from trino_tpu.runtime.metrics import REGISTRY
from trino_tpu.runtime.tracing import TRACER
from trino_tpu.spi.types import decimal_type

SCALE = 0.01
TEMPLATES = {"q02": q02, "q11": q11, "q15": q15, "q16": q16}
TABLES = ("part", "supplier", "partsupp", "nation", "region", "lineitem")
SEEDS = [1, 4_000_000_000]
VALIDATION_16 = {"m": 4, "n": 5, "syllable1": "MEDIUM", "syllable2": "POLISHED",
                 "sizes": [49, 14, 23, 45, 19, 3, 36, 9]}


@pytest.fixture(scope="module")
def runner():
    r = LocalQueryRunner.tpch(scale=SCALE)
    r.register_catalog("memory", MemoryConnector())
    for table in TABLES:
        r.execute(f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.{r.session.schema}.{table}")
    return r


@pytest.fixture(scope="module")
def host():
    wanted: dict = {}
    for module in TEMPLATES.values():
        for table, columns in module.COLUMNS.items():
            wanted.setdefault(table, [])
            wanted[table] += [c for c in columns if c not in wanted[table]]
    return ref.host_columns(SCALE, wanted)


@pytest.fixture(scope="module")
def server(runner):
    from trino_tpu.server import CoordinatorServer

    server = CoordinatorServer(runner).start()
    yield server
    server.stop()


@pytest.fixture(scope="module")
def client(server):
    """The served path, as the benchmark drives it: decimals arrive as exact strings."""
    from trino_tpu.client import StatementClient

    return StatementClient(f"http://{server.address}", timeout=600.0)


def sql_of(module, params, schema="memory.default"):
    return module.SQL.format(schema=schema, **module.literals(params))


def closed_root(trace_id):
    """The statement's finished tree: its root closes once the last page is out."""
    for _ in range(200):
        spans = TRACER.spans(trace_id)
        if spans and spans[0].end_ns is not None:
            return spans
        time.sleep(0.01)
    raise AssertionError(f"the statement {trace_id} never closed")


# --------------------------------------------- the system against the reference


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_statement_equals_the_plain_reference(client, host, name, seed):
    """Every statement `nested_stream` draws for the seed, as the traffic generator draws it."""
    module = TEMPLATES[name]
    mine = [s for s in Traffic(load_mix("nested_stream"), seed, "memory.default").statements
            if s.template == name]
    assert len(mine) == 2 and mine[0].params != mine[1].params
    assert [s.params for s in mine] == draw_params(module.DOMAIN, random.Random(f"{seed}:params:{name}"), 2)
    for statement in mine:
        want = module.expect(host, statement.params, ref.EXACT)
        assert want, statement.label   # an empty answer checks nothing
        comparison = ref.Comparison()
        got = client.execute(statement.sql).rows
        assert comparison.rows(statement.label, got, want, ref.as_client(want)), comparison.report()
        assert comparison.correct


def test_the_statements_are_the_specifications_text():
    assert q02.SQL.count("r_name = '{region}'") == 2 and "SELECT min(ps_supplycost)" in q02.SQL
    assert "p_type LIKE '%{type}'" in q02.SQL and q02.SQL.rstrip().endswith("LIMIT 100")
    assert q11.SQL.count("sum(ps_supplycost * ps_availqty)") == 3 and "HAVING" in q11.SQL
    assert q15.SQL.startswith("WITH revenue0 (supplier_no, total_revenue) AS (")
    assert "SELECT max(total_revenue)" in q15.SQL and "INTERVAL '3' MONTH" in q15.SQL
    assert "count(DISTINCT ps_suppkey)" in q16.SQL and "p_type NOT LIKE '{type}%'" in q16.SQL
    assert "ps_suppkey NOT IN (" in q16.SQL and "s_comment LIKE '%Customer%Complaints%'" in q16.SQL
    sizes = {name: math.prod(len(v) for v in m.DOMAIN.values()) for name, m in TEMPLATES.items()}
    assert sizes == {"q02": 1250, "q11": 22, "q15": 58, "q16": 18000}
    assert set(q11.TIED_AT_SF3).isdisjoint(q11.DOMAIN["nation"]) and len(q11.TIED_AT_SF3) == 3
    assert q11.literals({"nation": "GERMANY"})["fraction"] == "0.0000333333"   # 0.0001 / SF3
    assert q15.DOMAIN["month"][0] == "1993-01" and q15.DOMAIN["month"][-1] == "1997-10"
    config = json.loads((traffic.ROOT / "configs" / "tpch_nested_1chip.json").read_text())
    assert config["query_set"] == sorted(TEMPLATES) and config["scale_factor"] == 3
    subqueries = json.loads((traffic.ROOT / "configs" / "tpch_subqueries_1chip.json").read_text())
    assert config["guarantees"] == subqueries["guarantees"]


def test_q16s_answer_comes_back_over_several_protocol_pages(server, client, host, monkeypatch):
    """At SF0.01 an answer is some hundreds of rows, under one page of 4,096:
    the coordinator pages at 64 here, as it pages Q16's 26,699 rows at SF3."""
    from trino_tpu.server import coordinator

    monkeypatch.setattr(coordinator, "PAGE_ROWS", 64)
    want = q16.expect(host, VALIDATION_16, ref.EXACT)
    res = client.execute(sql_of(q16, VALIDATION_16))
    assert res.rows == ref.as_client(want) and len(want) > 3 * 64
    spans = closed_root(res.query_id)
    root = spans[0].attributes
    assert root["pages"] == -(-len(want) // 64) and root["rows"] == len(want)
    assert sum(s.name == "client_turn" for s in spans) >= root["pages"] - 1   # a turn after each page but the last
    (encoded,) = [s.attributes for s in spans if s.name == "encode"]
    assert encoded["rows"] == len(want) and encoded["columns"] == 4
    assert encoded["capacity"] >= encoded["rows"]
    tree = [s.to_dict() for s in spans]
    assert 0.0 < result_path_pct.of([tree]) < 100.0
    noted = result_path_pct.notes([tree])
    assert noted["pages_per_statement"] == root["pages"] and noted["rows_per_statement"] == len(want)
    assert noted["encode_capacity_per_row"] == encoded["capacity"] / len(want)


def test_the_float32_control_is_not_correct(capsys):
    """Float32 carries neither Q11's sums of ps_supplycost * ps_availqty (a
    product reaches 1e9 cents) nor Q15's revenues; Q2 and Q16 sum nothing."""
    assert control.main(["--workload", "resident_nested_stream", "--seeds", "5", "6",
                         "--scale", str(SCALE)]) == 0
    for text in capsys.readouterr().out.strip().splitlines():
        seen = json.loads(text)
        assert seen["correct"] is False
        assert seen["compared"]["exact_cells_wrong"]["value"] > 0


def test_float32_gets_q11_and_q15_wrong_and_q02_q16_right(host):
    for module, params in ((q11, {"nation": "GERMANY"}), (q15, {"month": "1995-04"})):
        exact, single = module.expect(host, params, ref.EXACT), module.expect(host, params, ref.FLOAT32)
        assert exact != single
    for module, params in ((q02, {"size": 15, "type": "BRASS", "region": "EUROPE"}), (q16, VALIDATION_16)):
        assert module.expect(host, params, ref.EXACT) == module.expect(host, params, ref.FLOAT32)


def test_an_altered_answer_is_found(client, host):
    for module, params, column in ((q11, {"nation": "KENYA"}, 1), (q16, VALIDATION_16, 3)):
        want = module.expect(host, params, ref.EXACT)
        got = client.execute(sql_of(module, params)).rows
        assert ref.Comparison().rows("right", got, want, ref.as_client(want))
        altered = [list(r) for r in got]
        cell = altered[len(altered) // 2][column]   # a decimal as a string, or a count
        altered[len(altered) // 2][column] = str(Decimal(cell) + Decimal("0.01")) if module is q11 else cell + 1
        comparison = ref.Comparison()
        assert not comparison.rows("altered", altered, want, ref.as_client(want))
        assert comparison.values["exact_cells_wrong"] == 1 and not comparison.correct
    # a row left out of a paged answer is a wrong shape
    comparison = ref.Comparison()
    assert not comparison.rows("short", got[:-1], want)
    assert comparison.values["statements_wrong_shape"] == 1


# ------------------------------------------- what the program had to learn


def test_a_with_query_takes_its_column_aliases(runner):
    rows = runner.execute(
        "WITH v (a, b) AS (SELECT n_nationkey, n_name FROM memory.default.nation) "
        "SELECT v.b, w.a FROM v, v AS w WHERE v.a = w.a AND v.a < 2 ORDER BY v.b").rows
    assert rows == [("ALGERIA", 0), ("ARGENTINA", 1)]
    with pytest.raises(LP.SemanticError, match="has 2 columns but 1 column aliases"):
        runner.execute("WITH v (a) AS (SELECT 1, 2) SELECT a FROM v")
    with pytest.raises(LP.SemanticError):   # the alias replaces the inner name
        runner.execute("WITH v (a) AS (SELECT n_name FROM memory.default.nation) SELECT n_name FROM v")


def test_decimals_of_different_scales_compare_in_all_their_digits(runner):
    """decimal(18,2) against decimal(18,12): the short common type is
    decimal(18,12), whose cast of 12,027,144.12 wraps in int64; the
    comparison is made in decimal(28,12), an Int128, and answers right."""
    assert LP._exact_comparison_type(decimal_type(18, 2), decimal_type(18, 12)) == decimal_type(28, 12)
    assert LP._exact_comparison_type(decimal_type(12, 2), decimal_type(18, 3)) is None   # Q17: fits 18
    assert LP._exact_comparison_type(decimal_type(18, 2), decimal_type(18, 2)) is None
    rows = runner.execute(
        "SELECT CAST(12027144.12 AS decimal(18,2)) > CAST(26419.730901576012 AS decimal(18,12)), "
        "CAST(12027144.12 AS decimal(18,2)) < CAST(26419.730901576012 AS decimal(18,12)), "
        "CAST(26419.73 AS decimal(18,2)) > CAST(26419.730901576012 AS decimal(18,12))").rows
    assert rows == [(True, False, False)]


def _widened_in(runner, statements, monkeypatch):
    """The comparisons planned in a long decimal because of their scales."""
    seen = []
    real = LP._exact_comparison_type

    def spy(a, b):
        wide = real(a, b)
        if wide is not None:
            seen.append((a.display(), b.display()))
        return wide

    monkeypatch.setattr(LP, "_exact_comparison_type", spy)
    for sql in statements:
        runner.plan_sql(sql)
    return seen


def test_only_q11s_comparison_is_widened_among_the_benchmarks_statements(runner, monkeypatch):
    """Every template of every cell, planned: the other cells' statements
    compare as they did before this PR."""
    import importlib

    from benchmark import harness

    others = set()
    for cell in harness.manifest()["workloads"]:
        others |= {t["name"] for t in load_mix(cell["traffic"])["templates"]}
    others -= set(TEMPLATES)
    statements = []
    for name in sorted(others):
        module = importlib.import_module(f"benchmark.templates.{name}")
        params = draw_params(module.DOMAIN, random.Random(name), 1)[0]
        statements.append(module.SQL.format(schema="memory.default", **module.literals(params)))
    assert len(statements) >= 13
    # lineitem, orders and customer are not loaded here: plan the others against the generator
    statements = [s.replace("memory.default", f"tpch.{runner.session.schema}") for s in statements]
    assert _widened_in(runner, statements, monkeypatch) == []
    mine = [sql_of(m, draw_params(m.DOMAIN, random.Random(n), 1)[0]) for n, m in sorted(TEMPLATES.items())]
    assert _widened_in(runner, mine, monkeypatch) == [("decimal(18,2)", "decimal(18,12)")]


# --------------------------------------------------------- spans and counters


def test_the_distinct_path_states_what_it_deduplicated(runner, host):
    before = REGISTRY.counter(E.DISTINCT_COUNTER).value
    res = runner.execute(sql_of(q16, VALIDATION_16))
    assert REGISTRY.counter(E.DISTINCT_COUNTER).value - before == 1
    spans = TRACER.spans(res.trace_id)
    (agg,) = [s for s in spans if s.name == "op:AggregationNode" and "distinct" in s.attributes]
    a = agg.attributes
    assert a["distinct"].startswith("ps_suppkey")
    # the rows into the dedup are partsupp's rows of the chosen parts; its groups
    # are the (brand, type, size, supplier) tuples, each once
    ps, part = host["partsupp"], host["part"]
    lit = q16.literals(VALIDATION_16)
    pos, _ = ref.lookup(part["p_partkey"], ps["ps_partkey"])
    typed = np.array([t.startswith(lit["type"]) for t in q16.population.PART_TYPES])
    chosen = ((part["p_brand"] != q16.population.BRANDS.index(lit["brand"])) & ~typed[part["p_type"]]
              & np.isin(part["p_size"], VALIDATION_16["sizes"]))
    keep = chosen[pos]
    # as `rows_in`: the live rows where the executor counted them, else the page's capacity
    assert int(keep.sum()) <= a["distinct_rows_in"] <= len(pos)
    tuples = {(part["p_brand"][p], part["p_type"][p], part["p_size"][p], s)
              for p, s in zip(pos[keep], ps["ps_suppkey"][keep])}
    assert a["distinct_groups"] == len(tuples)
    # the values were on the host already: the dedup's own read, and no other
    (read,) = [s for s in spans if s.parent_id == agg.span_id and s.name == "sync:num_groups"
               and s.attributes["value"] == a["distinct_groups"]]
    assert read is not None
    assert a["groups"] == len(res.rows)       # the count over the dedup, noted after it
    tree = [s.to_dict() for s in spans]
    assert 0.0 < distinct_agg_pct.of([tree]) < 100.0


def test_a_statement_without_distinct_notes_none(runner):
    res = runner.execute(sql_of(q11, {"nation": "KENYA"}))
    spans = TRACER.spans(res.trace_id)
    assert not any("distinct" in s.attributes for s in spans)
    assert distinct_agg_pct.of([[s.to_dict() for s in spans]]) is None


def _span(name, span_id, parent, start, end, **attributes):
    return {"name": name, "spanId": span_id, "parentSpanId": parent, "startNs": start, "endNs": end,
            "attributes": attributes}


def test_the_readers_by_hand():
    tree = [
        _span("statement", 1, None, 0, 1000, pages=3, rows=9000),
        _span("execution", 2, 1, 0, 600),
        _span("op:AggregationNode", 3, 2, 100, 500, distinct="ps_suppkey_1", distinct_rows_in=10,
              distinct_groups=7),
        _span("op:JoinNode", 4, 3, 100, 200),          # an input: another operator's time
        _span("sync:num_groups", 5, 3, 300, 400),      # the aggregation waiting: its own
        _span("op:AggregationNode", 6, 2, 500, 550),   # no DISTINCT
        _span("encode", 7, 1, 600, 700, rows=9000, capacity=16384, columns=4),
        _span("result_stream", 8, 1, 700, 750, rows=4096),
        _span("client_turn", 9, 1, 750, 800),
        _span("result_stream", 10, 1, 800, 850, rows=4096),
    ]
    assert distinct_agg_pct.of([tree]) == pytest.approx(100.0 * 300 / 1000)
    assert result_path_pct.of([tree]) == pytest.approx(100.0 * 250 / 1000)
    assert result_path_pct.notes([tree]) == {"pages_per_statement": 3.0, "rows_per_statement": 9000.0,
                                             "encode_capacity_per_row": 16384 / 9000}
    record = types.SimpleNamespace(start=0.0, statement=types.SimpleNamespace(label="q16{}"))
    assert distinct_agg_pct.by_statement([tree], [record]) == {"q16{}": [[10, 7]]}
    # a program whose spans state no distinct (the parent of PR 40), and no encode: nothing, not 0
    for span in tree:
        span["attributes"].pop("distinct", None)
    assert distinct_agg_pct.of([tree]) is None
    assert result_path_pct.of([[s for s in tree if s["name"] != "encode"]]) is None
    # an encode without `capacity` (the parent): the share, and no capacity in the notes
    tree[6]["attributes"].pop("capacity")
    assert "encode_capacity_per_row" not in result_path_pct.notes([tree])
    nothing = types.SimpleNamespace(_statement_trees=None, notes={})
    assert distinct_agg_pct.read(nothing) is None and result_path_pct.read(nothing) is None


def test_a_page_of_the_protocol_is_what_json_value_makes_of_each_cell(runner):
    """`_json_rows` decides `_json_value` once a column: a plain type's values
    go as they are, every other type's through `_json_value`, cell for cell
    the same wire values as before."""
    from trino_tpu.server.coordinator import _json_rows, _json_value

    res = runner.execute(
        "SELECT n_nationkey, n_name, CAST(n_nationkey AS decimal(12,2)) * 0.5, DATE '1995-03-01', "
        "CAST(n_nationkey AS double) / 7, n_nationkey > 3, ARRAY[n_nationkey, NULL], "
        "CASE WHEN n_nationkey = 2 THEN NULL ELSE n_regionkey END, CAST(n_nationkey AS real) "
        "FROM memory.default.nation ORDER BY n_nationkey")
    types = res.column_types
    generic = [[_json_value(v, t) for v, t in zip(row, types)] for row in res.rows]
    assert _json_rows(res.rows, types) == generic
    assert _json_rows(res.rows, [None] * len(types)) == [[_json_value(v) for v in row] for row in res.rows]
    plain = [row[:2] for row in res.rows]
    assert _json_rows(plain, types[:2]) == [list(r) for r in plain]
    assert generic[2][2] == "1.000" and generic[2][3] == "1995-03-01" and generic[2][7] is None


# ------------------------------------------------------------- the runner


def test_the_runner_refuses_a_program_without_with_column_aliases(monkeypatch, capsys):
    """The parent of PR 40 raises on Q15's view after the load: the
    configuration's runner ends at once with its own code there."""
    from benchmark.runners import local_memory_nested as runner

    config = json.loads((traffic.ROOT / "configs" / "tpch_nested_1chip.json").read_text())
    assert config["runner"] == "local_memory_nested"

    def parent(self, sql):
        raise LP.SemanticError("WITH column aliases not supported yet")

    monkeypatch.setattr(LocalQueryRunner, "plan_sql", parent)
    with pytest.raises(SystemExit) as refused:
        runner.start({**config, "scale_factor": SCALE})
    assert refused.value.code == runner.REFUSED == 4
    assert "not run" in capsys.readouterr().out


def test_the_runner_is_local_memory_on_this_program():
    from benchmark.runners import local_memory, local_memory_nested

    assert local_memory_nested.load is local_memory.load
    served = local_memory_nested.start({"name": "tpch_nested_1chip", "scale_factor": SCALE})
    assert "memory" in served.catalogs.names() and served.execute("SELECT 1").rows == [(1,)]
