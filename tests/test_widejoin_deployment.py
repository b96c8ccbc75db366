"""The wide-join deployment (`tpch_widejoin_1chip`: TPC-H Q4, Q7, Q8, Q12, Q19)
on the CPU: the statements in the specification's text against the templates'
plain reference through the served client (at SF0.01, and Q8 and Q19 at SF0.1
too, where SF0.01 leaves them few rows), the float32 control, what the
program had to learn for them (the disjunction an OR across a join implies of
each side, `optimizer.derive_join_disjuncts`), where the rule refuses, that
the other configurations' statements plan as before, its counter and span
attribute, and the reader `join_rows_per_query`."""

import importlib
import json
import random
import types

import pytest

from benchmark import control, harness
from benchmark import reference as ref
from benchmark.layer_metrics import join_rows_per_query
from benchmark.templates import q04, q07, q08, q12, q19
from benchmark.traffic import Traffic, draw_params, load_mix
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.planner import optimizer as O
from trino_tpu.planner.plan import FilterNode, TableScanNode
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime.metrics import REGISTRY
from trino_tpu.runtime.tracing import TRACER
from trino_tpu.sql.ir import references

SCALE = 0.01
TEMPLATES = {"q04": q04, "q07": q07, "q08": q08, "q12": q12, "q19": q19}
TABLES = ("lineitem", "orders", "customer", "part", "supplier", "partsupp", "nation", "region")
SEEDS = [1, 4_000_000_000]


def validation(module) -> dict:
    """The first value of each list of the domain: the specification's
    validation tuple (cl. 2.4.x.4)."""
    return {k: v[0] for k, v in module.DOMAIN.items()}


def sql_of(module, params, schema="memory.default"):
    return module.SQL.format(schema=schema, **module.literals(params))


def _loaded(scale, tables):
    r = LocalQueryRunner.tpch(scale=scale)
    r.register_catalog("memory", MemoryConnector())
    for table in tables:
        r.execute(f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.{r.session.schema}.{table}")
    return r


def _host(scale, modules):
    wanted: dict = {}
    for module in modules:
        for table, columns in module.COLUMNS.items():
            wanted.setdefault(table, [])
            wanted[table] += [c for c in columns if c not in wanted[table]]
    return ref.host_columns(scale, wanted)


@pytest.fixture(scope="module")
def runner():
    return _loaded(SCALE, TABLES)


@pytest.fixture(scope="module")
def host():
    return _host(SCALE, TEMPLATES.values())


def _client(runner):
    """The served path, as the benchmark drives it: decimals arrive as exact strings."""
    from trino_tpu.client import StatementClient
    from trino_tpu.server import CoordinatorServer

    server = CoordinatorServer(runner).start()
    return server, StatementClient(f"http://{server.address}", timeout=600.0)


@pytest.fixture(scope="module")
def client(runner):
    server, client = _client(runner)
    yield client
    server.stop()


def _answers_as_the_reference(client, host, module, params):
    want = module.expect(host, params, ref.EXACT)
    comparison = ref.Comparison()
    got = client.execute(sql_of(module, params)).rows
    assert comparison.rows(f"{module.__name__} {params}", got, want, ref.as_client(want)), comparison.report()
    assert comparison.correct and comparison.values["double_rel_gap"] < 1e-12
    return want


# --------------------------------------------- the system against the reference


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_statement_equals_the_plain_reference(client, host, name, seed):
    """Every statement `widejoin_stream` draws for the seed, as the traffic generator draws it."""
    module = TEMPLATES[name]
    mine = [s for s in Traffic(load_mix("widejoin_stream"), seed, "memory.default").statements
            if s.template == name]
    assert len(mine) == 2 and mine[0].params != mine[1].params
    assert [s.params for s in mine] == draw_params(module.DOMAIN, random.Random(f"{seed}:params:{name}"), 2)
    for statement in mine:
        assert statement.sql == sql_of(module, statement.params)
        assert _answers_as_the_reference(client, host, module, statement.params), statement.label


@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_the_validation_tuple_equals_the_plain_reference(client, host, name):
    want = _answers_as_the_reference(client, host, TEMPLATES[name], validation(TEMPLATES[name]))
    assert want and want != [[None]]      # rows that check something


@pytest.fixture(scope="module")
def tenth():
    """SF0.1, where Q8's one part type and Q19's three classes find enough lines."""
    runner = _loaded(0.1, ("lineitem", "orders", "customer", "part", "supplier", "nation", "region"))
    server, client = _client(runner)
    yield client, _host(0.1, (q08, q19))
    server.stop()


@pytest.mark.parametrize("name", ["q08", "q19"])
def test_at_a_tenth_of_the_scale_q08_and_q19_equal_the_plain_reference(tenth, name):
    client, host = tenth
    module = TEMPLATES[name]
    tuples = [validation(module)] + draw_params(module.DOMAIN, random.Random(f"tenth:{name}"), 2)
    for params in tuples:
        want = _answers_as_the_reference(client, host, module, params)
        assert want[0][-1] is not None      # Q19's sum found lines
    if name == "q08":   # the nation's share of each year, strictly inside (0, 1)
        assert all(0.0 < share < 1.0 for _, share in module.expect(host, tuples[0], ref.EXACT))


def test_the_statements_are_the_specifications_text():
    assert "EXISTS (" in q04.SQL and "l_commitdate < l_receiptdate" in q04.SQL
    assert "INTERVAL '3' MONTH" in q04.SQL
    assert "{schema}.nation n1, {schema}.nation n2" in q07.SQL
    assert "((n1.n_name = '{nation1}' AND n2.n_name = '{nation2}')" in q07.SQL
    assert "extract(year FROM l_shipdate)" in q07.SQL
    assert "{schema}.nation n1, {schema}.nation n2, {schema}.region" in q08.SQL
    assert ") / sum(volume) AS mkt_share" in q08.SQL
    assert "l_shipmode IN ('{shipmode1}', '{shipmode2}')" in q12.SQL
    assert q12.SQL.count("END) AS") == 2
    # Q19 keeps the join condition inside each branch, as cl. 2.4.19.1 writes it
    assert q19.SQL.count("p_partkey = l_partkey") == 3 and q19.SQL.count("'AIR REG'") == 3
    assert validation(q07) == {"nations": ["FRANCE", "GERMANY"]}
    assert q08.literals(validation(q08)) == {"nation": "BRAZIL", "region": "AMERICA",
                                             "type": "ECONOMY ANODIZED STEEL"}
    assert q12.literals({"modes": ["MAIL", "SHIP"], "year": 1994})["date"] == "1994-01-01"
    assert validation(q19) == {"quantity1": 1, "quantity2": 10, "quantity3": 20, "brand1": "Brand#12",
                               "brand2": "Brand#23", "brand3": "Brand#34"}


def test_the_float32_control_is_not_correct(capsys):
    """Float32 carries neither Q7's nor Q19's decimal sums, nor Q8's ratio to
    1e-9; Q4's and Q12's counts pass it."""
    assert control.main(["--workload", "resident_widejoin_stream", "--seeds", "5", "6",
                         "--scale", str(SCALE)]) == 0
    for text in capsys.readouterr().out.strip().splitlines():
        seen = json.loads(text)
        assert seen["correct"] is False
        assert seen["compared"]["exact_cells_wrong"]["value"] > 0


def test_float32_gets_the_sums_wrong_and_the_counts_right(host):
    for module in (q07, q08):
        assert module.expect(host, validation(module), ref.EXACT) != module.expect(
            host, validation(module), ref.FLOAT32)
    for module in (q04, q12):
        assert module.expect(host, validation(module), ref.EXACT) == module.expect(
            host, validation(module), ref.FLOAT32)


# ------------------------------------------- the disjunction across a join


def _filters_over(plan, table):
    """The predicates of the filters right above each scan of `table`."""
    out = []

    def walk(node, parent):
        if isinstance(node, TableScanNode) and str(node.table).endswith("." + table):
            out.append(parent.predicate if isinstance(parent, FilterNode) else None)
        for s in node.sources:
            walk(s, node)

    walk(plan.root, None)
    return out


def _column_of(plan, symbol) -> str:
    """The column a scan's symbol reads."""
    found = []

    def walk(node):
        if isinstance(node, TableScanNode):
            found.extend(c for s, c in node.assignments if s == symbol)
        for s in node.sources:
            walk(s)

    walk(plan.root)
    return found[0]


def _columns(plan, predicate) -> set:
    return {_column_of(plan, s) for s in references(predicate)} if predicate is not None else set()


@pytest.fixture
def without_the_rule(monkeypatch):
    def off():
        monkeypatch.setattr(O, "derive_join_disjuncts", lambda root: root)

    return off


def test_q7s_nation_scans_are_each_filtered_to_the_pair(runner):
    plan = runner.plan_sql(sql_of(q07, validation(q07)))
    filters = _filters_over(plan, "nation")
    assert len(filters) == 2
    assert all(_columns(plan, f) == {"n_name"} for f in filters)
    assert all("'FRANCE'" in str(f) and "'GERMANY'" in str(f) for f in filters)
    # the original disjunction stays above the joins
    assert "$or($and(" in runner.explain(sql_of(q07, validation(q07)))


def test_q19s_part_and_lineitem_are_each_filtered_by_the_three_classes(runner):
    plan = runner.plan_sql(sql_of(q19, validation(q19)))
    (part,) = _filters_over(plan, "part")
    (lineitem,) = _filters_over(plan, "lineitem")
    assert {"p_brand", "p_container", "p_size"} <= _columns(plan, part)
    assert all(b in str(part) for b in ("Brand#12", "Brand#23", "Brand#34"))
    assert "l_quantity" in _columns(plan, lineitem)
    assert {"l_shipmode", "l_shipinstruct"} <= _columns(plan, lineitem)   # the common conjuncts


def test_without_the_rule_the_or_stays_above_the_join(runner, without_the_rule):
    without_the_rule()
    plan = runner.plan_sql(sql_of(q07, validation(q07)))
    assert _filters_over(plan, "nation") == [None, None]
    plan = runner.plan_sql(sql_of(q19, validation(q19)))
    (part,) = _filters_over(plan, "part")
    assert "p_brand" not in _columns(plan, part)


@pytest.fixture(scope="module")
def nulls(runner):
    """Two small tables with NULLs in the columns the branches test."""
    runner.execute(
        "CREATE TABLE memory.default.ors_s AS SELECT * FROM (VALUES "
        "(1, 'FRANCE', 'z'), (2, 'GERMANY', 'z'), (3, CAST(NULL AS varchar), 'z'), "
        "(4, 'PERU', 'z'), (5, CAST(NULL AS varchar), 'q'), (6, 'FRANCE', CAST(NULL AS varchar))) "
        "AS v (k, a, a2)")
    runner.execute(
        "CREATE TABLE memory.default.ors_c AS SELECT * FROM (VALUES "
        "(1, 'GERMANY'), (2, 'FRANCE'), (3, 'FRANCE'), (4, CAST(NULL AS varchar)), (5, 'w'), "
        "(6, 'GERMANY'), (7, 'y')) AS v (k, b)")
    return runner


NULL_CASES = {
    # a NULL a where the pair is tested: rows 3 and 6 answer as without the rule
    "nation_pair": ("SELECT s.k FROM memory.default.ors_s s, memory.default.ors_c c WHERE s.k = c.k "
                    "AND ((s.a = 'FRANCE' AND c.b = 'GERMANY') OR (s.a = 'GERMANY' AND c.b = 'FRANCE')) "
                    "ORDER BY s.k", [(1,), (2,), (6,)]),
    # row 5: a is NULL, yet its second branch is TRUE; its derived OR is too
    "null_in_one_branch": ("SELECT s.k FROM memory.default.ors_s s, memory.default.ors_c c WHERE s.k = c.k "
                           "AND ((s.a = 'FRANCE' AND c.b = 'GERMANY') OR (s.a2 = 'q' AND c.b = 'w')) "
                           "ORDER BY s.k", [(1,), (5,), (6,)]),
}


@pytest.mark.parametrize("case", sorted(NULL_CASES))
def test_a_null_in_a_branchs_column_answers_as_without_the_rule(nulls, case, without_the_rule):
    sql, want = NULL_CASES[case]
    before = REGISTRY.counter(O.DERIVED_PREDICATES_COUNTER).value
    assert nulls.execute(sql).rows == want
    assert REGISTRY.counter(O.DERIVED_PREDICATES_COUNTER).value - before == 2
    without_the_rule()
    assert nulls.execute(sql).rows == want


def test_a_null_part_brand_answers_as_without_the_rule(nulls, without_the_rule):
    nulls.execute(
        "CREATE TABLE memory.default.ors_p AS SELECT * FROM (VALUES "
        "(1, 'Brand#12', 3), (2, CAST(NULL AS varchar), 3), (3, 'Brand#23', 8), (4, 'Brand#23', 30)) "
        "AS v (pk, brand, size)")
    nulls.execute(
        "CREATE TABLE memory.default.ors_l AS SELECT * FROM (VALUES "
        "(1, 5), (2, 5), (2, 15), (3, 15), (4, 15), (3, 2)) AS v (lk, qty)")
    sql = ("SELECT lk, qty FROM memory.default.ors_l, memory.default.ors_p "
           "WHERE (pk = lk AND brand = 'Brand#12' AND size <= 5 AND qty <= 10) "
           "OR (pk = lk AND brand = 'Brand#23' AND size <= 10 AND qty >= 10) ORDER BY lk, qty")
    want = [(1, 5), (3, 15)]
    assert nulls.execute(sql).rows == want
    without_the_rule()
    assert nulls.execute(sql).rows == want


def test_nothing_is_derived_through_a_left_join(nulls, without_the_rule):
    """Filtering the null-supplying side would pad rows the OR then keeps."""
    sql = ("SELECT s.k, c.b FROM memory.default.ors_s s LEFT JOIN memory.default.ors_c c ON s.k = c.k "
           "WHERE (s.a = 'FRANCE' AND c.b = 'GERMANY') OR (s.a = 'PERU' AND c.b IS NULL) ORDER BY s.k")
    before = REGISTRY.counter(O.DERIVED_PREDICATES_COUNTER).value
    plan = nulls.plan_sql(sql)
    assert REGISTRY.counter(O.DERIVED_PREDICATES_COUNTER).value == before
    assert _filters_over(plan, "ors_s") == [None] and _filters_over(plan, "ors_c") == [None]
    want = [(1, "GERMANY"), (4, None), (6, "GERMANY")]
    assert nulls.execute(sql).rows == want
    without_the_rule()
    assert nulls.execute(sql).rows == want


def test_nothing_is_derived_for_a_side_some_branch_does_not_test(nulls):
    sql = ("SELECT s.k FROM memory.default.ors_s s, memory.default.ors_c c WHERE s.k = c.k "
           "AND ((s.a = 'FRANCE' AND c.b = 'GERMANY') OR c.b = 'y') ORDER BY s.k")
    plan = nulls.plan_sql(sql)
    assert _filters_over(plan, "ors_s") == [None]
    (c_side,) = _filters_over(plan, "ors_c")
    assert _columns(plan, c_side) == {"b"} and "'y'" in str(c_side)
    assert nulls.execute(sql).rows == [(1,), (6,)]


def _other_templates():
    """The templates of the other configurations' cells."""
    others = set()
    for cell in harness.manifest()["workloads"]:
        others |= {t["name"] for t in load_mix(cell["traffic"])["templates"]}
    return sorted(others - set(TEMPLATES))


@pytest.mark.parametrize("name", _other_templates())
def test_the_other_configurations_statements_plan_as_before(runner, name, monkeypatch):
    """None of them has an OR across a join: the rule leaves each plan's text
    as it was without it."""
    module = importlib.import_module(f"benchmark.templates.{name}")
    sql = sql_of(module, validation(module))
    with_rule = runner.explain(sql)
    before = REGISTRY.counter(O.DERIVED_PREDICATES_COUNTER).value
    monkeypatch.setattr(O, "derive_join_disjuncts", lambda root: root)
    assert runner.explain(sql) == with_rule
    assert REGISTRY.counter(O.DERIVED_PREDICATES_COUNTER).value == before


def test_the_fifteen_queries_of_the_five_other_configurations_are_held():
    """Q1, Q6, Q14 (and the mesh cell's variants of Q1 and Q14), Q3, Q5, Q10,
    Q18, Q13, Q17, Q21, Q22, Q2, Q11, Q15, Q16."""
    queries = {f"q{n:02d}" for n in (1, 2, 3, 5, 6, 10, 11, 13, 14, 15, 16, 17, 18, 21, 22)}
    assert set(_other_templates()) == queries | {"q01v", "q14v"}


# --------------------------------------------------------- spans and counters


@pytest.mark.parametrize("name, derived", [("q07", 2), ("q19", 2), ("q04", 0), ("q08", 0), ("q12", 0)])
def test_the_counter_and_the_optimizer_span_count_what_was_derived(runner, name, derived):
    module = TEMPLATES[name]
    before = REGISTRY.counter(O.DERIVED_PREDICATES_COUNTER).value
    res = runner.execute(sql_of(module, validation(module)))
    assert REGISTRY.counter(O.DERIVED_PREDICATES_COUNTER).value - before == derived
    (optimizing,) = [s for s in TRACER.spans(res.trace_id) if s.name == "optimizer"]
    assert optimizing.attributes["derived_predicates"] == derived


def test_the_reader_on_a_statement_of_this_program(runner):
    res = runner.execute(sql_of(q07, validation(q07)))
    tree = [s.to_dict() for s in TRACER.spans(res.trace_id)]
    joins = [s["attributes"] for s in tree if s["name"] == "op:JoinNode"]
    assert len(joins) == 5       # six tables
    mean = join_rows_per_query.of([tree])
    assert mean == sum(a["probe_rows"] + a["build_rows"] for a in joins)
    assert join_rows_per_query.derived([tree]) == 2
    record = types.SimpleNamespace(start=0.0, statement=types.SimpleNamespace(template="q07"))
    assert join_rows_per_query.by_template([tree], [record]) == {"q07": mean}


def test_q7_feeds_its_joins_fewer_rows_with_the_rule(runner, without_the_rule):
    """Each nation scan keeps 2 of 25 nations, so fewer suppliers' and
    customers' rows reach the joins."""
    def join_rows():
        res = runner.execute(sql_of(q07, validation(q07)))
        return join_rows_per_query.of([[s.to_dict() for s in TRACER.spans(res.trace_id)]])

    with_rule = join_rows()
    without_the_rule()
    assert join_rows() > with_rule


# ---------------------------------------------------------------- the runner


def test_the_runner_refuses_a_program_that_cannot_sort_a_double_here(monkeypatch, capsys):
    """A program whose sort packs a double into 32-bit words fails every Q8
    on the chip (a bitcast the TPU's compiler refuses): the
    configuration's runner ends at once with its own code there, before any
    table is made."""
    from benchmark.runners import local_memory_wide as wide

    config = json.loads((harness.ROOT / "configs" / "tpch_widejoin_1chip.json").read_text())
    assert config["runner"] == "local_memory_wide"

    def parent(self, sql, *args, **kwargs):
        raise RuntimeError("UNIMPLEMENTED: While rewriting computation to not contain X64 element types")

    monkeypatch.setattr(LocalQueryRunner, "execute", parent)
    with pytest.raises(SystemExit) as refused:
        wide.start({**config, "scale_factor": SCALE})
    assert refused.value.code == wide.REFUSED == 4
    assert "not run" in capsys.readouterr().out


def test_the_runner_serves_this_program_with_no_table_made():
    from benchmark.runners import local_memory, local_memory_wide

    assert local_memory_wide.load is local_memory.load
    served = local_memory_wide.start({"name": "tpch_widejoin_1chip", "scale_factor": SCALE})
    assert "memory" in served.catalogs.names()
    assert served.execute("SHOW TABLES FROM memory.default").rows == []
