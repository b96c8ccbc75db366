"""The sub-query deployment (`tpch_subqueries_1chip`: TPC-H Q13, Q17, Q21, Q22) on
the CPU at SF0.01: the statements in the specification's text against the
templates' plain reference through the served client, what each reference
rests on (Q13's customers without an order, Q17's rounding of avg, Q21's sets
of suppliers, Q22's anti-join), the float32 control, the operators' new span
attributes and counters, the reader `outer_join_pct`, and the kernels this
deployment changed (a LEFT join matched on narrowed keys, grouped min and max
over sorted segments) against numpy."""

import json
import random
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import control, population, traffic
from benchmark import reference as ref
from benchmark.layer_metrics import _operators as readers
from benchmark.layer_metrics import outer_join_pct
from benchmark.templates import q13, q17, q21, q22
from benchmark.traffic import Traffic, draw_params, load_mix
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.ops import kernels as K
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime import executor as E
from trino_tpu.runtime.metrics import REGISTRY
from trino_tpu.runtime.tracing import TRACER

SCALE = 0.01
TEMPLATES = {"q13": q13, "q17": q17, "q21": q21, "q22": q22}
TABLES = ("lineitem", "orders", "customer", "supplier", "nation", "part")
SEEDS = [1, 2**31 + 7, 4_000_000_000]


@pytest.fixture(scope="module")
def runner():
    r = LocalQueryRunner.tpch(scale=SCALE)
    r.memory = MemoryConnector()
    r.register_catalog("memory", r.memory)
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
def client(runner):
    """The served path, as the benchmark drives it: decimals arrive as exact strings."""
    from trino_tpu.client import StatementClient
    from trino_tpu.server import CoordinatorServer

    server = CoordinatorServer(runner).start()
    yield StatementClient(f"http://{server.address}", timeout=600.0)
    server.stop()


def run(runner, module, params, schema="memory.default"):
    return runner.execute(module.SQL.format(schema=schema, **module.literals(params)))


# --------------------------------------------- the system against the reference


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_statement_equals_the_plain_reference(client, host, name, seed):
    """Every statement `subquery_stream` draws for the seed, as the traffic generator draws it."""
    module = TEMPLATES[name]
    traffic = Traffic(load_mix("subquery_stream"), seed, "memory.default")
    mine = [s for s in traffic.statements if s.template == name]
    assert len(mine) == 2 and mine[0].params != mine[1].params
    assert [s.params for s in mine] == draw_params(module.DOMAIN, random.Random(f"{seed}:params:{name}"), 2)
    for statement in mine:
        want = module.expect(host, statement.params, ref.EXACT)
        comparison = ref.Comparison()
        got = client.execute(statement.sql).rows
        assert comparison.rows(statement.label, got, want, ref.as_client(want)), comparison.report()
        assert comparison.correct and comparison.values["double_rel_gap"] < 1e-12
        if hasattr(module, "ties"):
            assert not module.ties(host, statement.params)


def test_the_statements_are_the_specifications_text():
    assert "LEFT OUTER JOIN" in q13.SQL and "AS c_orders (c_custkey, c_count)" in q13.SQL
    assert "NOT LIKE '%{word1}%{word2}%'" in q13.SQL
    assert "p_container = '{container}'" in q17.SQL and "0.2 * avg(l_quantity)" in q17.SQL
    assert "NOT EXISTS" in q21.SQL and q21.SQL.rstrip().endswith("LIMIT 100")
    assert q22.SQL.count("substring(c_phone from 1 for 2)") == 3 and "NOT EXISTS" in q22.SQL
    sizes = {name: int(np.prod([len(v) for v in m.DOMAIN.values()])) for name, m in TEMPLATES.items()}
    assert sizes == {"q13": 16, "q17": 1000, "q21": 25, "q22": 24}
    codes = q22.DOMAIN["codes"]
    assert codes[0] == [13, 31, 23, 29, 30, 18, 17]  # the validation tuple, cl. 2.4.22.4
    assert all(len(set(c)) == 7 and 10 <= min(c) and max(c) <= 34 for c in codes)
    assert len({tuple(c) for c in codes}) == len(codes)


def test_q13_counts_the_customers_without_an_order(client, host):
    """A third of the customers place no order (cl. 4.2.3): the LEFT join pads
    them with NULLs, count(o_orderkey) skips the NULLs, and they are the row
    `c_count` 0."""
    params = {"word1": "special", "word2": "requests"}
    rows = run(client, q13, params).rows
    assert rows == q13.expect(host, params, ref.EXACT)
    zero = [r for r in rows if r[0] == 0]
    customers = len(host["customer"]["c_custkey"])
    assert zero == [[0, customers - len(np.unique(host["orders"]["o_custkey"]))]]
    assert zero[0][1] >= customers // 3 and sum(r[1] for r in rows) == customers
    assert q13.like("a special b requests", "special", "requests")
    assert not q13.like("requests special", "special", "requests")
    assert not q13.like("specialrequest", "special", "requests")


def test_q17s_average_is_rounded_half_up_to_the_cent(runner):
    """avg of a decimal(12,2) is a decimal(12,2). Part 1's 201 lines hold 1,006
    units: 5.004975..., which rounds to 5.00, so `l_quantity < 0.2 * avg` is
    1.000 < 1.000 for the line of one unit and does not keep it; a reference
    that kept the average exact (1.000 < 1.000995) would. Part 2's average is
    5.995: the half rounds up to 6.00 and the line of 1.19 units is kept."""
    lines = [(1, 1.00, 10.00)] + [(1, 5.00, 50.00)] * 195 + [(1, 6.00, 60.00)] * 5
    lines += [(2, 1.19, 70.00), (2, 10.80, 80.00)]
    values = ", ".join(
        f"(CAST({k} AS bigint), CAST({q} AS decimal(12,2)), CAST({p} AS decimal(12,2)))" for k, q, p in lines)
    runner.execute(f"CREATE TABLE memory.q17.lineitem AS SELECT * FROM (VALUES {values}) "
                   "AS t (l_partkey, l_quantity, l_extendedprice)")
    runner.execute("CREATE TABLE memory.q17.part AS SELECT * FROM (VALUES "
                   "(CAST(1 AS bigint), 'Brand#23', 'MED BOX'), (CAST(2 AS bigint), 'Brand#23', 'MED BOX')) "
                   "AS t (p_partkey, p_brand, p_container)")
    params = {"m": 2, "n": 3, "syllable1": "MED", "syllable2": "BOX"}
    got = run(runner, q17, params, schema="memory.q17").rows
    assert [list(r) for r in got] == [[70.0 / 7.0]]  # part 2's small line alone
    # the reference on the same lines
    quantity = np.array([round(q * 100) for _, q, _ in lines])
    part = np.array([k - 1 for k, _, _ in lines])
    small = q17.small_lines(quantity, part, 2, ref.EXACT)
    assert small.tolist() == [False] * 201 + [True, False]
    exact_average = np.array([quantity[part == g].mean() for g in (0, 1)])
    assert (quantity * 10 < 2 * exact_average[part]).tolist() == [True] + [False] * 200 + [True, False]
    assert ref.dec_avg(100600, 201) == 500 and ref.dec_avg(1199, 2) == 600


def test_q21s_sets_of_suppliers_agree_with_a_double_loop(host):
    """The reference decides EXISTS / NOT EXISTS from the sizes of an order's
    sets of suppliers; the statement's own words are two loops over the
    order's lines."""
    li = host["lineitem"]
    late = li["l_receiptdate"] > li["l_commitdate"]
    by_order: dict = {}
    for i, key in enumerate(li["l_orderkey"].tolist()):
        by_order.setdefault(key, []).append(i)
    waiting = np.zeros(len(late), dtype=bool)
    for rows in by_order.values():
        for i in rows:
            others = [j for j in rows if li["l_suppkey"][j] != li["l_suppkey"][i]]
            waiting[i] = late[i] and bool(others) and not any(late[j] for j in others)
    assert np.array_equal(q21.waiting_lines(li), waiting) and 0 < waiting.sum() < late.sum()
    # and one nation's answer from them, counted by hand
    supp, orders = host["supplier"], host["orders"]
    finished = dict(zip(orders["o_orderkey"].tolist(), (orders["o_orderstatus"] == 0).tolist()))
    nation = dict(zip(supp["s_suppkey"].tolist(), supp["s_nationkey"].tolist()))
    saudi_arabia = [name for name, _ in population.NATIONS].index("SAUDI ARABIA")  # n_nationkey
    counts: dict = {}
    for i in np.flatnonzero(waiting).tolist():
        s = int(li["l_suppkey"][i])
        if nation[s] == saudi_arabia and finished[int(li["l_orderkey"][i])]:
            counts[s] = counts.get(s, 0) + 1
    want = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:100]
    assert q21.expect(host, {"nation": "SAUDI ARABIA"}, ref.EXACT) == [[f"Supplier#{s:09d}", n] for s, n in want]
    assert want


def test_q22_with_no_orders_keeps_every_customer_over_the_average(runner, client, host):
    """NOT EXISTS over an empty `orders` holds for every customer."""
    runner.execute(f"CREATE TABLE memory.q22.customer AS SELECT * FROM tpch.{runner.session.schema}.customer")
    runner.execute(f"CREATE TABLE memory.q22.orders AS SELECT * FROM tpch.{runner.session.schema}.orders WHERE false")
    params = {"codes": q22.DOMAIN["codes"][0]}
    empty = {**host, "orders": {"o_custkey": np.zeros(0, dtype=np.int64)}}
    want = q22.expect(empty, params, ref.EXACT)
    assert run(client, q22, params, schema="memory.q22").rows == ref.as_client(want)
    cust = host["customer"]
    code = 10 + (cust["c_custkey"] - 1) % 25
    listed = np.isin(code, params["codes"])
    positive = cust["c_acctbal"][listed & (cust["c_acctbal"] > 0)]
    average = ref.dec_avg(int(positive.sum()), len(positive))
    assert sum(r[1] for r in want) == int((listed & (cust["c_acctbal"] > average)).sum())
    with_orders = q22.expect(host, params, ref.EXACT)
    assert sum(r[1] for r in with_orders) < sum(r[1] for r in want)  # the anti-join drops two thirds


def test_the_float32_control_is_not_correct(capsys):
    """At SF0.1, not the other tests' SF0.01: there a country code's ten
    balances add up to less than 2**24 cents and float32 carries them."""
    assert control.main(["--workload", "resident_subquery_stream", "--seeds", "5", "6", "7",
                         "--scale", "0.1"]) == 0
    for text in capsys.readouterr().out.strip().splitlines():
        seen = json.loads(text)
        assert seen["correct"] is False
        assert seen["compared"]["exact_cells_wrong"]["value"] >= 7   # Q22's sums of c_acctbal
        assert seen["compared"]["double_rel_gap"]["value"] > 3 * ref.DOUBLE_REL_LIMIT   # Q17's sum


def test_the_runner_refuses_a_program_whose_grouped_extremes_are_scatters(monkeypatch, capsys):
    """The parent of PR 36 fits under two cycles of the cell into a window (Q21
    11.2 s): the configuration's runner ends at once with its own code there."""
    from benchmark.runners import local_memory_subqueries as runner

    config = json.loads((traffic.ROOT / "configs" / "tpch_subqueries_1chip.json").read_text())
    assert config["runner"] == "local_memory_subqueries"
    monkeypatch.delattr(K, "segment_running")
    with pytest.raises(SystemExit) as refused:
        runner.start({**config, "scale_factor": SCALE})
    assert refused.value.code == runner.REFUSED == 4
    assert "not run" in capsys.readouterr().out


def test_the_runner_is_local_memory_on_this_program():
    from benchmark.runners import local_memory, local_memory_subqueries

    assert local_memory_subqueries.load is local_memory.load
    served = local_memory_subqueries.start({"name": "tpch_subqueries_1chip", "scale_factor": SCALE})
    assert "memory" in served.catalogs.names() and served.execute("SELECT 1").rows == [(1,)]


# ------------------------------------------------- the kernels against numpy


@pytest.mark.parametrize("kind", ["min", "max"])
@pytest.mark.parametrize("dtype", ["int64", "float64", "bool"])
def test_grouped_extremes_over_sorted_segments_are_numpys_reduceat(kind, dtype):
    """`K.segment_reduce` for min and max over group-sorted rows, read off the
    segments' bounds: one long group, groups of one row, a group whose every
    row is NULL (it reads the identity, and its count says so), and slots
    past the groups."""
    rng = np.random.default_rng(36)
    n, capacity = 6000, 2048
    sizes = rng.integers(1, 8, size=1500)
    sizes[7] = 2500                                      # one group far longer than a row of a blocked scan
    sizes = sizes[np.cumsum(sizes) <= n - 100]           # the last 100 rows and more are inactive
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    groups, live = len(sizes), int(sizes.sum())
    new_group = np.zeros(n, dtype=bool)
    new_group[starts] = True
    if dtype == "bool":
        values = rng.random(n) < 0.5
    elif dtype == "float64":
        values = rng.normal(size=n)
    else:
        values = rng.integers(-(2**62), 2**62, size=n)
    weight = (rng.random(n) < 0.8) & (np.arange(n) < live)
    weight[starts[3]:starts[4]] = False                  # every value of group 3 is NULL
    padded = np.concatenate([starts, np.full(capacity - groups, n)])
    ends = np.concatenate([padded[1:], [n]]) - 1
    got = jax.jit(K.segment_reduce, static_argnums=(3, 4))(
        jnp.asarray(values), jnp.asarray(weight), None, capacity, kind, jnp.asarray(new_group),
        (jnp.asarray(padded, dtype=jnp.int32), jnp.asarray(ends, dtype=jnp.int32)),
    )
    identity = np.asarray(K._reduce_identity(jnp.asarray(values).dtype, kind))
    masked = np.where(weight, values, identity)
    reduceat = (np.minimum if kind == "min" else np.maximum).reduceat(masked, starts)
    assert got.shape == (capacity,) and got.dtype == values.dtype
    assert np.array_equal(np.asarray(got)[:groups], reduceat)
    assert np.asarray(got)[3] == identity and np.count_nonzero(weight[starts[3]:starts[4]]) == 0


def test_reductions_read_at_the_segments_ends_are_numpys_reduceat():
    """`K.segment_reduce_at_ends`: a count, an exact sum, a minimum and two
    maxima of one round in ONE gather, over a presorted layout (rows of no
    weight before the first group, between groups and after the last), with a
    group of no participant and slots past the groups."""
    rng = np.random.default_rng(3600)
    n, capacity = 5000, 1024
    sizes = rng.integers(1, 9, size=700)
    sizes[11] = 2300
    sizes = sizes[np.cumsum(sizes) <= n - 200]
    starts = 40 + np.concatenate([[0], np.cumsum(sizes)[:-1]])    # 40 inactive rows lead the page
    groups = len(sizes)
    active = (np.arange(n) >= 40) & (np.arange(n) < 40 + sizes.sum()) & (rng.random(n) < 0.9)
    active[starts] = True                                         # a group starts at a live row
    new_group = np.zeros(n, dtype=bool)
    new_group[starts] = True
    weight = active & (rng.random(n) < 0.8)
    weight[starts[5]:starts[6]] = False
    ints = rng.integers(-(2**40), 2**40, size=n)
    floats = rng.normal(size=n)
    flags = rng.random(n) < 0.5
    padded = np.concatenate([starts, np.full(capacity - groups, n)])
    ends = jnp.asarray(np.concatenate([padded[1:], [n]]) - 1, dtype=jnp.int32)
    asked = [(jnp.asarray(weight.astype(np.int64)), jnp.asarray(weight), "count"),
             (jnp.asarray(ints), jnp.asarray(weight), "sum"),
             (jnp.asarray(ints), jnp.asarray(weight), "min"),
             (jnp.asarray(floats), jnp.asarray(weight), "max"),
             (jnp.asarray(flags), jnp.asarray(weight), "max")]
    assert all(K.reads_at_ends(v, kind) for v, _, kind in asked)
    assert not K.reads_at_ends(jnp.asarray(floats), "sum") and not K.reads_at_ends(jnp.zeros((4, 2)), "min")
    calls = []
    gather_rows = K.gather_rows
    try:
        K.gather_rows = lambda arrays, idx: calls.append(len(list(arrays))) or gather_rows(arrays, idx)
        got = [np.asarray(g) for g in K.segment_reduce_at_ends(asked, jnp.asarray(new_group), ends)]
    finally:
        K.gather_rows = gather_rows
    assert calls == [5]
    at = np.append(starts, n)
    by_hand = lambda values, op, identity: np.array(   # noqa: E731
        [op(np.where(weight, values, identity)[a:b]) for a, b in zip(at[:-1], at[1:])])
    assert got[0].dtype == np.int64 and np.array_equal(got[0][:groups], by_hand(weight.astype(np.int64), np.sum, 0))
    assert got[1].dtype == np.int64 and np.array_equal(got[1][:groups], by_hand(ints, np.sum, 0))
    assert np.array_equal(got[2][:groups], by_hand(ints, np.min, np.iinfo(np.int64).max))
    assert np.array_equal(got[3][:groups], by_hand(floats, np.max, -np.inf))
    assert got[4].dtype == np.bool_ and np.array_equal(got[4][:groups], by_hand(flags, np.max, False))
    assert got[0][5] == 0 and got[1][5] == 0 and got[2][5] == np.iinfo(np.int64).max
    assert not got[0][groups:].any() and not got[1][groups:].any()      # slots past the groups count nothing


def test_an_aggregations_reads_travel_together():
    """Q21's aggregation (min, max, count of one column by a bigint key) makes
    three gathers of its slots: the key's values and validity at the groups'
    first rows, then a round of the three participant counts and a round of the
    two extremes at the groups' last rows. Each aggregate, the key and each
    validity byte read by itself, it made nine of 5.2M slots a Q21 (PR 36)."""
    rows, capacity = 4096, 1024
    rng = np.random.default_rng(21)
    key = np.sort(rng.integers(0, 900, size=rows))
    value = rng.integers(1, 30_000, size=rows)
    valid = rng.random(rows) < 0.9
    page = E.Page(
        (E.Column(E.BIGINT, jnp.asarray(key), jnp.ones(rows, bool)), E.Column(E.BIGINT, jnp.asarray(value), jnp.asarray(valid))),
        jnp.ones(rows, bool))
    aggregations = tuple(
        (name, E.Aggregation(function=name, args=("v",), output_type=E.BIGINT)) for name in ("min", "max", "count"))
    new_group = np.concatenate([[True], key[1:] != key[:-1]])
    groups = int(new_group.sum())
    calls = []
    gather_rows = K.gather_rows
    try:
        K.gather_rows = lambda arrays, idx: calls.append(len(list(arrays))) or gather_rows(arrays, idx)
        out = E._aggregate_impl(("k",), aggregations, ("k", "v"), capacity, 0, page, jnp.asarray(new_group), jnp.int32(groups))
    finally:
        K.gather_rows = gather_rows
    assert calls == [2, 3, 2]
    got = {name: np.asarray(c.data)[:groups] for name, c in zip(("k", "min", "max", "count"), out.columns)}
    seen = np.unique(key)
    assert np.array_equal(got["k"], seen) and int(np.asarray(out.active).sum()) == groups
    assert np.array_equal(got["count"], [valid[key == k].sum() for k in seen])
    for k, low, high, ok in zip(seen, got["min"], got["max"], np.asarray(out.columns[1].valid)):
        mine = value[(key == k) & valid]
        assert (not ok and mine.size == 0) or (low == mine.min() and high == mine.max())


def test_the_running_extreme_starts_anew_at_every_flag():
    values = jnp.asarray([5, 3, 4, 9, 1, 7, 7, 2, 8])
    flags = jnp.asarray([True, False, False, True, False, True, False, False, False])
    assert K.segment_running(values, flags, "min").tolist() == [5, 3, 3, 9, 1, 7, 7, 2, 2]
    assert K.segment_running(values, flags, "max").tolist() == [5, 5, 5, 9, 9, 7, 7, 7, 8]
    # rows before the first flag are a segment nobody reads, and do not reach into the next
    late = jnp.asarray([False, False, True, False, False, False, False, False, False])
    assert K.segment_running(values, late, "min").tolist()[2:] == [4, 4, 1, 1, 1, 1, 1]


def test_grouped_min_and_max_make_no_scatter():
    """The program Q21's aggregations run (min, max, count by a bigint key): its
    lowered text holds no scatter; before PR 36 each extreme was one over the page."""
    rows, capacity = 4096, 1024
    page = E.Page(
        tuple(E.Column(E.BIGINT, jnp.zeros(rows, jnp.int64), jnp.ones(rows, bool)) for _ in range(2)),
        jnp.ones(rows, bool))
    aggregations = tuple(
        (name, E.Aggregation(function=name, args=("v",), output_type=E.BIGINT)) for name in ("min", "max", "count"))
    text = E._jit_aggregate.lower(
        ("k",), aggregations, ("k", "v"), capacity, 0, page, jnp.zeros(rows, bool), jnp.int32(1)).as_text()
    assert "scatter" not in text and text.count("stablehlo.while") == 2


# --------------------------------------------------------- spans and counters


def _counter(name, **labels):
    return REGISTRY.counter(name, labels).value


CASES = {   # params, {join kind: joins}, decorrelated by kind, the aggregations' functions, semi-joins (negated)
    "q13": ({"word1": "special", "word2": "requests"}, {"LEFT": 1}, {}, [["count"], ["count"]], []),
    "q17": ({"m": 2, "n": 3, "syllable1": "MED", "syllable2": "BOX"}, {"INNER": 2}, {"scalar": 1},
            [["sum"], ["avg"]], [False]),
    "q21": ({"nation": "SAUDI ARABIA"}, {"INNER": 3, "LEFT": 2}, {"exists": 2},
            [["count"], ["min", "max", "count"], ["min", "max", "count"]], []),
    "q22": ({"codes": [13, 31, 23, 29, 30, 18, 17]}, {"CROSS": 1}, {"exists": 1},
            [["count", "sum"], ["avg"]], [True]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_operator_spans_and_counters(runner, name):
    params, joins, decorrelated, functions, semis = CASES[name]
    kinds = ("INNER", "LEFT", "FULL", "CROSS")
    joins_before = {k: _counter(E.JOINS_COUNTER, kind=k) for k in kinds}
    rewritten_before = {k: _counter("trino_tpu_decorrelated_subqueries_total", kind=k)
                        for k in ("scalar", "exists", "in")}
    res = run(runner, TEMPLATES[name], params)
    spans = TRACER.spans(res.trace_id)
    join_spans = [s.attributes for s in spans if s.name == "op:JoinNode"]
    assert {k: sum(a["kind"] == k for a in join_spans) for k in kinds if k in joins} == joins
    assert len(join_spans) == sum(joins.values())
    for a in join_spans + [s.attributes for s in spans if s.name == "op:SemiJoinNode"]:
        # the match's merge holds each row once; the ranks come back in one word where two fit it
        assert a["merged_rows"] == a["probe_capacity"] + a["build_capacity"]
        assert a["rank_words"] == (1 if "negated" in a or a["build_capacity"] < 65536 else 2)
    for a in join_spans:
        narrowed = sum(b for b in (a["key_bits"] or []) if b)
        if a["kind"] == "CROSS":
            assert a["key_words"] == 1 and "unmatched_rows" not in a
        elif a["kind"] == "LEFT":
            # no dynamic filter reads an outer join's build side: a bigint key in its 64 bits
            assert a["key_bits"] is None and a["key_words"] == 2
            assert 0 <= a["unmatched_rows"] <= a["rows_out"]
        else:
            assert narrowed and a["key_words"] == -(-narrowed // 32) == 1 and "unmatched_rows" not in a
    if name == "q13":   # the customers no kept order matches: a third of them and more
        assert join_spans[0]["unmatched_rows"] >= 500
    for k in kinds:
        assert _counter(E.JOINS_COUNTER, kind=k) - joins_before[k] == joins.get(k, 0)
    (planning,) = [s.attributes for s in spans if s.name == "planner"]
    assert planning["decorrelated"] == sum(decorrelated.values())
    for k, before in rewritten_before.items():
        assert _counter("trino_tpu_decorrelated_subqueries_total", kind=k) - before == decorrelated.get(k, 0)
    aggregations = [s.attributes for s in spans if s.name == "op:AggregationNode"]
    assert [a["functions"] for a in aggregations] == functions
    semi_spans = [s.attributes for s in spans if s.name == "op:SemiJoinNode"]
    assert [a["negated"] for a in semi_spans] == semis
    # every value was on the host already: the reads are the ones the operators made before
    syncs = {s.name for s in spans if s.name.startswith("sync:")}
    assert syncs <= {"sync:compact", "sync:join_capacity", "sync:num_groups", "sync:dynamic_filter",
                     "sync:scan_pack", "sync:presorted_check"}
    # a grouping by the key its input is stored by (the catalog saw the order) sorts nothing
    presorted = {"q13": ["sort", "presorted"], "q21": ["direct", "presorted", "presorted"]}
    if name in presorted:
        assert [a["path"] for a in aggregations] == presorted[name]
    # an outer join's unmatched rows ride the read that sizes its output: one read a join, as before
    reads = [s.attributes["value"] for s in spans if s.name == "sync:join_capacity"]
    assert sorted(reads) == sorted(a["rows_out"] for a in join_spans)


def test_not_in_marks_its_semi_join_negated(runner):
    res = runner.execute(
        "SELECT count(*) FROM memory.default.customer WHERE c_custkey NOT IN "
        "(SELECT o_custkey FROM memory.default.orders)")
    before = _counter("trino_tpu_decorrelated_subqueries_total", kind="in")
    assert res.rows[0][0] == 500
    (semi,) = [s.attributes for s in TRACER.spans(res.trace_id) if s.name == "op:SemiJoinNode"]
    assert semi["negated"] is True
    res = runner.execute(
        "SELECT count(*) FROM memory.default.customer WHERE c_custkey IN "
        "(SELECT o_custkey FROM memory.default.orders WHERE o_orderkey < 100)")
    (semi,) = [s.attributes for s in TRACER.spans(res.trace_id) if s.name == "op:SemiJoinNode"]
    assert semi["negated"] is False
    assert _counter("trino_tpu_decorrelated_subqueries_total", kind="in") - before == 1


# ------------------------------------------------- the benchmark's new reader


def _span(name, span_id, parent, start, end, **attributes):
    return {"name": name, "spanId": span_id, "parentSpanId": parent, "startNs": start, "endNs": end,
            "attributes": attributes}


def test_outer_join_pct_reads_the_outer_and_semi_joins_own_time():
    tree = [
        _span("statement", 1, None, 0, 1000),
        _span("execution", 2, 1, 100, 900),
        _span("op:JoinNode", 3, 2, 100, 700, kind="LEFT"),
        _span("op:AggregationNode", 4, 3, 150, 350),        # an input: another operator's time
        _span("sync:compact", 5, 3, 400, 600),              # the join waiting: its own
        _span("op:JoinNode", 6, 3, 350, 400, kind="INNER"),  # an input, and not an outer join
        _span("op:SemiJoinNode", 7, 2, 700, 800, negated=True),
        _span("op:TableScanNode", 8, 7, 700, 720),
    ]
    # the LEFT join 600 - (200 + 50), the semi-join 100 - 20, of 1000
    assert outer_join_pct.of([tree]) == pytest.approx(100.0 * (350 + 80) / 1000)
    # a program whose join spans state no kind (the parent of PR 36): nothing, not 0
    for span in tree:
        span["attributes"].pop("kind", None)
    assert outer_join_pct.of([tree]) is None
    assert outer_join_pct.of([[_span("statement", 1, None, 0, 10)]]) is None
    assert outer_join_pct.read(types.SimpleNamespace(_statement_trees=None)) is None
    # inner joins only, and their kind stated: no outer join's time, which is 0
    inner = [_span("statement", 1, None, 0, 100), _span("op:JoinNode", 2, 1, 10, 50, kind="INNER")]
    assert outer_join_pct.of([inner]) == 0.0


def test_outer_join_pct_on_the_programs_own_spans(runner):
    res = run(runner, q21, {"nation": "SAUDI ARABIA"})
    tree = [s.to_dict() for s in TRACER.spans(res.trace_id)]
    share = outer_join_pct.of([tree])
    assert 0.0 < share < 100.0
    picked = [s for s in tree if outer_join_pct.picked(s)]
    assert len(picked) == 2 and all(s["attributes"]["kind"] == "LEFT" for s in picked)


def test_the_programs_the_readers_count_are_the_ones_these_statements_run(runner, monkeypatch):
    """The join and grouping programs of `_operators.py` by the executor's own
    names: the four statements run match, expand, semi-join, group sort, the
    presorted grouping and the three aggregates; no statement reaches `_jit_left_join_residual` (Q13's
    NOT LIKE is pushed below its join) or `_jit_full_join_tail`."""
    launched = set()
    for program in readers.JOIN_PROGRAMS + readers.GROUP_PROGRAMS:
        name = program[len("jit_"):]           # what the profiler calls jit_<name>
        function = getattr(E, name)
        assert getattr(function, "__wrapped__", function).__name__ == name
        # the jitted object the executor calls: `_jit_x` itself, or `_jit_x` of `_x_impl`
        jitted = name if name.startswith("_jit_") else "_jit" + name[:-len("_impl")]

        def spy(*args, _name=name, _real=getattr(E, jitted), **kwargs):
            launched.add(_name)
            return _real(*args, **kwargs)

        monkeypatch.setattr(E, jitted, spy)
    for name, (params, *_) in CASES.items():
        run(runner, TEMPLATES[name], params)
    assert launched == {"_jit_join_match", "_jit_join_expand", "_jit_semijoin", "_group_sort_impl",
                        "_presorted_group_impl", "_aggregate_impl", "_direct_aggregate_impl", "_sort_impl"}


# ------------------------------ what the plans needed (PR 36, the chip's findings)

SF3 = {   # what the memory catalog holds after the load at SF3: rows, integer ranges, dictionary sizes
    "lineitem": (17993932, {"l_orderkey": (1, 18000000), "l_suppkey": (1, 30000), "l_partkey": (1, 600000)},
                 {"l_returnflag": 3, "l_linestatus": 2}),
    "orders": (4500000, {"o_orderkey": (1, 18000000), "o_custkey": (1, 449999)}, {"o_orderstatus": 3}),
    "supplier": (30000, {"s_suppkey": (1, 30000), "s_nationkey": (0, 24)}, {"s_name": 30000}),
    "part": (600000, {"p_partkey": (1, 600000), "p_size": (1, 50)}, {"p_brand": 25, "p_container": 40}),
    "nation": (25, {"n_nationkey": (0, 24), "n_regionkey": (0, 4)}, {"n_name": 25}),
}


def _plan_at_sf3(module, params, codes=True):
    return _plans_at_sf3(module, params, 1, codes)[0]


def _plans_at_sf3(module, params, times, codes=True):
    from trino_tpu.planner.logical_planner import LogicalPlanner
    from trino_tpu.planner.optimizer import optimize
    from trino_tpu.spi.connector import SchemaTableName
    from trino_tpu.sql.parser import parse_statement

    r = LocalQueryRunner.tpch(scale=0.001)
    memory = MemoryConnector()
    r.register_catalog("memory", memory)
    for table, (rows, spans, strings) in SF3.items():
        r.execute(f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.{r.session.schema}.{table} WHERE false")
        stored = memory.table(SchemaTableName("default", table))
        stored.rows, stored.spans, stored.codes = rows, dict(spans), dict(strings) if codes else {}
    sql = module.SQL.format(schema="memory.default", **module.literals(params))
    return [
        optimize(LogicalPlanner(r.metadata, r.session).plan(parse_statement(sql)), r.metadata, r.session).root
        for _ in range(times)
    ]


def _nodes(root, kind):
    found = [root] if isinstance(root, kind) else []
    for source in root.sources:
        found += _nodes(source, kind)
    return found


def test_q17_groups_the_lines_of_the_chosen_parts_at_sf3_statistics():
    """The decorrelated avg(l_quantity) by l_partkey is joined back on its key
    to 600 parts of 600,000: its input is semi-joined with those parts first
    (`reduce_aggregation_by_join_keys`). On the chip the aggregation of all
    18.9M lines took 0.73 s of the statement's 1.19 s (PR 36)."""
    from trino_tpu.planner import plan as P

    root = _plan_at_sf3(q17, {"m": 2, "n": 3, "syllable1": "MED", "syllable2": "BOX"})
    (grouped,) = [a for a in _nodes(root, P.AggregationNode) if a.group_keys]
    assert isinstance(grouped.source, P.FilterNode) and isinstance(grouped.source.source, P.SemiJoinNode)
    semi = grouped.source.source
    assert isinstance(semi.source, P.TableScanNode) and semi.source.table.schema_table.table == "lineitem"
    assert semi.source_key == grouped.group_keys[0] and not semi.negated and not semi.null_aware
    (parts,) = _nodes(semi.filtering_source, P.TableScanNode)
    assert parts.table.schema_table.table == "part"
    assert [c for c, _ in parts.constraint.domains] == ["p_brand", "p_container"]  # the copy keeps the filter
    # the copy's symbols are its own: a plan names a symbol in one place
    other_parts = [s for s in _nodes(root, P.TableScanNode)
                   if s.table.schema_table.table == "part" and s is not parts]
    assert len(other_parts) == 1 and not set(parts.output_symbols) & set(other_parts[0].output_symbols)
    # without the dictionaries' sizes the estimator takes brand and container to keep 0.81 of the
    # parts, more than one group in sixteen: the aggregation stays whole
    whole = _plan_at_sf3(q17, {"m": 2, "n": 3, "syllable1": "MED", "syllable2": "BOX"}, codes=False)
    assert not _nodes(whole, P.SemiJoinNode)


def test_q21_keeps_its_aggregations_whole_at_sf3_statistics():
    """Its LEFT joins read about 220,000 of 4.5M orders, but no scan chain of
    the probe side brings the keys in fewer rows than a sixteenth of the groups
    (the finished orders are 1.5M by the estimator): nothing is semi-joined."""
    from trino_tpu.planner import plan as P

    root = _plan_at_sf3(q21, {"nation": "SAUDI ARABIA"})
    assert not _nodes(root, P.SemiJoinNode)
    grouped = [a for a in _nodes(root, P.AggregationNode) if a.group_keys and "min" in
               [g.function for _, g in a.aggregations]]
    assert len(grouped) == 2
    assert sorted(j.kind.name for j in _nodes(root, P.JoinNode)) == ["INNER", "INNER", "INNER", "LEFT", "LEFT"]


def test_a_reduced_aggregation_answers_as_the_whole_one(runner):
    """The rule on the CPU's tables, INNER and LEFT: the same rows with and without it."""
    from trino_tpu.planner import rules

    inner = ("SELECT p_partkey, a FROM memory.default.part, (SELECT l_partkey, avg(l_quantity) AS a, min(l_suppkey) AS s "
             "FROM memory.default.lineitem GROUP BY l_partkey) WHERE p_partkey = l_partkey AND p_brand = 'Brand#23' "
             "AND p_container = 'MED BOX' ORDER BY 1")
    left = ("SELECT p_partkey, n FROM (SELECT p_partkey FROM memory.default.part WHERE p_brand = 'Brand#23' AND "
            "p_container = 'MED BOX') LEFT JOIN (SELECT l_partkey, count(*) AS n FROM memory.default.lineitem "
            "WHERE l_quantity > 49 GROUP BY l_partkey) ON p_partkey = l_partkey ORDER BY 1")
    for sql in (inner, left):
        res = runner.execute(sql)
        semis = [s for s in TRACER.spans(res.trace_id) if s.name == "op:SemiJoinNode"]
        assert len(semis) == 1 and res.rows
        share, rules.REDUCE_GROUPS_SHARE = rules.REDUCE_GROUPS_SHARE, 10**9   # never worth it
        try:
            whole = runner.execute(sql)
        finally:
            rules.REDUCE_GROUPS_SHARE = share
        assert not [s for s in TRACER.spans(whole.trace_id) if s.name == "op:SemiJoinNode"]
        assert whole.rows == res.rows
    assert any(n is None for _, n in runner.execute(left).rows)  # a part no such line names: padded, not dropped


# ------------------- grouping by the key the rows are stored by (PR 36, second repair)


@pytest.mark.parametrize("density", [1.0, 0.6, 0.02])
def test_last_active_prev_is_the_value_of_the_row_before_among_the_active(density):
    """At every active row: the value of the last active row before it, through
    runs of filtered rows of any length (one of 3,000 here) and a dense tail."""
    rng = np.random.default_rng(int(density * 100))
    n = 12000
    values = rng.integers(-(2**62), 2**62, size=n)
    active = rng.random(n) < density
    active[4000:7000] = False
    active[-500:] = False
    prev, has = jax.jit(K.last_active_prev)(jnp.asarray(values), jnp.asarray(active))
    rows = np.flatnonzero(active)
    assert not bool(has[rows[0]]) and bool(np.asarray(has)[rows[1:]].all())
    assert np.array_equal(np.asarray(prev)[rows[1:]], values[rows[:-1]])


def test_the_memory_catalog_sees_which_columns_rise(runner):
    """`lineitem` is written in l_orderkey's order: the catalog sees it in the
    read it makes of every page written, a scan states it, and an INSERT of a
    lower key ends it."""
    from trino_tpu.spi.connector import SchemaTableName

    lineitem = runner.memory.table(SchemaTableName("default", "lineitem"))
    assert lineitem.ordered["l_orderkey"] and not lineitem.ordered["l_partkey"]
    assert not lineitem.ordered["l_shipdate"]
    meta = runner.memory.metadata()
    assert meta.get_table_metadata(SchemaTableName("default", "lineitem")).sorted_by == ("l_orderkey",)
    assert meta.get_table_metadata(SchemaTableName("default", "customer")).sorted_by == ("c_custkey",)
    runner.execute("CREATE TABLE memory.rise.t AS SELECT * FROM (VALUES (1, 5), (2, 4), (2, 9)) AS v (a, b)")
    name = SchemaTableName("rise", "t")
    assert runner.memory.table(name).ordered == {"a": True, "b": False}
    runner.execute("INSERT INTO memory.rise.t VALUES (2, 1), (7, 2)")   # not under the rows before: still rises
    assert runner.memory.table(name).ordered["a"] and meta.get_table_metadata(name).sorted_by == ("a",)
    grouped = runner.execute("SELECT a, min(b), max(b), count(*) FROM memory.rise.t GROUP BY a ORDER BY a")
    assert [tuple(r) for r in grouped.rows] == [(1, 5, 5, 1), (2, 1, 9, 3), (7, 2, 2, 1)]
    (aggregation,) = [s.attributes for s in TRACER.spans(grouped.trace_id) if s.name == "op:AggregationNode"]
    assert aggregation["path"] == "presorted"
    runner.execute("INSERT INTO memory.rise.t VALUES (3, 3)")           # under the 7 before it
    assert not runner.memory.table(name).ordered["a"] and meta.get_table_metadata(name).sorted_by == ()
    again = runner.execute("SELECT a, min(b), max(b), count(*) FROM memory.rise.t GROUP BY a ORDER BY a")
    assert [tuple(r) for r in again.rows] == [(1, 5, 5, 1), (2, 1, 9, 3), (3, 3, 3, 1), (7, 2, 2, 1)]
    (aggregation,) = [s.attributes for s in TRACER.spans(again.trace_id) if s.name == "op:AggregationNode"]
    assert aggregation["path"] == "sort"
    runner.execute("DELETE FROM memory.rise.t WHERE a = 3")             # the pages are read again
    assert runner.memory.table(name).ordered["a"]


def test_many_groups_take_a_stored_pages_class_of_slots():
    """Up to 2**20 groups a power of two, above it a multiple of 2**20: 4.5M
    groups are gathered into 5,242,880 slots and not 8,388,608."""
    from trino_tpu.spi.page import capacity_class

    assert capacity_class(4_500_000) == 5_242_880 and capacity_class(4_126_184) == 4_194_304
    assert E._round_capacity(450_000, base=16) == 524_288 == capacity_class(450_000)


def test_the_same_statement_plans_the_same_every_time():
    """The estimator's memo is keyed by id(node) and lives through passes that
    drop nodes: it holds what it has seen, or a new node inherits a dropped
    one's id and its estimate. Before PR 36 kept them, Q17 planned otherwise in
    one planning of twenty at SF3's statistics, and a run of the benchmark
    compiled a plan of its own inside the window (a Q17 of 15.9 s)."""
    from trino_tpu.planner.plan import LogicalPlan, format_plan

    params = {"m": 2, "n": 3, "syllable1": "MED", "syllable2": "BOX"}
    plans = {format_plan(LogicalPlan(root, {})) for root in _plans_at_sf3(q17, params, 150)}
    assert len(plans) == 1
