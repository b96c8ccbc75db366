"""Edge cases of the join and the grouped sum on the served path.

Each case runs SQL through ``LocalQueryRunner`` with default session
properties and compares the rows with ones computed in Python from the same
inputs: the ``UNNEST(sequence(...))`` rows the statement builds, or the TPC-H
tables as ``tests/oracle.tpch_df`` decodes them.
"""

import re
from decimal import Decimal

import pytest

from tests.oracle import assert_rows_equal, tpch_df

SCALE = 0.0005


@pytest.fixture(scope="module")
def runner():
    from trino_tpu.runtime import LocalQueryRunner

    return LocalQueryRunner.tpch(scale=SCALE)


def _null_every(t, k):
    return None if t % k == 0 else t


# --------------------------------------------------------------------- joins

_NULL_KEYS = """
    SELECT a.x, b.y FROM
      (SELECT IF(t % 3 = 0, CAST(NULL AS BIGINT), t) AS x
       FROM UNNEST(sequence(1, 200)) AS u(t)) a
    {kind} JOIN
      (SELECT IF(t % 5 = 0, CAST(NULL AS BIGINT), t) AS k, t AS y
       FROM UNNEST(sequence(1, 300)) AS v(t)) b
    ON a.x = b.k"""


def _null_keys(left):
    rows = []
    for t in range(1, 201):
        x = _null_every(t, 3)
        if x is not None and x % 5 != 0:
            rows.append((x, x))
        elif left:
            rows.append((x, None))
    return rows


def _lineitem_orders(lkey, okey):
    li = tpch_df("lineitem", SCALE)
    od = tpch_df("orders", SCALE)
    li = li[li.l_orderkey < 40]
    od = od[od.o_orderkey < 40]
    m = li.merge(od, left_on=lkey, right_on=okey)
    return list(zip(m.l_orderkey, m.l_linenumber, m.o_orderkey))


def _customer_nation():
    cu = tpch_df("customer", SCALE)
    na = tpch_df("nation", SCALE)
    m = cu.merge(na, left_on="c_mktsegment", right_on="n_name")
    return list(zip(m.c_name, m.n_name))


def _q13_counts():
    cu = tpch_df("customer", SCALE)
    od = tpch_df("orders", SCALE)
    keep = ~od.o_comment.map(lambda s: re.search("special.*requests", s, re.S) is not None)
    per = od[keep].groupby("o_custkey").size()
    return [(int(c), int(per.get(c, 0))) for c in cu.c_custkey]


def _decimal_by_segment():
    cu = tpch_df("customer", SCALE)
    od = tpch_df("orders", SCALE)
    m = od.merge(cu, left_on="o_custkey", right_on="c_custkey")
    cents = {}
    for seg, price in zip(m.c_mktsegment, m.o_totalprice):
        cents[seg] = cents.get(seg, 0) + int(round(price * 100))
    return [(seg, Decimal(c).scaleb(-2)) for seg, c in cents.items()]


_CAPACITY = """
    SELECT count(*), count(b.u), sum(a.x), sum(b.u) FROM
      (SELECT t AS x FROM UNNEST(sequence(1, {n})) AS p(t)) a
    LEFT JOIN
      (SELECT t * 2 AS k, t AS u FROM UNNEST(sequence(1, {n})) AS q(t)) b
    ON a.x = b.k"""


def _capacity(n):
    # build keys are the even numbers up to 2n: probe row x matches x / 2
    halves = [x // 2 for x in range(1, n + 1) if x % 2 == 0]
    return [(n, len(halves), n * (n + 1) // 2, sum(halves))]


JOIN_CASES = [
    pytest.param(_NULL_KEYS.format(kind=""), lambda: _null_keys(False), id="null_keys_inner"),
    pytest.param(_NULL_KEYS.format(kind="LEFT"), lambda: _null_keys(True), id="null_keys_left"),
    pytest.param(
        "SELECT c_name, n_name FROM customer JOIN nation ON c_mktsegment = n_name",
        _customer_nation, id="varchar_mktsegment_nation_no_rows",
    ),
    pytest.param(
        """SELECT l_orderkey, l_linenumber, o_orderkey
           FROM lineitem JOIN orders ON l_returnflag = o_orderstatus
           WHERE l_orderkey < 40 AND o_orderkey < 40""",
        lambda: _lineitem_orders("l_returnflag", "o_orderstatus"), id="varchar_lut_miss",
    ),
    pytest.param(
        """SELECT l_orderkey, l_linenumber, o_orderkey
           FROM lineitem JOIN orders ON l_linestatus = o_orderstatus
           WHERE l_orderkey < 40 AND o_orderkey < 40""",
        lambda: _lineitem_orders("l_linestatus", "o_orderstatus"), id="varchar_lut_hit",
    ),
    pytest.param(
        """SELECT a.x, b.y FROM (SELECT t AS x FROM UNNEST(sequence(1, 50)) AS u(t)) a
           JOIN (SELECT t AS y FROM UNNEST(sequence(1, 50)) AS v(t) WHERE t > 100) b
           ON a.x = b.y""",
        lambda: [], id="empty_build_inner",
    ),
    pytest.param(
        """SELECT a.x, b.y FROM (SELECT t AS x FROM UNNEST(sequence(1, 50)) AS u(t)) a
           LEFT JOIN (SELECT t AS y FROM UNNEST(sequence(1, 50)) AS v(t) WHERE t > 100) b
           ON a.x = b.y""",
        lambda: [(x, None) for x in range(1, 51)], id="empty_build_left",
    ),
    pytest.param(
        """SELECT a.x, b.y FROM (SELECT t AS x FROM UNNEST(sequence(1, 50)) AS u(t) WHERE t > 100) a
           LEFT JOIN (SELECT t AS y FROM UNNEST(sequence(1, 50)) AS v(t)) b
           ON a.x = b.y""",
        lambda: [], id="empty_probe_left",
    ),
    pytest.param(
        """SELECT a.x, b.y FROM (SELECT t AS x FROM UNNEST(sequence(1, 40)) AS u(t)) a
           RIGHT JOIN (SELECT t AS y FROM UNNEST(sequence(20, 70)) AS v(t)) b
           ON a.x = b.y""",
        lambda: [(y if y <= 40 else None, y) for y in range(20, 71)], id="right_join",
    ),
    pytest.param(
        """SELECT c_custkey, count(o_orderkey)
           FROM customer LEFT JOIN orders
             ON c_custkey = o_custkey AND o_comment NOT LIKE '%special%requests%'
           GROUP BY c_custkey""",
        _q13_counts, id="q13_left_join_count",
    ),
    pytest.param(
        """SELECT a.t, count(*), sum(b.u) FROM
             (SELECT t, t % 3 AS k FROM UNNEST(sequence(1, 12)) AS p(t)) a
           JOIN (SELECT u, u % 3 AS k FROM UNNEST(sequence(1, 600)) AS q(u)) b
           ON a.k = b.k GROUP BY a.t""",
        lambda: [
            (t, 200, sum(u for u in range(1, 601) if u % 3 == t % 3)) for t in range(1, 13)
        ],
        id="duplicate_build_keys",
    ),
    *[
        pytest.param(_CAPACITY.format(n=n), lambda n=n: _capacity(n), id=f"capacity_{n}")
        for n in (1023, 1024, 1025, 4096, 4097)
    ],
    pytest.param(
        """SELECT c_mktsegment, sum(p)
           FROM (SELECT o_custkey, CAST(o_totalprice AS decimal(38, 2)) AS p FROM orders) o
           JOIN customer ON o_custkey = c_custkey
           GROUP BY c_mktsegment""",
        _decimal_by_segment, id="decimal38_sum_over_join",
    ),
]


@pytest.mark.parametrize("sql, expected", JOIN_CASES)
def test_join_edge(runner, sql, expected):
    assert_rows_equal(runner.execute(sql).rows, expected(), ordered=False)


# --------------------------------------------------------------- grouped sums

# values near 2**62 of both signs: the positive ones of a group alone sum past
# the int64 range while the group's total stays inside it, so the answer is
# exact in whatever order the reduction adds them only if it wraps as int64
# does (float64 or a narrower type would lose the low digits)
_BIG = 4_000_000_000_000_000_000


def _extreme(key):
    sums = {}
    for t in range(400):
        v = (1 if (t // 4) % 2 == 0 else -1) * (_BIG + t)
        sums[key(t)] = sums.get(key(t), 0) + v
    return list(sums.items())


_EXTREME = """
    SELECT {key}, sum(IF((t / 4) % 2 = 0, 1, -1) * ({big} + t))
    FROM UNNEST(sequence(0, 399)) AS u(t) GROUP BY 1"""


def _lineitem_groups(where, keys, value):
    li = tpch_df("lineitem", SCALE)
    li = li[where(li)]
    g = li.groupby(keys(li))
    return [
        (*(k if isinstance(k, tuple) else (k,)), int(s), int(c))
        for (k, s), c in zip(g[value].sum().items(), g.size())
    ]


def _by(key, value, count_of=None, lo=1, hi=1000):
    out = {}
    for t in range(lo, hi + 1):
        s, c = out.get(key(t), (0, 0))
        v = value(t)
        counted = count_of(t) if count_of else t
        out[key(t)] = (s + v, c + (counted is not None))
    return [(k, s, c) for k, (s, c) in out.items()]


GROUP_CASES = [
    # a boolean key: the direct-indexed path
    pytest.param(
        _EXTREME.format(key="t % 2 = 0", big=_BIG), lambda: _extreme(lambda t: t % 2 == 0),
        id="extreme_magnitudes_direct",
    ),
    # a bigint key: the sort-based path
    pytest.param(
        _EXTREME.format(key="t % 4", big=_BIG), lambda: _extreme(lambda t: t % 4),
        id="extreme_magnitudes_sorted",
    ),
    pytest.param(
        """SELECT k, sum(t), count(*)
           FROM (SELECT 'only' AS k, t FROM UNNEST(sequence(1, 1000)) AS u(t)) GROUP BY k""",
        lambda: [("only", 500500, 1000)], id="one_group_constant_key",
    ),
    pytest.param(
        """SELECT t > 0, sum(t), count(*) FROM UNNEST(sequence(1, 1000)) AS u(t) GROUP BY 1""",
        lambda: [(True, 500500, 1000)], id="one_group_of_two_slots",
    ),
    pytest.param(
        """SELECT l_returnflag, l_linestatus, sum(l_linenumber), count(*)
           FROM lineitem WHERE l_returnflag <> 'N' GROUP BY l_returnflag, l_linestatus""",
        lambda: _lineitem_groups(
            lambda li: li.l_returnflag != "N",
            lambda li: [li.l_returnflag, li.l_linestatus], "l_linenumber",
        ),
        id="groups_emptied_by_filter_direct",
    ),
    pytest.param(
        """SELECT l_suppkey % 7, sum(l_linenumber), count(*)
           FROM lineitem WHERE l_suppkey % 7 <> 3 GROUP BY 1""",
        lambda: _lineitem_groups(
            lambda li: li.l_suppkey % 7 != 3, lambda li: li.l_suppkey % 7, "l_linenumber",
        ),
        id="groups_emptied_by_filter_sorted",
    ),
    pytest.param(
        """SELECT t % 2 = 0, sum(-t * 1000003), count(*)
           FROM UNNEST(sequence(1, 1000)) AS u(t) GROUP BY 1""",
        lambda: _by(lambda t: t % 2 == 0, lambda t: -t * 1000003), id="negative_values_direct",
    ),
    pytest.param(
        """SELECT t % 5, sum(t - 700), count(*)
           FROM UNNEST(sequence(1, 1000)) AS u(t) GROUP BY 1""",
        lambda: _by(lambda t: t % 5, lambda t: t - 700), id="negative_values_sorted",
    ),
    pytest.param(
        """SELECT t % 2 = 0, sum(t), count(IF(t % 3 = 0, CAST(NULL AS BIGINT), t))
           FROM UNNEST(sequence(1, 1000)) AS u(t) GROUP BY 1""",
        lambda: _by(lambda t: t % 2 == 0, lambda t: t, lambda t: _null_every(t, 3)),
        id="grouped_count_direct",
    ),
    pytest.param(
        """SELECT t % 5, sum(t), count(IF(t % 3 = 0, CAST(NULL AS BIGINT), t))
           FROM UNNEST(sequence(1, 1000)) AS u(t) GROUP BY 1""",
        lambda: _by(lambda t: t % 5, lambda t: t, lambda t: _null_every(t, 3)),
        id="grouped_count_sorted",
    ),
]


@pytest.mark.parametrize("sql, expected", GROUP_CASES)
def test_grouped_sum_edge(runner, sql, expected):
    assert_rows_equal(runner.execute(sql).rows, expected(), ordered=False)
