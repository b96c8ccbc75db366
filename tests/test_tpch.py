"""Real TPC-H queries vs the pandas oracle (the BASELINE.json workload ladder:
Q6 scan+filter+sum, Q1 multi-key group-by, Q3/Q14 joins, Q13 left join,
Q18 having+in-subquery+joins, Q5 six-way join)."""

import datetime

import numpy as np
import pandas as pd
import pytest

from tests.oracle import tpch_df, assert_rows_equal

SCALE = 0.0005
EPOCH = datetime.date(1970, 1, 1)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


@pytest.fixture(scope="module")
def runner():
    from trino_tpu.runtime import LocalQueryRunner

    return LocalQueryRunner.tpch(scale=SCALE)


def test_q6(runner):
    res = runner.execute(
        """
        SELECT sum(l_extendedprice * l_discount) AS revenue
        FROM lineitem
        WHERE l_shipdate >= DATE '1994-01-01'
          AND l_shipdate < DATE '1994-01-01' + INTERVAL '1' YEAR
          AND l_discount BETWEEN 0.06 - 0.01 AND 0.06 + 0.01
          AND l_quantity < 24
        """
    )
    li = tpch_df("lineitem", SCALE)
    m = li[
        (li.l_shipdate >= days("1994-01-01"))
        & (li.l_shipdate < days("1995-01-01"))
        & (li.l_discount >= 0.05)
        & (li.l_discount <= 0.07)
        & (li.l_quantity < 24)
    ]
    expected = (m.l_extendedprice * m.l_discount).sum()
    assert_rows_equal(res.rows, [(expected,)], float_tol=1e-9)


def test_q1(runner):
    res = runner.execute(
        """
        SELECT l_returnflag, l_linestatus,
               sum(l_quantity) AS sum_qty,
               sum(l_extendedprice) AS sum_base_price,
               sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
               sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
               avg(l_quantity) AS avg_qty,
               avg(l_extendedprice) AS avg_price,
               avg(l_discount) AS avg_disc,
               count(*) AS count_order
        FROM lineitem
        WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '90' DAY
        GROUP BY l_returnflag, l_linestatus
        ORDER BY l_returnflag, l_linestatus
        """
    )
    li = tpch_df("lineitem", SCALE)
    m = li[li.l_shipdate <= days("1998-12-01") - 90].copy()
    m["disc_price"] = m.l_extendedprice * (1 - m.l_discount)
    m["charge"] = m.disc_price * (1 + m.l_tax)
    g = (
        m.groupby(["l_returnflag", "l_linestatus"])
        .agg(
            sum_qty=("l_quantity", "sum"),
            sum_base_price=("l_extendedprice", "sum"),
            sum_disc_price=("disc_price", "sum"),
            sum_charge=("charge", "sum"),
            avg_qty=("l_quantity", "mean"),
            avg_price=("l_extendedprice", "mean"),
            avg_disc=("l_discount", "mean"),
            count_order=("l_orderkey", "count"),
        )
        .reset_index()
        .sort_values(["l_returnflag", "l_linestatus"])
    )
    # decimal avg columns round to the column scale (Trino semantics)
    g["avg_qty"] = g.avg_qty.round(2)
    g["avg_price"] = g.avg_price.round(2)
    g["avg_disc"] = g.avg_disc.round(2)
    assert_rows_equal(
        res.rows, [tuple(r) for r in g.itertuples(index=False)], float_tol=1e-9
    )


def test_q3(runner):
    res = runner.execute(
        """
        SELECT l_orderkey,
               sum(l_extendedprice * (1 - l_discount)) AS revenue,
               o_orderdate, o_shippriority
        FROM customer, orders, lineitem
        WHERE c_mktsegment = 'BUILDING'
          AND c_custkey = o_custkey
          AND l_orderkey = o_orderkey
          AND o_orderdate < DATE '1995-03-15'
          AND l_shipdate > DATE '1995-03-15'
        GROUP BY l_orderkey, o_orderdate, o_shippriority
        ORDER BY revenue DESC, o_orderdate, l_orderkey
        LIMIT 10
        """
    )
    c = tpch_df("customer", SCALE)
    o = tpch_df("orders", SCALE)
    li = tpch_df("lineitem", SCALE)
    m = (
        c[c.c_mktsegment == "BUILDING"]
        .merge(o[o.o_orderdate < days("1995-03-15")], left_on="c_custkey", right_on="o_custkey")
        .merge(li[li.l_shipdate > days("1995-03-15")], left_on="o_orderkey", right_on="l_orderkey")
    )
    m["revenue"] = m.l_extendedprice * (1 - m.l_discount)
    g = (
        m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])["revenue"]
        .sum()
        .reset_index()
        .sort_values(["revenue", "o_orderdate", "l_orderkey"], ascending=[False, True, True])
        .head(10)
    )
    assert_rows_equal(
        res.rows,
        [
            (int(r.l_orderkey), round(r.revenue, 4), int(r.o_orderdate), int(r.o_shippriority))
            for r in g.itertuples()
        ],
        float_tol=1e-9,
    )


def test_q5(runner):
    res = runner.execute(
        """
        SELECT n_name, sum(l_extendedprice * (1 - l_discount)) AS revenue
        FROM customer, orders, lineitem, supplier, nation, region
        WHERE c_custkey = o_custkey
          AND l_orderkey = o_orderkey
          AND l_suppkey = s_suppkey
          AND c_nationkey = s_nationkey
          AND s_nationkey = n_nationkey
          AND n_regionkey = r_regionkey
          AND r_name = 'ASIA'
          AND o_orderdate >= DATE '1994-01-01'
          AND o_orderdate < DATE '1995-01-01'
        GROUP BY n_name
        ORDER BY revenue DESC
        """
    )
    c = tpch_df("customer", SCALE)
    o = tpch_df("orders", SCALE)
    li = tpch_df("lineitem", SCALE)
    s = tpch_df("supplier", SCALE)
    n = tpch_df("nation", SCALE)
    r = tpch_df("region", SCALE)
    m = (
        c.merge(o[(o.o_orderdate >= days("1994-01-01")) & (o.o_orderdate < days("1995-01-01"))],
                left_on="c_custkey", right_on="o_custkey")
        .merge(li, left_on="o_orderkey", right_on="l_orderkey")
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
    )
    m = m[m.c_nationkey == m.s_nationkey]
    m = m.merge(n, left_on="s_nationkey", right_on="n_nationkey").merge(
        r[r.r_name == "ASIA"], left_on="n_regionkey", right_on="r_regionkey"
    )
    m["revenue"] = m.l_extendedprice * (1 - m.l_discount)
    g = m.groupby("n_name")["revenue"].sum().reset_index().sort_values("revenue", ascending=False)
    assert_rows_equal(
        res.rows,
        [(r_.n_name, round(r_.revenue, 4)) for r_ in g.itertuples()],
        float_tol=1e-9,
    )


def test_q13(runner):
    res = runner.execute(
        """
        SELECT c_count, count(*) AS custdist
        FROM (
          SELECT c_custkey, count(o_orderkey) AS c_count
          FROM customer LEFT JOIN orders ON c_custkey = o_custkey
            AND o_comment NOT LIKE '%special%requests%'
          GROUP BY c_custkey
        ) AS c_orders
        GROUP BY c_count
        ORDER BY custdist DESC, c_count DESC
        """
    )
    c = tpch_df("customer", SCALE)
    o = tpch_df("orders", SCALE)
    of = o[~o.o_comment.str.contains("special.*requests", regex=True)]
    m = c.merge(of, left_on="c_custkey", right_on="o_custkey", how="left")
    cc = m.groupby("c_custkey")["o_orderkey"].count().reset_index(name="c_count")
    cd = (
        cc.groupby("c_count").size().reset_index(name="custdist")
        .sort_values(["custdist", "c_count"], ascending=[False, False])
    )
    assert_rows_equal(
        res.rows, [(int(r.c_count), int(r.custdist)) for r in cd.itertuples()]
    )


def test_q14(runner):
    res = runner.execute(
        """
        SELECT 100.00 * sum(CASE WHEN p_type LIKE 'PROMO%'
                                 THEN l_extendedprice * (1 - l_discount)
                                 ELSE 0 END)
               / sum(l_extendedprice * (1 - l_discount)) AS promo_revenue
        FROM lineitem, part
        WHERE l_partkey = p_partkey
          AND l_shipdate >= DATE '1995-09-01'
          AND l_shipdate < DATE '1995-10-01'
        """
    )
    li = tpch_df("lineitem", SCALE)
    p = tpch_df("part", SCALE)
    m = li[(li.l_shipdate >= days("1995-09-01")) & (li.l_shipdate < days("1995-10-01"))].merge(
        p, left_on="l_partkey", right_on="p_partkey"
    )
    disc = m.l_extendedprice * (1 - m.l_discount)
    promo = disc.where(m.p_type.str.startswith("PROMO"), 0.0)
    expected = 100.0 * promo.sum() / disc.sum()
    assert_rows_equal(res.rows, [(expected,)], float_tol=1e-9)


def test_q18(runner):
    res = runner.execute(
        """
        SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
               sum(l_quantity)
        FROM customer, orders, lineitem
        WHERE o_orderkey IN (
            SELECT l_orderkey FROM lineitem
            GROUP BY l_orderkey HAVING sum(l_quantity) > 150
          )
          AND c_custkey = o_custkey
          AND o_orderkey = l_orderkey
        GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
        ORDER BY o_totalprice DESC, o_orderdate, o_orderkey
        LIMIT 100
        """
    )
    c = tpch_df("customer", SCALE)
    o = tpch_df("orders", SCALE)
    li = tpch_df("lineitem", SCALE)
    big = li.groupby("l_orderkey")["l_quantity"].sum()
    big = set(big[big > 150].index)
    m = (
        c.merge(o[o.o_orderkey.isin(big)], left_on="c_custkey", right_on="o_custkey")
        .merge(li, left_on="o_orderkey", right_on="l_orderkey")
    )
    g = (
        m.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"])["l_quantity"]
        .sum()
        .reset_index()
        .sort_values(["o_totalprice", "o_orderdate", "o_orderkey"], ascending=[False, True, True])
        .head(100)
    )
    assert_rows_equal(
        res.rows,
        [
            (r.c_name, int(r.c_custkey), int(r.o_orderkey), int(r.o_orderdate),
             r.o_totalprice, r.l_quantity)
            for r in g.itertuples()
        ],
        float_tol=1e-9,
    )


def test_q12(runner):
    res = runner.execute(
        """
        SELECT l_shipmode,
               sum(CASE WHEN o_orderpriority = '1-URGENT' OR o_orderpriority = '2-HIGH'
                        THEN 1 ELSE 0 END) AS high_line_count,
               sum(CASE WHEN o_orderpriority <> '1-URGENT' AND o_orderpriority <> '2-HIGH'
                        THEN 1 ELSE 0 END) AS low_line_count
        FROM orders, lineitem
        WHERE o_orderkey = l_orderkey
          AND l_shipmode IN ('MAIL', 'SHIP')
          AND l_commitdate < l_receiptdate
          AND l_shipdate < l_commitdate
          AND l_receiptdate >= DATE '1994-01-01'
          AND l_receiptdate < DATE '1995-01-01'
        GROUP BY l_shipmode
        ORDER BY l_shipmode
        """
    )
    o = tpch_df("orders", SCALE)
    li = tpch_df("lineitem", SCALE)
    m = li[
        li.l_shipmode.isin(["MAIL", "SHIP"])
        & (li.l_commitdate < li.l_receiptdate)
        & (li.l_shipdate < li.l_commitdate)
        & (li.l_receiptdate >= days("1994-01-01"))
        & (li.l_receiptdate < days("1995-01-01"))
    ].merge(o, left_on="l_orderkey", right_on="o_orderkey")
    high = m.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    g = (
        m.assign(h=high.astype(int), l=(~high).astype(int))
        .groupby("l_shipmode")
        .agg(h=("h", "sum"), l=("l", "sum"))
        .reset_index()
        .sort_values("l_shipmode")
    )
    assert_rows_equal(
        res.rows, [(r.l_shipmode, int(r.h), int(r.l)) for r in g.itertuples()]
    )


def test_q19_simplified(runner):
    # Q19's OR-of-ANDs over two tables (quantity windows x brand x container)
    res = runner.execute(
        """
        SELECT sum(l_extendedprice * (1 - l_discount)) AS revenue
        FROM lineitem, part
        WHERE p_partkey = l_partkey
          AND ((p_brand = 'Brand#12' AND l_quantity BETWEEN 1 AND 11)
            OR (p_brand = 'Brand#23' AND l_quantity BETWEEN 10 AND 20))
        """
    )
    li = tpch_df("lineitem", SCALE)
    p = tpch_df("part", SCALE)
    m = li.merge(p, left_on="l_partkey", right_on="p_partkey")
    cond = ((m.p_brand == "Brand#12") & m.l_quantity.between(1, 11)) | (
        (m.p_brand == "Brand#23") & m.l_quantity.between(10, 20)
    )
    expected = (m[cond].l_extendedprice * (1 - m[cond].l_discount)).sum()
    assert_rows_equal(res.rows, [(round(expected, 4),)], float_tol=1e-9)


def test_q7(runner):
    res = runner.execute(
        """
        SELECT supp_nation, cust_nation, l_year, sum(volume) AS revenue FROM (
          SELECT n1.n_name AS supp_nation, n2.n_name AS cust_nation,
                 EXTRACT(YEAR FROM l_shipdate) AS l_year,
                 l_extendedprice * (1 - l_discount) AS volume
          FROM supplier, lineitem, orders, customer, nation n1, nation n2
          WHERE s_suppkey = l_suppkey AND o_orderkey = l_orderkey AND c_custkey = o_custkey
            AND s_nationkey = n1.n_nationkey AND c_nationkey = n2.n_nationkey
            AND ((n1.n_name = 'FRANCE' AND n2.n_name = 'GERMANY')
              OR (n1.n_name = 'GERMANY' AND n2.n_name = 'FRANCE'))
            AND l_shipdate BETWEEN DATE '1995-01-01' AND DATE '1996-12-31') AS shipping
        GROUP BY supp_nation, cust_nation, l_year
        ORDER BY supp_nation, cust_nation, l_year
        """
    )
    s = tpch_df("supplier", SCALE)
    li = tpch_df("lineitem", SCALE)
    o = tpch_df("orders", SCALE)
    c = tpch_df("customer", SCALE)
    n = tpch_df("nation", SCALE)
    m = (
        li[(li.l_shipdate >= days("1995-01-01")) & (li.l_shipdate <= days("1996-12-31"))]
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(c, left_on="o_custkey", right_on="c_custkey")
        .merge(n.add_suffix("_1"), left_on="s_nationkey", right_on="n_nationkey_1")
        .merge(n.add_suffix("_2"), left_on="c_nationkey", right_on="n_nationkey_2")
    )
    m = m[
        ((m.n_name_1 == "FRANCE") & (m.n_name_2 == "GERMANY"))
        | ((m.n_name_1 == "GERMANY") & (m.n_name_2 == "FRANCE"))
    ].copy()
    m["l_year"] = pd.to_datetime(m.l_shipdate, unit="D").dt.year
    m["volume"] = m.l_extendedprice * (1 - m.l_discount)
    g = (
        m.groupby(["n_name_1", "n_name_2", "l_year"])["volume"].sum().reset_index()
        .sort_values(["n_name_1", "n_name_2", "l_year"])
    )
    assert_rows_equal(
        res.rows,
        [(r_.n_name_1, r_.n_name_2, int(r_.l_year), round(r_.volume, 4)) for r_ in g.itertuples()],
        float_tol=1e-9,
    )


def test_q9(runner):
    res = runner.execute(
        """
        SELECT nation, o_year, sum(amount) AS sum_profit FROM (
          SELECT n_name AS nation, EXTRACT(YEAR FROM o_orderdate) AS o_year,
                 l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity AS amount
          FROM part, supplier, lineitem, partsupp, orders, nation
          WHERE s_suppkey = l_suppkey AND ps_suppkey = l_suppkey AND ps_partkey = l_partkey
            AND p_partkey = l_partkey AND o_orderkey = l_orderkey AND s_nationkey = n_nationkey
            AND p_name LIKE '%green%') AS profit
        GROUP BY nation, o_year
        ORDER BY nation, o_year DESC
        """
    )
    p = tpch_df("part", SCALE)
    s = tpch_df("supplier", SCALE)
    li = tpch_df("lineitem", SCALE)
    ps = tpch_df("partsupp", SCALE)
    o = tpch_df("orders", SCALE)
    n = tpch_df("nation", SCALE)
    m = (
        li.merge(p[p.p_name.str.contains("green")], left_on="l_partkey", right_on="p_partkey")
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
        .merge(ps, left_on=["l_partkey", "l_suppkey"], right_on=["ps_partkey", "ps_suppkey"])
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
    )
    m = m.copy()
    m["o_year"] = pd.to_datetime(m.o_orderdate, unit="D").dt.year
    m["amount"] = m.l_extendedprice * (1 - m.l_discount) - m.ps_supplycost * m.l_quantity
    g = (
        m.groupby(["n_name", "o_year"])["amount"].sum().reset_index()
        .sort_values(["n_name", "o_year"], ascending=[True, False])
    )
    assert_rows_equal(
        res.rows,
        [(r_.n_name, int(r_.o_year), round(r_.amount, 4)) for r_ in g.itertuples()],
        float_tol=1e-9,
    )


def test_q10(runner):
    res = runner.execute(
        """
        SELECT c_custkey, c_name, sum(l_extendedprice * (1 - l_discount)) AS revenue, c_acctbal
        FROM customer, orders, lineitem, nation
        WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey
          AND o_orderdate >= DATE '1993-10-01' AND o_orderdate < DATE '1994-01-01'
          AND l_returnflag = 'R' AND c_nationkey = n_nationkey
        GROUP BY c_custkey, c_name, c_acctbal
        ORDER BY revenue DESC, c_custkey
        LIMIT 20
        """
    )
    c = tpch_df("customer", SCALE)
    o = tpch_df("orders", SCALE)
    li = tpch_df("lineitem", SCALE)
    n = tpch_df("nation", SCALE)
    m = (
        c.merge(
            o[(o.o_orderdate >= days("1993-10-01")) & (o.o_orderdate < days("1994-01-01"))],
            left_on="c_custkey", right_on="o_custkey",
        )
        .merge(li[li.l_returnflag == "R"], left_on="o_orderkey", right_on="l_orderkey")
        .merge(n, left_on="c_nationkey", right_on="n_nationkey")
    )
    m["revenue"] = m.l_extendedprice * (1 - m.l_discount)
    g = (
        m.groupby(["c_custkey", "c_name", "c_acctbal"])["revenue"].sum().reset_index()
        .sort_values(["revenue", "c_custkey"], ascending=[False, True]).head(20)
    )
    assert_rows_equal(
        res.rows,
        [
            (int(r_.c_custkey), r_.c_name, round(r_.revenue, 4), r_.c_acctbal)
            for r_ in g.itertuples()
        ],
        float_tol=1e-9,
    )


def test_q11(runner):
    res = runner.execute(
        """
        SELECT ps_partkey, sum(ps_supplycost * ps_availqty) AS value
        FROM partsupp, supplier, nation
        WHERE ps_suppkey = s_suppkey AND s_nationkey = n_nationkey AND n_name = 'GERMANY'
        GROUP BY ps_partkey
        HAVING sum(ps_supplycost * ps_availqty) > (
          SELECT sum(ps_supplycost * ps_availqty) * 0.0001
          FROM partsupp, supplier, nation
          WHERE ps_suppkey = s_suppkey AND s_nationkey = n_nationkey AND n_name = 'GERMANY')
        ORDER BY value DESC, ps_partkey
        """
    )
    ps = tpch_df("partsupp", SCALE)
    s = tpch_df("supplier", SCALE)
    n = tpch_df("nation", SCALE)
    m = ps.merge(s, left_on="ps_suppkey", right_on="s_suppkey").merge(
        n[n.n_name == "GERMANY"], left_on="s_nationkey", right_on="n_nationkey"
    )
    m["value"] = m.ps_supplycost * m.ps_availqty
    g = m.groupby("ps_partkey")["value"].sum().reset_index()
    threshold = m.value.sum() * 0.0001
    g = g[g.value > threshold].sort_values(["value", "ps_partkey"], ascending=[False, True])
    assert_rows_equal(
        res.rows,
        [(int(r_.ps_partkey), round(r_.value, 4)) for r_ in g.itertuples()],
        float_tol=1e-9,
    )


def test_q15(runner):
    res = runner.execute(
        """
        WITH revenue0 AS (
          SELECT l_suppkey AS supplier_no, sum(l_extendedprice * (1 - l_discount)) AS total_revenue
          FROM lineitem
          WHERE l_shipdate >= DATE '1996-01-01' AND l_shipdate < DATE '1996-04-01'
          GROUP BY l_suppkey)
        SELECT s_suppkey, s_name, total_revenue
        FROM supplier, revenue0
        WHERE s_suppkey = supplier_no AND total_revenue = (SELECT max(total_revenue) FROM revenue0)
        ORDER BY s_suppkey
        """
    )
    li = tpch_df("lineitem", SCALE)
    s = tpch_df("supplier", SCALE)
    rev = (
        li[(li.l_shipdate >= days("1996-01-01")) & (li.l_shipdate < days("1996-04-01"))]
        .assign(rev=lambda d: d.l_extendedprice * (1 - d.l_discount))
        .groupby("l_suppkey")["rev"].sum()
    )
    top = rev[rev.round(4) == round(rev.max(), 4)]
    m = s[s.s_suppkey.isin(top.index)].sort_values("s_suppkey")
    assert_rows_equal(
        res.rows,
        [(int(r_.s_suppkey), r_.s_name, round(rev[r_.s_suppkey], 4)) for r_ in m.itertuples()],
        float_tol=1e-9,
    )


def test_q16(runner):
    res = runner.execute(
        """
        SELECT p_brand, p_type, p_size, count(DISTINCT ps_suppkey) AS supplier_cnt
        FROM partsupp, part
        WHERE p_partkey = ps_partkey AND p_brand <> 'Brand#45'
          AND p_type NOT LIKE 'MEDIUM POLISHED%'
          AND p_size IN (49, 14, 23, 45, 19, 3, 36, 9)
          AND ps_suppkey NOT IN (SELECT s_suppkey FROM supplier
                                 WHERE s_comment LIKE '%Customer%Complaints%')
        GROUP BY p_brand, p_type, p_size
        ORDER BY supplier_cnt DESC, p_brand, p_type, p_size
        """
    )
    ps = tpch_df("partsupp", SCALE)
    p = tpch_df("part", SCALE)
    s = tpch_df("supplier", SCALE)
    complained = s[s.s_comment.str.contains("Customer.*Complaints", regex=True)].s_suppkey
    ps = ps[~ps.ps_suppkey.isin(complained)]
    pf = p[
        (p.p_brand != "Brand#45")
        & ~p.p_type.str.startswith("MEDIUM POLISHED")
        & p.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])
    ]
    m = ps.merge(pf, left_on="ps_partkey", right_on="p_partkey")
    g = (
        m.groupby(["p_brand", "p_type", "p_size"])["ps_suppkey"].nunique().reset_index(name="cnt")
        .sort_values(["cnt", "p_brand", "p_type", "p_size"], ascending=[False, True, True, True])
    )
    assert_rows_equal(
        res.rows,
        [(r_.p_brand, r_.p_type, int(r_.p_size), int(r_.cnt)) for r_ in g.itertuples()],
    )


def test_q4(runner):
    res = runner.execute(
        """
        SELECT o_orderpriority, count(*) AS order_count
        FROM orders
        WHERE o_orderdate >= DATE '1993-07-01' AND o_orderdate < DATE '1993-10-01'
          AND EXISTS (SELECT * FROM lineitem
                      WHERE l_orderkey = o_orderkey AND l_commitdate < l_receiptdate)
        GROUP BY o_orderpriority ORDER BY o_orderpriority
        """
    )
    o = tpch_df("orders", SCALE)
    li = tpch_df("lineitem", SCALE)
    good = set(li[li.l_commitdate < li.l_receiptdate].l_orderkey)
    m = o[
        (o.o_orderdate >= days("1993-07-01"))
        & (o.o_orderdate < days("1993-10-01"))
        & o.o_orderkey.isin(good)
    ]
    exp = m.groupby("o_orderpriority").size().reset_index(name="c").sort_values("o_orderpriority")
    assert_rows_equal(res.rows, [tuple(r) for r in exp.itertuples(index=False)])


def test_q17(runner):
    res = runner.execute(
        """
        SELECT sum(l_extendedprice) / 7.0 AS avg_yearly
        FROM lineitem, part
        WHERE p_partkey = l_partkey AND p_brand = 'Brand#23'
          AND l_quantity < (SELECT 0.2 * avg(l_quantity) FROM lineitem l2
                            WHERE l2.l_partkey = p_partkey)
        """
    )
    li = tpch_df("lineitem", SCALE)
    p = tpch_df("part", SCALE)
    avg_by_part = li.groupby("l_partkey")["l_quantity"].mean()
    m = li.merge(p[p.p_brand == "Brand#23"], left_on="l_partkey", right_on="p_partkey")
    m = m[m.l_quantity < 0.2 * m.l_partkey.map(avg_by_part)]
    expected = m.l_extendedprice.sum() / 7.0 if len(m) else None
    got = res.rows[0][0]
    if expected is None:
        assert got is None
    else:
        assert abs(got - expected) <= 1e-9 * max(1.0, abs(expected))


def test_q22_shape(runner):
    res = runner.execute(
        """
        SELECT count(*) FROM customer
        WHERE c_acctbal > 500
          AND NOT EXISTS (SELECT * FROM orders
                          WHERE o_custkey = c_custkey AND o_totalprice > 100000)
        """
    )
    c = tpch_df("customer", SCALE)
    o = tpch_df("orders", SCALE)
    has_big = set(o[o.o_totalprice > 100000].o_custkey)
    exp = int(((c.c_acctbal > 500) & ~c.c_custkey.isin(has_big)).sum())
    assert res.rows == [(exp,)]


def test_q2_shape(runner):
    res = runner.execute(
        """
        SELECT s_name, p_partkey, ps_supplycost
        FROM part, supplier, partsupp
        WHERE p_partkey = ps_partkey AND s_suppkey = ps_suppkey
          AND ps_supplycost = (SELECT min(ps_supplycost) FROM partsupp ps2
                               WHERE ps2.ps_partkey = p_partkey)
        ORDER BY p_partkey, s_name LIMIT 10
        """
    )
    p = tpch_df("part", SCALE)
    s = tpch_df("supplier", SCALE)
    ps = tpch_df("partsupp", SCALE)
    min_cost = ps.groupby("ps_partkey")["ps_supplycost"].min()
    m = ps.merge(p, left_on="ps_partkey", right_on="p_partkey").merge(
        s, left_on="ps_suppkey", right_on="s_suppkey"
    )
    m = m[m.ps_supplycost == m.ps_partkey.map(min_cost)]
    exp = m.sort_values(["p_partkey", "s_name"]).head(10)
    assert_rows_equal(
        res.rows,
        [(r.s_name, int(r.p_partkey), r.ps_supplycost) for r in exp.itertuples()],
        float_tol=1e-9,
    )
