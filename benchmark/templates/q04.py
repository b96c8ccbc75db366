"""TPC-H Q4, order priority checking (specification clause 2.4.4): how many
orders of one quarter had at least one line received after its commit date,
by priority."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT o_orderpriority,
       count(*) AS order_count
FROM {schema}.orders
WHERE o_orderdate >= DATE '{date}'
  AND o_orderdate < DATE '{date}' + INTERVAL '3' MONTH
  AND EXISTS (
        SELECT *
        FROM {schema}.lineitem
        WHERE l_orderkey = o_orderkey
          AND l_commitdate < l_receiptdate)
GROUP BY o_orderpriority
ORDER BY o_orderpriority"""

# clause 2.4.4.3: DATE is the first day of a month from January 1993 to
# October 1997 (58 months)
DOMAIN = {"month": [f"{y}-{m:02d}" for y in range(1993, 1998) for m in range(1, 13)
                    if (y, m) <= (1997, 10)]}
COLUMNS = {
    "orders": ["o_orderkey", "o_orderdate", "o_orderpriority"],
    "lineitem": ["l_orderkey", "l_commitdate", "l_receiptdate"],
}


def literals(p: dict) -> dict:
    return {"date": f"{p['month']}-01"}


def _groups(host: dict, p: dict):
    """(priority code, order count) of every group, in the answer's order;
    codes order as the strings do."""
    orders, li = host["orders"], host["lineitem"]
    first = literals(p)["date"]
    late = np.unique(li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]])
    odate = orders["o_orderdate"]
    keep = ((odate >= ref.days(first)) & (odate < ref.days(ref.add_months(first, 3)))
            & np.isin(orders["o_orderkey"], late))
    return np.unique(orders["o_orderpriority"][keep], return_counts=True)


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    priority, count = _groups(host, p)
    return [[population.PRIORITIES[c], int(n)] for c, n in zip(priority, count)]


def ties(host: dict, p: dict) -> bool:
    """The ORDER BY is the grouping key: no two rows can tie. Evaluated all
    the same, as the join templates' are."""
    priority, _ = _groups(host, p)
    return grouped.adjacent_ties(len(priority), priority)
