"""TPC-H Q12, shipping modes and order priority (specification clause
2.4.12): of the lines of two ship modes received late in one year, how many
belong to high-priority orders and how many to the rest."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT l_shipmode,
       sum(CASE
             WHEN o_orderpriority = '1-URGENT'
               OR o_orderpriority = '2-HIGH'
             THEN 1
             ELSE 0
           END) AS high_line_count,
       sum(CASE
             WHEN o_orderpriority <> '1-URGENT'
               AND o_orderpriority <> '2-HIGH'
             THEN 1
             ELSE 0
           END) AS low_line_count
FROM {schema}.orders, {schema}.lineitem
WHERE o_orderkey = l_orderkey
  AND l_shipmode IN ('{shipmode1}', '{shipmode2}')
  AND l_commitdate < l_receiptdate
  AND l_shipdate < l_commitdate
  AND l_receiptdate >= DATE '{date}'
  AND l_receiptdate < DATE '{date}' + INTERVAL '1' YEAR
GROUP BY l_shipmode
ORDER BY l_shipmode"""

# clause 2.4.12.3: SHIPMODE1 and SHIPMODE2 two different modes of the seven,
# DATE the first of January of a year in [1993, 1997]. The harness's domain is
# a product of lists, so the ordered pairs are written out: the validation
# pair of cl. 2.4.12.4 (MAIL, SHIP; 1994) first
_VALIDATION = ["MAIL", "SHIP"]
DOMAIN = {"modes": [_VALIDATION] + [[a, b] for a in population.SHIP_MODES for b in population.SHIP_MODES
                                    if a != b and [a, b] != _VALIDATION],
          "year": [1993, 1994, 1995, 1996, 1997]}
COLUMNS = {
    "orders": ["o_orderkey", "o_orderpriority"],
    "lineitem": ["l_orderkey", "l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate"],
}
_HIGH = [population.PRIORITIES.index("1-URGENT"), population.PRIORITIES.index("2-HIGH")]


def literals(p: dict) -> dict:
    return {"shipmode1": p["modes"][0], "shipmode2": p["modes"][1], "date": f"{p['year']}-01-01"}


def _groups(host: dict, p: dict):
    """(mode code, high count, low count) of every group, in the answer's
    order; codes order as the strings do."""
    orders, li = host["orders"], host["lineitem"]
    modes = [population.SHIP_MODES.index(m) for m in p["modes"]]
    receipt, commit = li["l_receiptdate"], li["l_commitdate"]
    rows = np.flatnonzero(
        np.isin(li["l_shipmode"], modes) & (commit < receipt) & (li["l_shipdate"] < commit)
        & (receipt >= ref.days(f"{p['year']}-01-01")) & (receipt < ref.days(f"{p['year'] + 1}-01-01")))
    opos, found = ref.lookup(orders["o_orderkey"], li["l_orderkey"][rows])
    high = np.isin(orders["o_orderpriority"][opos[found]], _HIGH)
    mode = li["l_shipmode"][rows[found]]
    groups, inverse = np.unique(mode, return_inverse=True)
    n_high = np.bincount(inverse, weights=high, minlength=len(groups)).astype(np.int64)
    n_all = np.bincount(inverse, minlength=len(groups)).astype(np.int64)
    return groups, n_high, n_all - n_high


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    groups, high, low = _groups(host, p)
    return [[population.SHIP_MODES[m], int(h), int(lo)] for m, h, lo in zip(groups, high, low)]


def ties(host: dict, p: dict) -> bool:
    """The ORDER BY is the grouping key: no two rows can tie. Evaluated all
    the same, as the join templates' are."""
    groups, _, _ = _groups(host, p)
    return grouped.adjacent_ties(len(groups), groups)
