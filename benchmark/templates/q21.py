"""TPC-H Q21, suppliers who kept orders waiting (specification clause 2.4.21):
suppliers of one nation who alone were late on an order of several suppliers."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT s_name,
       count(*) AS numwait
FROM {schema}.supplier, {schema}.lineitem l1, {schema}.orders, {schema}.nation
WHERE s_suppkey = l1.l_suppkey
  AND o_orderkey = l1.l_orderkey
  AND o_orderstatus = 'F'
  AND l1.l_receiptdate > l1.l_commitdate
  AND EXISTS (
        SELECT *
        FROM {schema}.lineitem l2
        WHERE l2.l_orderkey = l1.l_orderkey
          AND l2.l_suppkey <> l1.l_suppkey)
  AND NOT EXISTS (
        SELECT *
        FROM {schema}.lineitem l3
        WHERE l3.l_orderkey = l1.l_orderkey
          AND l3.l_suppkey <> l1.l_suppkey
          AND l3.l_receiptdate > l3.l_commitdate)
  AND s_nationkey = n_nationkey
  AND n_name = '{nation}'
GROUP BY s_name
ORDER BY numwait DESC, s_name
LIMIT 100"""

# clause 2.4.21.3: NATION is one of the 25 names
DOMAIN = {"nation": [name for name, _ in population.NATIONS]}
COLUMNS = {
    "supplier": ["s_suppkey", "s_nationkey"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_receiptdate", "l_commitdate"],
    "orders": ["o_orderkey", "o_orderstatus"],
    "nation": ["n_nationkey", "n_name"],
}
FIRST = 100
_NATION_NAMES = sorted(n for n, _ in population.NATIONS)


def literals(p: dict) -> dict:
    return {"nation": p["nation"]}


def suppliers_per_order(orderkey: np.ndarray, suppkey: np.ndarray, orders: np.ndarray) -> np.ndarray:
    """How many different suppliers each of `orders` (ascending keys) has among
    the lines (orderkey, suppkey): the size of the order's set of suppliers."""
    base = int(suppkey.max(initial=0)) + 1
    pairs = np.unique(orderkey * base + suppkey)  # each (order, supplier) once
    pos, found = ref.lookup(orders, pairs // base)
    return np.bincount(pos[found], minlength=len(orders))


_WAITING: dict = {}   # the one population a process evaluates: its waiting lines, whatever the nation


def waiting_lines(li: dict) -> np.ndarray:
    """The lines l1 that are late, in an order with another supplier (EXISTS),
    where no other supplier's line is late (NOT EXISTS). By sets: the order's
    suppliers are two or more, and its late suppliers are l1's alone."""
    if _WAITING.get("of") is not li["l_orderkey"]:
        _WAITING["of"], _WAITING["lines"] = li["l_orderkey"], _waiting_lines(li)
    return _WAITING["lines"]


def _waiting_lines(li: dict) -> np.ndarray:
    late = li["l_receiptdate"] > li["l_commitdate"]
    keys = np.unique(li["l_orderkey"])
    everyone = suppliers_per_order(li["l_orderkey"], li["l_suppkey"], keys)
    late_ones = suppliers_per_order(li["l_orderkey"][late], li["l_suppkey"][late], keys)
    pos, _ = ref.lookup(keys, li["l_orderkey"])
    return late & (everyone[pos] >= 2) & (late_ones[pos] == 1)


def _groups(host: dict, p: dict):
    """(numwait, s_suppkey) of every group, in the answer's order."""
    supp, li, orders, nation = host["supplier"], host["lineitem"], host["orders"], host["nation"]
    wanted = nation["n_nationkey"][nation["n_name"] == _NATION_NAMES.index(p["nation"])]
    spos, sfound = ref.lookup(supp["s_suppkey"], li["l_suppkey"])
    opos, ofound = ref.lookup(orders["o_orderkey"], li["l_orderkey"])
    finished = orders["o_orderstatus"] == population.ORDER_STATUS.index("F")
    keep = (waiting_lines(li) & sfound & np.isin(supp["s_nationkey"][spos], wanted)
            & ofound & finished[opos])
    which, numwait = np.unique(supp["s_suppkey"][spos[keep]], return_counts=True)
    order = np.lexsort((which, -numwait))  # s_name orders as s_suppkey does
    return numwait[order], which[order]


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    numwait, suppkey = _groups(host, p)
    return [[f"Supplier#{int(s):09d}", int(n)] for n, s in zip(numwait[:FIRST], suppkey[:FIRST])]


def ties(host: dict, p: dict) -> bool:
    """Whether two rows kept, or the last kept and the first cut, tie on the
    specification's ORDER BY (numwait DESC, s_name); s_name is the grouping's
    key, so none can."""
    numwait, suppkey = _groups(host, p)
    return grouped.adjacent_ties(FIRST, numwait, suppkey)
