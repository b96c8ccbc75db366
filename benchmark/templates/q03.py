"""TPC-H Q3, shipping priority (specification clause 2.4.3): the ten unshipped
orders of a market segment with the highest value."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT l_orderkey,
       sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate,
       o_shippriority
FROM {schema}.customer, {schema}.orders, {schema}.lineitem
WHERE c_mktsegment = '{segment}'
  AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND o_orderdate < DATE '{date}'
  AND l_shipdate > DATE '{date}'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate
LIMIT 10"""

# clause 2.4.3.3: SEGMENT is one of the five market segments, DATE a day in
# [1995-03-01, 1995-03-31]
DOMAIN = {"segment": list(population.SEGMENTS), "day": list(range(1, 32))}
COLUMNS = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice", "l_discount"],
}
FIRST = 10


def literals(p: dict) -> dict:
    return {"segment": p["segment"], "date": f"1995-03-{p['day']:02d}"}


def _groups(host: dict, p: dict, num: ref.Arith):
    """(revenue units, order date, ship priority, order key) of every group,
    in the specification's order; rows that tie on it by the other columns."""
    cust, orders, li = host["customer"], host["orders"], host["lineitem"]
    date = ref.days(f"1995-03-{p['day']:02d}")
    pos, found = ref.lookup(cust["c_custkey"], orders["o_custkey"])
    wanted = found & (cust["c_mktsegment"][pos] == population.SEGMENTS.index(p["segment"]))
    wanted &= orders["o_orderdate"] < date
    m = li["l_shipdate"] > date
    opos, ofound = ref.lookup(orders["o_orderkey"], li["l_orderkey"][m])
    keep = ofound & wanted[opos]
    units = grouped.discounted(li, m, num)[keep]
    which, inverse = np.unique(opos[keep], return_inverse=True)
    revenue = grouped.totals(units, inverse, len(which), num)
    odate, prio = orders["o_orderdate"][which], orders["o_shippriority"][which]
    okey = orders["o_orderkey"][which]
    order = np.lexsort((prio, okey, odate, -revenue))
    return revenue[order], odate[order], prio[order], okey[order]


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    revenue, odate, prio, okey = _groups(host, p, num)
    return [
        [int(okey[i]), ref.dec(revenue[i], 4), grouped.iso(odate[i]), int(prio[i])]
        for i in range(min(FIRST, len(okey)))
    ]


def ties(host: dict, p: dict) -> bool:
    """Whether two rows kept, or the last kept and the first cut, tie on the
    specification's ORDER BY (revenue DESC, o_orderdate)."""
    revenue, odate, _, _ = _groups(host, p, ref.EXACT)
    return grouped.adjacent_ties(FIRST, revenue, odate)
