"""TPC-H Q15, top supplier (specification clause 2.4.15): the suppliers whose
revenue in one quarter is the largest. The specification creates the view
`revenue0` before the query and drops it after; the served path sends one
statement, so the view is the statement's WITH query."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """WITH revenue0 (supplier_no, total_revenue) AS (
        SELECT l_suppkey,
               sum(l_extendedprice * (1 - l_discount))
        FROM {schema}.lineitem
        WHERE l_shipdate >= DATE '{date}'
          AND l_shipdate < DATE '{date}' + INTERVAL '3' MONTH
        GROUP BY l_suppkey)
SELECT s_suppkey,
       s_name,
       s_address,
       s_phone,
       total_revenue
FROM {schema}.supplier, revenue0
WHERE s_suppkey = supplier_no
  AND total_revenue = (
        SELECT max(total_revenue)
        FROM revenue0)
ORDER BY s_suppkey"""

# clause 2.4.15.3: DATE is the first day of a month from January 1993 to
# October 1997 (58 months)
DOMAIN = {"month": [f"{y}-{m:02d}" for y in range(1993, 1998) for m in range(1, 13)
                    if (y, m) <= (1997, 10)]}
COLUMNS = {
    "lineitem": ["l_suppkey", "l_shipdate", "l_extendedprice", "l_discount"],
    "supplier": ["s_suppkey", "s_address"],
}


def _date(p: dict) -> str:
    return f"{p['month']}-01"


def literals(p: dict) -> dict:
    return {"date": _date(p)}


def _top(host: dict, p: dict, num: ref.Arith):
    """(supplier positions at the largest revenue, ascending, and that revenue
    in units of 1e-4)."""
    li, supp = host["lineitem"], host["supplier"]
    ship = li["l_shipdate"]
    m = (ship >= ref.days(_date(p))) & (ship < ref.days(ref.add_months(_date(p), 3)))
    sold, inverse = np.unique(li["l_suppkey"][m], return_inverse=True)  # revenue0's groups
    if len(sold) == 0:
        return np.zeros(0, np.int64), 0
    revenue = grouped.totals(grouped.discounted(li, m, num), inverse, len(sold), num)
    best = int(revenue.max())
    pos, found = ref.lookup(supp["s_suppkey"], sold[revenue == best])
    return pos[found], best


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    supp = host["supplier"]
    rows, best = _top(host, p, num)
    out = []
    for s in rows:
        key = int(supp["s_suppkey"][s])
        out.append([key, f"Supplier#{key:09d}", population.COMMENT_POOL[supp["s_address"][s]],
                    f"{10 + (key - 1) % 25}-{key:011d}", ref.dec(best, 4)])
    return out


def ties(host: dict, p: dict) -> bool:
    """The specification's ORDER BY is s_suppkey, the supplier's key: no two
    rows can tie. Evaluated all the same, as the join templates' are."""
    rows, _ = _top(host, p, ref.EXACT)
    keys = host["supplier"]["s_suppkey"][rows]
    return grouped.adjacent_ties(len(keys), keys)
