"""TPC-H Q10, returned item reporting (specification clause 2.4.10): the
twenty customers who lost most revenue to returned parts in a quarter."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT c_custkey,
       c_name,
       sum(l_extendedprice * (1 - l_discount)) AS revenue,
       c_acctbal,
       n_name,
       c_address,
       c_phone,
       c_comment
FROM {schema}.customer, {schema}.orders, {schema}.lineitem, {schema}.nation
WHERE c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND o_orderdate >= DATE '{date}'
  AND o_orderdate < DATE '{date}' + INTERVAL '3' MONTH
  AND l_returnflag = 'R'
  AND c_nationkey = n_nationkey
GROUP BY c_custkey, c_name, c_acctbal, c_phone, n_name, c_address, c_comment
ORDER BY revenue DESC
LIMIT 20"""

# clause 2.4.10.3: DATE is the first day of a month from February 1993 to
# January 1995
DOMAIN = {"month": [f"1993-{m:02d}" for m in range(2, 13)] + [f"1994-{m:02d}" for m in range(1, 13)]
          + ["1995-01"]}
COLUMNS = {
    "customer": ["c_custkey", "c_name", "c_acctbal", "c_nationkey", "c_address", "c_phone",
                 "c_comment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "lineitem": ["l_orderkey", "l_returnflag", "l_extendedprice", "l_discount"],
    "nation": ["n_nationkey", "n_name"],
}
FIRST = 20
_NATION_NAMES = sorted(n for n, _ in population.NATIONS)


def _date(p: dict) -> str:
    return f"{p['month']}-01"


def literals(p: dict) -> dict:
    return {"date": _date(p)}


def _rows(host: dict, p: dict, num: ref.Arith):
    """(revenue units of every group in the answer's order, the rows of the
    first FIRST + 1 of them)."""
    cust, orders, li, nation = host["customer"], host["orders"], host["lineitem"], host["nation"]
    odate = orders["o_orderdate"]
    in_quarter = (odate >= ref.days(_date(p))) & (odate < ref.days(ref.add_months(_date(p), 3)))
    cpos, cfound = ref.lookup(cust["c_custkey"], orders["o_custkey"])
    npos, nfound = ref.lookup(nation["n_nationkey"], cust["c_nationkey"])
    order_ok = in_quarter & cfound & nfound[cpos]
    returned = li["l_returnflag"] == population.RETURN_FLAGS.index("R")
    opos, ofound = ref.lookup(orders["o_orderkey"], li["l_orderkey"][returned])
    keep = ofound & order_ok[opos]
    which, inverse = np.unique(cpos[opos[keep]], return_inverse=True)
    revenue = grouped.totals(grouped.discounted(li, returned, num)[keep], inverse, len(which), num)
    # equal revenue: by the other output columns, c_custkey first (it is unique)
    order = np.lexsort((cust["c_custkey"][which], -revenue))
    rows = []
    for g in order[:FIRST + 1]:
        c = which[g]
        key = int(cust["c_custkey"][c])
        rows.append([
            key, f"Customer#{key:09d}", ref.dec(revenue[g], 4), ref.dec(cust["c_acctbal"][c], 2),
            _NATION_NAMES[nation["n_name"][npos[c]]], population.COMMENT_POOL[cust["c_address"][c]],
            f"{10 + (key - 1) % 25}-{key:011d}", population.COMMENT_POOL[cust["c_comment"][c]],
        ])
    return revenue[order], rows


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    return _rows(host, p, num)[1][:FIRST]


def ties(host: dict, p: dict) -> bool:
    """Whether two rows kept, or the last kept and the first cut, tie on the
    specification's ORDER BY (revenue DESC)."""
    return grouped.adjacent_ties(FIRST, _rows(host, p, ref.EXACT)[0])
