"""TPC-H Q5, local supplier volume (specification clause 2.4.5): a year's
revenue from lineitems whose customer and supplier share a nation of a region."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT n_name,
       sum(l_extendedprice * (1 - l_discount)) AS revenue
FROM {schema}.customer, {schema}.orders, {schema}.lineitem,
     {schema}.supplier, {schema}.nation, {schema}.region
WHERE c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND l_suppkey = s_suppkey
  AND c_nationkey = s_nationkey
  AND s_nationkey = n_nationkey
  AND n_regionkey = r_regionkey
  AND r_name = '{region}'
  AND o_orderdate >= DATE '{date}'
  AND o_orderdate < DATE '{date}' + INTERVAL '1' YEAR
GROUP BY n_name
ORDER BY revenue DESC"""

# clause 2.4.5.3: REGION is one of the five regions, DATE the first of
# January of a year in [1993, 1997]
DOMAIN = {"region": list(population.REGIONS), "year": [1993, 1994, 1995, 1996, 1997]}
COLUMNS = {
    "customer": ["c_custkey", "c_nationkey"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
}
_NATION_NAMES = sorted(n for n, _ in population.NATIONS)


def literals(p: dict) -> dict:
    return {"region": p["region"], "date": f"{p['year']}-01-01"}


def _groups(host: dict, p: dict, num: ref.Arith):
    """(revenue units, nation name) of every group, in the answer's order."""
    cust, orders, li = host["customer"], host["orders"], host["lineitem"]
    supp, nation, region = host["supplier"], host["nation"], host["region"]
    rpos, rfound = ref.lookup(region["r_regionkey"], nation["n_regionkey"])
    in_region = rfound & (region["r_name"][rpos] == population.REGIONS.index(p["region"]))
    odate = orders["o_orderdate"]
    in_year = (odate >= ref.days(f"{p['year']}-01-01")) & (odate < ref.days(f"{p['year'] + 1}-01-01"))
    cpos, cfound = ref.lookup(cust["c_custkey"], orders["o_custkey"])
    order_nation = np.where(cfound & in_year, cust["c_nationkey"][cpos], -1)
    opos, ofound = ref.lookup(orders["o_orderkey"], li["l_orderkey"])
    spos, sfound = ref.lookup(supp["s_suppkey"], li["l_suppkey"])
    s_nation = supp["s_nationkey"][spos]
    npos, nfound = ref.lookup(nation["n_nationkey"], s_nation)
    keep = ofound & sfound & nfound & (order_nation[opos] == s_nation) & in_region[npos]
    which, inverse = np.unique(npos[keep], return_inverse=True)
    revenue = grouped.totals(grouped.discounted(li, keep, num), inverse, len(which), num)
    names = np.array([_NATION_NAMES[c] for c in nation["n_name"][which]], dtype=object)
    order = sorted(range(len(which)), key=lambda i: (-int(revenue[i]), names[i]))
    return revenue[order], names[order]


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    revenue, names = _groups(host, p, num)
    return [[names[i], ref.dec(revenue[i], 4)] for i in range(len(names))]


def ties(host: dict, p: dict) -> bool:
    """Whether two rows tie on the specification's ORDER BY (revenue DESC)."""
    revenue, _ = _groups(host, p, ref.EXACT)
    return grouped.adjacent_ties(len(revenue), revenue)
