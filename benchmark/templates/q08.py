"""TPC-H Q8, national market share (specification clause 2.4.8): the share of
one nation's suppliers in the revenue of one part type sold to a region, by
year of order."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped
from benchmark.templates import _wide as wide

SQL = """SELECT o_year,
       sum(CASE
             WHEN nation = '{nation}'
             THEN volume
             ELSE 0
           END) / sum(volume) AS mkt_share
FROM (
        SELECT extract(year FROM o_orderdate) AS o_year,
               l_extendedprice * (1 - l_discount) AS volume,
               n2.n_name AS nation
        FROM {schema}.part, {schema}.supplier, {schema}.lineitem, {schema}.orders,
             {schema}.customer, {schema}.nation n1, {schema}.nation n2, {schema}.region
        WHERE p_partkey = l_partkey
          AND s_suppkey = l_suppkey
          AND l_orderkey = o_orderkey
          AND o_custkey = c_custkey
          AND c_nationkey = n1.n_nationkey
          AND n1.n_regionkey = r_regionkey
          AND r_name = '{region}'
          AND s_nationkey = n2.n_nationkey
          AND o_orderdate BETWEEN DATE '1995-01-01' AND DATE '1996-12-31'
          AND p_type = '{type}') AS all_nations
GROUP BY o_year
ORDER BY o_year"""

# clause 2.4.8.3: NATION is one of the 25, REGION the region of NATION
# (`literals` derives it) and TYPE one of the 150 types; the validation tuple
# of cl. 2.4.8.4 (BRAZIL, AMERICA, ECONOMY ANODIZED STEEL) first
DOMAIN = {"nation": ["BRAZIL"] + [n for n in wide.NATION_NAMES if n != "BRAZIL"],
          "type": ["ECONOMY ANODIZED STEEL"]
          + [t for t in population.PART_TYPES if t != "ECONOMY ANODIZED STEEL"]}
COLUMNS = {
    "part": ["p_partkey", "p_type"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_extendedprice", "l_discount"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "customer": ["c_custkey", "c_nationkey"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
}


def literals(p: dict) -> dict:
    return {"nation": p["nation"], "region": wide.region_of(p["nation"]), "type": p["type"]}


def _groups(host: dict, p: dict, num: ref.Arith):
    """(year, the nation's revenue units, all revenue units) of every group,
    in the answer's order."""
    part, supp, li = host["part"], host["supplier"], host["lineitem"]
    orders, cust = host["orders"], host["customer"]
    ppos, pfound = ref.lookup(part["p_partkey"], li["l_partkey"])
    rows = np.flatnonzero(pfound & (part["p_type"][ppos] == population.PART_TYPES.index(p["type"])))
    opos, ofound = ref.lookup(orders["o_orderkey"], li["l_orderkey"][rows])
    odate = orders["o_orderdate"][opos]
    cpos, cfound = ref.lookup(cust["c_custkey"], orders["o_custkey"][opos])
    in_region = np.isin(cust["c_nationkey"][cpos], wide.nations_of_region(host, wide.region_of(p["nation"])))
    spos, sfound = ref.lookup(supp["s_suppkey"], li["l_suppkey"][rows])
    keep = (ofound & (odate >= ref.days("1995-01-01")) & (odate <= ref.days("1996-12-31"))
            & cfound & in_region & sfound)
    ours = supp["s_nationkey"][spos[keep]] == wide.nation_key(host, p["nation"])
    years, inverse = np.unique(wide.year(odate[keep]), return_inverse=True)
    volume = grouped.discounted(li, rows[keep], num)
    nation = grouped.totals(volume * ours, inverse, len(years), num)
    total = grouped.totals(volume, inverse, len(years), num)
    return years, nation, total


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    years, nation, total = _groups(host, p, num)
    # the engine's answer is a double: the two decimal sums cast, then divided
    return [[int(y), (int(n) / 1e4) / (int(t) / 1e4)] for y, n, t in zip(years, nation, total)]


def ties(host: dict, p: dict) -> bool:
    """The ORDER BY is the grouping key: no two rows can tie. Evaluated all
    the same, as the join templates' are."""
    years, _, _ = _groups(host, p, ref.EXACT)
    return grouped.adjacent_ties(len(years), years)
