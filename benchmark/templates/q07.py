"""TPC-H Q7, volume shipping (specification clause 2.4.7): the value of the
goods two nations shipped each other, by supplier nation, customer nation and
year of shipment."""

import numpy as np

from benchmark import reference as ref
from benchmark.templates import _grouped as grouped
from benchmark.templates import _wide as wide

SQL = """SELECT supp_nation,
       cust_nation,
       l_year,
       sum(volume) AS revenue
FROM (
        SELECT n1.n_name AS supp_nation,
               n2.n_name AS cust_nation,
               extract(year FROM l_shipdate) AS l_year,
               l_extendedprice * (1 - l_discount) AS volume
        FROM {schema}.supplier, {schema}.lineitem, {schema}.orders,
             {schema}.customer, {schema}.nation n1, {schema}.nation n2
        WHERE s_suppkey = l_suppkey
          AND o_orderkey = l_orderkey
          AND c_custkey = o_custkey
          AND s_nationkey = n1.n_nationkey
          AND c_nationkey = n2.n_nationkey
          AND ((n1.n_name = '{nation1}' AND n2.n_name = '{nation2}')
            OR (n1.n_name = '{nation2}' AND n2.n_name = '{nation1}'))
          AND l_shipdate BETWEEN DATE '1995-01-01' AND DATE '1996-12-31') AS shipping
GROUP BY supp_nation, cust_nation, l_year
ORDER BY supp_nation, cust_nation, l_year"""

# clause 2.4.7.3: NATION1 and NATION2 are two different nations of the 25. The
# harness's domain is a product of lists, so the ordered pairs are written
# out: the validation pair of cl. 2.4.7.4 first, then the other 599
_VALIDATION = ["FRANCE", "GERMANY"]
DOMAIN = {"nations": [_VALIDATION] + [[a, b] for a in wide.NATION_NAMES for b in wide.NATION_NAMES
                                      if a != b and [a, b] != _VALIDATION]}
COLUMNS = {
    "supplier": ["s_suppkey", "s_nationkey"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_shipdate", "l_extendedprice", "l_discount"],
    "orders": ["o_orderkey", "o_custkey"],
    "customer": ["c_custkey", "c_nationkey"],
    "nation": ["n_nationkey", "n_name"],
}


def literals(p: dict) -> dict:
    return {"nation1": p["nations"][0], "nation2": p["nations"][1]}


def _groups(host: dict, p: dict, num: ref.Arith):
    """(group key, revenue units) of every group in the answer's order; the
    key is (supplier nation code * 25 + customer nation code) * 10000 + year,
    codes ordering as the names do."""
    supp, li, orders, cust = host["supplier"], host["lineitem"], host["orders"], host["customer"]
    first, second = p["nations"]
    k1, k2 = wide.nation_key(host, first), wide.nation_key(host, second)
    ship = li["l_shipdate"]
    rows = np.flatnonzero((ship >= ref.days("1995-01-01")) & (ship <= ref.days("1996-12-31")))
    spos, sfound = ref.lookup(supp["s_suppkey"], li["l_suppkey"][rows])
    s_nation = supp["s_nationkey"][spos]
    opos, ofound = ref.lookup(orders["o_orderkey"], li["l_orderkey"][rows])
    cpos, cfound = ref.lookup(cust["c_custkey"], orders["o_custkey"][opos])
    c_nation = cust["c_nationkey"][cpos]
    keep = (sfound & ofound & cfound
            & (((s_nation == k1) & (c_nation == k2)) | ((s_nation == k2) & (c_nation == k1))))
    code = {k1: wide.NATION_NAMES.index(first), k2: wide.NATION_NAMES.index(second)}
    s_code = np.where(s_nation[keep] == k1, code[k1], code[k2])
    c_code = np.where(c_nation[keep] == k1, code[k1], code[k2])
    key = (s_code.astype(np.int64) * 25 + c_code) * 10000 + wide.year(ship[rows[keep]])
    groups, inverse = np.unique(key, return_inverse=True)
    revenue = grouped.totals(grouped.discounted(li, rows[keep], num), inverse, len(groups), num)
    return groups, revenue


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    groups, revenue = _groups(host, p, num)
    out = []
    for key, units in zip(groups, revenue):
        nations, year = divmod(int(key), 10000)
        s_code, c_code = divmod(nations, 25)
        out.append([wide.NATION_NAMES[s_code], wide.NATION_NAMES[c_code], year, ref.dec(units, 4)])
    return out


def ties(host: dict, p: dict) -> bool:
    """The ORDER BY is the grouping's three keys: no two rows can tie.
    Evaluated all the same, as the join templates' are."""
    groups, _ = _groups(host, p, ref.EXACT)
    return grouped.adjacent_ties(len(groups), groups)
