"""TPC-H Q18, large volume customer (specification clause 2.4.18): the
hundred largest orders among those whose lines add up to more than a quantity."""

import numpy as np

from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT c_name,
       c_custkey,
       o_orderkey,
       o_orderdate,
       o_totalprice,
       sum(l_quantity)
FROM {schema}.customer, {schema}.orders, {schema}.lineitem
WHERE o_orderkey IN (
        SELECT l_orderkey
        FROM {schema}.lineitem
        GROUP BY l_orderkey
        HAVING sum(l_quantity) > {quantity})
  AND c_custkey = o_custkey
  AND o_orderkey = l_orderkey
GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
ORDER BY o_totalprice DESC, o_orderdate
LIMIT 100"""

# clause 2.4.18.3: QUANTITY in [312, 315]
DOMAIN = {"quantity": [312, 313, 314, 315]}
COLUMNS = {
    "customer": ["c_custkey", "c_name"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "lineitem": ["l_orderkey", "l_quantity"],
}
FIRST = 100


def literals(p: dict) -> dict:
    return {"quantity": p["quantity"]}


def _groups(host: dict, p: dict, num: ref.Arith):
    """(total price, order date, order key, customer key, quantity units) of
    every order kept, in the answer's order."""
    cust, orders, li = host["customer"], host["orders"], host["lineitem"]
    opos, ofound = ref.lookup(orders["o_orderkey"], li["l_orderkey"])
    quantity = grouped.totals(li["l_quantity"][ofound], opos[ofound], len(orders["o_orderkey"]), num)
    _, cfound = ref.lookup(cust["c_custkey"], orders["o_custkey"])
    large = np.flatnonzero((quantity > 100 * p["quantity"]) & cfound)
    price, odate = orders["o_totalprice"][large], orders["o_orderdate"][large]
    okey, ckey = orders["o_orderkey"][large], orders["o_custkey"][large]
    # equal (o_totalprice, o_orderdate): by c_name (which orders as c_custkey does), then o_orderkey
    order = np.lexsort((okey, ckey, odate, -price))
    return price[order], odate[order], okey[order], ckey[order], quantity[large][order]


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    price, odate, okey, ckey, quantity = _groups(host, p, num)
    return [
        [f"Customer#{int(ckey[i]):09d}", int(ckey[i]), int(okey[i]), grouped.iso(odate[i]),
         ref.dec(price[i], 2), ref.dec(quantity[i], 2)]
        for i in range(min(FIRST, len(okey)))
    ]


def ties(host: dict, p: dict) -> bool:
    """Whether two rows kept, or the last kept and the first cut, tie on the
    specification's ORDER BY (o_totalprice DESC, o_orderdate)."""
    price, odate, _, _, _ = _groups(host, p, ref.EXACT)
    return grouped.adjacent_ties(FIRST, price, odate)
