"""TPC-H Q19, discounted revenue (specification clause 2.4.19): the revenue of
three classes of parts, each a brand, four containers, a size range and a
quantity range, shipped by air and delivered in person."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT sum(l_extendedprice * (1 - l_discount)) AS revenue
FROM {schema}.lineitem, {schema}.part
WHERE (
        p_partkey = l_partkey
        AND p_brand = '{brand1}'
        AND p_container IN ('SM CASE', 'SM BOX', 'SM PACK', 'SM PKG')
        AND l_quantity >= {quantity1} AND l_quantity <= {quantity1} + 10
        AND p_size BETWEEN 1 AND 5
        AND l_shipmode IN ('AIR', 'AIR REG')
        AND l_shipinstruct = 'DELIVER IN PERSON'
      )
   OR (
        p_partkey = l_partkey
        AND p_brand = '{brand2}'
        AND p_container IN ('MED BAG', 'MED BOX', 'MED PKG', 'MED PACK')
        AND l_quantity >= {quantity2} AND l_quantity <= {quantity2} + 10
        AND p_size BETWEEN 1 AND 10
        AND l_shipmode IN ('AIR', 'AIR REG')
        AND l_shipinstruct = 'DELIVER IN PERSON'
      )
   OR (
        p_partkey = l_partkey
        AND p_brand = '{brand3}'
        AND p_container IN ('LG CASE', 'LG BOX', 'LG PACK', 'LG PKG')
        AND l_quantity >= {quantity3} AND l_quantity <= {quantity3} + 10
        AND p_size BETWEEN 1 AND 15
        AND l_shipmode IN ('AIR', 'AIR REG')
        AND l_shipinstruct = 'DELIVER IN PERSON'
      )"""

# clause 2.4.19.3: QUANTITY1 in [1, 10], QUANTITY2 in [10, 20], QUANTITY3 in
# [20, 30], BRAND1 to BRAND3 each Brand#MN with M and N in [1, 5]; each list
# starts with the validation tuple's value (cl. 2.4.19.4: 1, 10, 20,
# Brand#12, Brand#23, Brand#34)


def _brands(first: str) -> list:
    return [first] + [b for b in population.BRANDS if b != first]


DOMAIN = {"quantity1": list(range(1, 11)), "quantity2": list(range(10, 21)),
          "quantity3": list(range(20, 31)),
          "brand1": _brands("Brand#12"), "brand2": _brands("Brand#23"), "brand3": _brands("Brand#34")}
COLUMNS = {
    "lineitem": ["l_partkey", "l_quantity", "l_extendedprice", "l_discount", "l_shipmode",
                 "l_shipinstruct"],
    "part": ["p_partkey", "p_brand", "p_container", "p_size"],
}
# (containers, largest size) of the three classes, as the text writes them
_CLASSES = [(("SM CASE", "SM BOX", "SM PACK", "SM PKG"), 5),
            (("MED BAG", "MED BOX", "MED PKG", "MED PACK"), 10),
            (("LG CASE", "LG BOX", "LG PACK", "LG PKG"), 15)]


def literals(p: dict) -> dict:
    return {k: p[k] for k in DOMAIN}


def _rows(host: dict, p: dict) -> np.ndarray:
    """Lineitem's rows in the sum. 'AIR REG' is no mode of the population
    (dbgen's is 'REG AIR'), so the IN keeps AIR alone."""
    li, part = host["lineitem"], host["part"]
    rows = np.flatnonzero((li["l_shipmode"] == population.SHIP_MODES.index("AIR"))
                          & (li["l_shipinstruct"] == population.SHIP_INSTRUCTS.index("DELIVER IN PERSON")))
    pos, found = ref.lookup(part["p_partkey"], li["l_partkey"][rows])
    brand, container, size = part["p_brand"][pos], part["p_container"][pos], part["p_size"][pos]
    quantity = li["l_quantity"][rows]           # cents
    hit = np.zeros(len(rows), dtype=bool)
    for i, (containers, largest) in enumerate(_CLASSES):
        least = p[f"quantity{i + 1}"] * 100
        hit |= ((brand == population.BRANDS.index(p[f"brand{i + 1}"]))
                & np.isin(container, [population.CONTAINERS.index(c) for c in containers])
                & (size >= 1) & (size <= largest)
                & (quantity >= least) & (quantity <= least + 1000))
    return rows[found & hit]


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    rows = _rows(host, p)
    if len(rows) == 0:
        return [[None]]        # a sum over no rows is NULL
    return [[ref.dec(num.total(grouped.discounted(host["lineitem"], rows, num)), 4)]]


def ties(host: dict, p: dict) -> bool:
    """The answer is one row: nothing can tie."""
    return False
