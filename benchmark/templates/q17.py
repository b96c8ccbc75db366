"""TPC-H Q17, small-quantity-order revenue (specification clause 2.4.17): the
yearly revenue lost if orders under a fifth of a part's average quantity were
no longer taken, for the parts of one brand and container."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT sum(l_extendedprice) / 7.0 AS avg_yearly
FROM {schema}.lineitem, {schema}.part
WHERE p_partkey = l_partkey
  AND p_brand = '{brand}'
  AND p_container = '{container}'
  AND l_quantity < (
        SELECT 0.2 * avg(l_quantity)
        FROM {schema}.lineitem
        WHERE l_partkey = p_partkey)"""

# clause 2.4.17.3: BRAND = Brand#MN with M and N in [1, 5]; CONTAINER one of the
# 40 two-syllable containers
DOMAIN = {"m": [1, 2, 3, 4, 5], "n": [1, 2, 3, 4, 5],
          "syllable1": ["SM", "LG", "MED", "JUMBO", "WRAP"],
          "syllable2": ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]}
COLUMNS = {
    "lineitem": ["l_partkey", "l_quantity", "l_extendedprice"],
    "part": ["p_partkey", "p_brand", "p_container"],
}


def literals(p: dict) -> dict:
    return {"brand": f"Brand#{p['m']}{p['n']}", "container": f"{p['syllable1']} {p['syllable2']}"}


def small_lines(quantity: np.ndarray, part: np.ndarray, parts: int, num: ref.Arith) -> np.ndarray:
    """Which lines hold less than 0.2 * avg(l_quantity) of their part
    (`part`: each line's position among `parts`). avg of a decimal(12,2) is a
    decimal(12,2): the exact quotient ROUNDED HALF UP to the cent
    (`ref.dec_avg`), and 0.2 times it has three decimals; the comparison is at
    that scale, units of 1e-3: quantity * 10 < 2 * avg."""
    total = grouped.totals(quantity, part, parts, num)
    count = np.bincount(part, minlength=parts)
    avg = (2 * total + count) // np.maximum(2 * count, 1)
    return quantity * 10 < 2 * avg[part]


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    li, part = host["lineitem"], host["part"]
    lit = literals(p)
    chosen = ((part["p_brand"] == population.BRANDS.index(lit["brand"]))
              & (part["p_container"] == population.CONTAINERS.index(lit["container"])))
    pos, found = ref.lookup(part["p_partkey"], li["l_partkey"])
    mine = np.flatnonzero(found & chosen[pos])  # the average is over the part's lines, all of them kept here
    small = small_lines(li["l_quantity"][mine], pos[mine], len(chosen), num)
    if not small.any():
        return [[None]]
    # the engine's answer is a double: decimal sum / 7.0
    return [[num.total(num.lift(li["l_extendedprice"][mine][small])) / 100.0 / 7.0]]
