"""TPC-H Q14, promotion effect (specification clause 2.4.14)."""

import numpy as np

from benchmark import population
from benchmark import reference as ref

SQL = """SELECT 100.00 * sum(CASE WHEN p_type LIKE 'PROMO%'
                         THEN l_extendedprice * (1 - l_discount)
                         ELSE 0 END)
       / sum(l_extendedprice * (1 - l_discount)) AS promo_revenue
FROM {schema}.lineitem, {schema}.part
WHERE l_partkey = p_partkey
  AND l_shipdate >= DATE '{date}'
  AND l_shipdate < DATE '{date}' + INTERVAL '1' MONTH"""

# clause 2.4.14.3: DATE is the first day of a month of a year in [1993, 1997]
DOMAIN = {"year": [1993, 1994, 1995, 1996, 1997], "month": list(range(1, 13))}
COLUMNS = {
    "lineitem": ["l_shipdate", "l_partkey", "l_extendedprice", "l_discount"],
    "part": ["p_partkey", "p_type"],
}

_PROMO = np.array([v.startswith("PROMO") for v in population.PART_TYPES])


def literals(p: dict) -> dict:
    return {"date": f"{p['year']}-{p['month']:02d}-01"}


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    li, part = host["lineitem"], host["part"]
    first = f"{p['year']}-{p['month']:02d}-01"
    m = (li["l_shipdate"] >= ref.days(first)) & (
        li["l_shipdate"] < ref.days(ref.add_months(first, 1))
    )
    pos, found = ref.lookup(part["p_partkey"], li["l_partkey"][m])
    revenue = (num.lift(li["l_extendedprice"][m]) * num.lift(100 - li["l_discount"][m]))[found]
    promo = _PROMO[part["p_type"][pos[found]]]
    total = num.total(revenue)
    if total == 0:
        return [[None]]
    # the engine's answer is a double: 100.00 * decimal sum / decimal sum
    return [[100.0 * num.total(revenue[promo]) / total]]
