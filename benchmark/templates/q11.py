"""TPC-H Q11, important stock identification (specification clause 2.4.11):
the parts whose stock held by one nation's suppliers is worth more than a
fraction of that nation's whole stock."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT ps_partkey,
       sum(ps_supplycost * ps_availqty) AS value
FROM {schema}.partsupp, {schema}.supplier, {schema}.nation
WHERE ps_suppkey = s_suppkey
  AND s_nationkey = n_nationkey
  AND n_name = '{nation}'
GROUP BY ps_partkey HAVING
        sum(ps_supplycost * ps_availqty) > (
                SELECT sum(ps_supplycost * ps_availqty) * {fraction}
                FROM {schema}.partsupp, {schema}.supplier, {schema}.nation
                WHERE ps_suppkey = s_suppkey
                  AND s_nationkey = n_nationkey
                  AND n_name = '{nation}')
ORDER BY value DESC"""

# clause 2.4.11.3: NATION one of the 25 names; FRACTION is 0.0001 / SF, here
# the configuration's SF 3 written to ten decimals. A nation whose answer ties
# on the ORDER BY at SF 3 is left out (the template's `ties`, evaluated once
# over the 25 at SF 3: PERF.md, PR 40)
TIED_AT_SF3 = ["EGYPT", "ROMANIA", "RUSSIA"]
DOMAIN = {"nation": [name for name, _ in population.NATIONS if name not in TIED_AT_SF3]}
FRACTION = "0.0000333333"
COLUMNS = {
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_availqty", "ps_supplycost"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
}
_NATION_NAMES = sorted(n for n, _ in population.NATIONS)


def literals(p: dict) -> dict:
    return {"nation": p["nation"], "fraction": FRACTION}


def _groups(host: dict, p: dict, num: ref.Arith):
    """(part key, value in cents) of every row, in the answer's order."""
    ps, supp, nation = host["partsupp"], host["supplier"], host["nation"]
    mine = nation["n_nationkey"][nation["n_name"] == _NATION_NAMES.index(p["nation"])]
    spos, sfound = ref.lookup(supp["s_suppkey"], ps["ps_suppkey"])
    keep = sfound & np.isin(supp["s_nationkey"][spos], mine)
    units = num.lift(ps["ps_supplycost"][keep]) * num.lift(ps["ps_availqty"][keep])
    which, inverse = np.unique(ps["ps_partkey"][keep], return_inverse=True)
    value = grouped.totals(units, inverse, len(which), num)
    # value > total * FRACTION, exactly at the literal's scale: value * 10^s > total * digits,
    # which for an integer value is value > floor(total * digits / 10^s)
    digits, scale = int(FRACTION.replace(".", "")), len(FRACTION.split(".")[1])
    floor = num.total(units) * digits // 10 ** scale
    big = np.flatnonzero(value > floor)
    order = big[np.argsort(-value[big], kind="stable")]
    return which[order], value[order]


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    partkey, value = _groups(host, p, num)
    return [[int(k), ref.dec(v, 2)] for k, v in zip(partkey, value)]


def ties(host: dict, p: dict) -> bool:
    """Whether two rows tie on the specification's ORDER BY (value DESC)."""
    _, value = _groups(host, p, ref.EXACT)
    return grouped.adjacent_ties(len(value), value)
