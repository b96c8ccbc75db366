"""TPC-H Q2, minimum cost supplier (specification clause 2.4.2): for the parts
of one size and type, the suppliers of a region who offer each at the
region's lowest cost."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT s_acctbal,
       s_name,
       n_name,
       p_partkey,
       p_mfgr,
       s_address,
       s_phone,
       s_comment
FROM {schema}.part, {schema}.supplier, {schema}.partsupp, {schema}.nation, {schema}.region
WHERE p_partkey = ps_partkey
  AND s_suppkey = ps_suppkey
  AND p_size = {size}
  AND p_type LIKE '%{type}'
  AND s_nationkey = n_nationkey
  AND n_regionkey = r_regionkey
  AND r_name = '{region}'
  AND ps_supplycost = (
        SELECT min(ps_supplycost)
        FROM {schema}.partsupp, {schema}.supplier, {schema}.nation, {schema}.region
        WHERE p_partkey = ps_partkey
          AND s_suppkey = ps_suppkey
          AND s_nationkey = n_nationkey
          AND n_regionkey = r_regionkey
          AND r_name = '{region}')
ORDER BY s_acctbal DESC, n_name, s_name, p_partkey
LIMIT 100"""

# clause 2.4.2.3: SIZE in [1, 50], TYPE one of the five third syllables,
# REGION one of the five regions
DOMAIN = {"size": list(range(1, 51)), "type": ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"],
          "region": list(population.REGIONS)}
COLUMNS = {
    "part": ["p_partkey", "p_size", "p_type", "p_mfgr"],
    "supplier": ["s_suppkey", "s_nationkey", "s_acctbal", "s_address", "s_comment"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
}
FIRST = 100
_NATION_NAMES = sorted(n for n, _ in population.NATIONS)


def literals(p: dict) -> dict:
    return {"size": p["size"], "type": p["type"], "region": p["region"]}


def _rows(host: dict, p: dict):
    """(s_acctbal, nation name, supplier key, part key, supplier position,
    part position) of every row, in the answer's order. Nothing is summed:
    the float32 control answers as the reference does."""
    part, supp, ps = host["part"], host["supplier"], host["partsupp"]
    nation, region = host["nation"], host["region"]
    rpos, rfound = ref.lookup(region["r_regionkey"], nation["n_regionkey"])
    nation_in = rfound & (region["r_name"][rpos] == population.REGIONS.index(p["region"]))
    npos, nfound = ref.lookup(nation["n_nationkey"], supp["s_nationkey"])
    supp_in = nfound & nation_in[npos]
    spos, sfound = ref.lookup(supp["s_suppkey"], ps["ps_suppkey"])
    keep = np.flatnonzero(sfound & supp_in[spos])     # partsupp rows of the region's suppliers
    ppos, pfound = ref.lookup(part["p_partkey"], ps["ps_partkey"][keep])
    cost = ps["ps_supplycost"][keep]
    lowest = np.full(len(part["p_partkey"]), np.iinfo(np.int64).max)
    np.minimum.at(lowest, ppos[pfound], cost[pfound])  # the sub-query: min by part over the region
    typed = np.array([t.endswith(p["type"]) for t in population.PART_TYPES])
    chosen = (part["p_size"] == p["size"]) & typed[part["p_type"]]
    at = pfound & chosen[ppos] & (cost == lowest[ppos])
    sp, pp = spos[keep][at], ppos[at]
    balance = supp["s_acctbal"][sp]
    nation_name = nation["n_name"][npos[sp]]        # codes order as the names do
    skey, pkey = supp["s_suppkey"][sp], part["p_partkey"][pp]
    # s_name is Supplier#<key>: it orders as s_suppkey does
    order = np.lexsort((pkey, skey, nation_name, -balance))
    return balance[order], nation_name[order], skey[order], pkey[order], sp[order], pp[order]


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    part, supp = host["part"], host["supplier"]
    balance, nation_name, skey, pkey, sp, pp = _rows(host, p)
    return [
        [ref.dec(balance[i], 2), f"Supplier#{int(skey[i]):09d}", _NATION_NAMES[nation_name[i]],
         int(pkey[i]), population.MFGRS[part["p_mfgr"][pp[i]]],
         population.COMMENT_POOL[supp["s_address"][sp[i]]],
         f"{10 + (int(skey[i]) - 1) % 25}-{int(skey[i]):011d}",
         population.COMMENT_POOL[supp["s_comment"][sp[i]]]]
        for i in range(min(FIRST, len(skey)))
    ]


def ties(host: dict, p: dict) -> bool:
    """Whether two rows kept, or the last kept and the first cut, tie on the
    specification's ORDER BY (s_acctbal DESC, n_name, s_name, p_partkey): only
    a (part, supplier) pair that partsupp holds twice at the same cost can."""
    balance, nation_name, skey, pkey, _, _ = _rows(host, p)
    return grouped.adjacent_ties(FIRST, balance, nation_name, skey, pkey)
