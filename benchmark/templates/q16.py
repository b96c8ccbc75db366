"""TPC-H Q16, parts/supplier relationship (specification clause 2.4.16): how
many suppliers can supply parts of given sizes that are not of one brand or
type, leaving out the suppliers customers complained about."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT p_brand,
       p_type,
       p_size,
       count(DISTINCT ps_suppkey) AS supplier_cnt
FROM {schema}.partsupp, {schema}.part
WHERE p_partkey = ps_partkey
  AND p_brand <> '{brand}'
  AND p_type NOT LIKE '{type}%'
  AND p_size IN ({s1}, {s2}, {s3}, {s4}, {s5}, {s6}, {s7}, {s8})
  AND ps_suppkey NOT IN (
        SELECT s_suppkey
        FROM {schema}.supplier
        WHERE s_comment LIKE '%Customer%Complaints%')
GROUP BY p_brand, p_type, p_size
ORDER BY supplier_cnt DESC, p_brand, p_type, p_size"""

# clause 2.4.16.3: BRAND = Brand#MN with M and N in [1, 5]; TYPE the first two
# syllables of a type; SIZE eight different values of [1, 50]. The harness's
# domain is a product of lists, so the eight-size tuples are written out: the
# validation tuple of cl. 2.4.16.4 first, then 23 drawn once
# (random.Random(16).sample(range(1, 51), 8)) and fixed here
DOMAIN = {"m": [1, 2, 3, 4, 5], "n": [1, 2, 3, 4, 5],
          "syllable1": list(population.TYPE_SYLL1), "syllable2": list(population.TYPE_SYLL2),
          "sizes": [
    [49, 14, 23, 45, 19, 3, 36, 9], [24, 31, 49, 19, 27, 15, 29, 1], [27, 43, 46, 17, 16, 41, 15, 1],
    [19, 20, 22, 43, 10, 39, 49, 2], [15, 39, 17, 2, 10, 49, 43, 41], [2, 30, 49, 39, 41, 19, 15, 20],
    [24, 17, 27, 6, 23, 32, 28, 34], [42, 12, 37, 19, 38, 3, 47, 6], [1, 34, 24, 16, 32, 10, 20, 44],
    [21, 30, 49, 5, 11, 45, 31, 1], [29, 32, 1, 31, 45, 8, 30, 40], [6, 32, 42, 2, 10, 15, 26, 24],
    [3, 35, 50, 43, 42, 26, 39, 21], [31, 33, 43, 44, 42, 5, 15, 21], [7, 46, 6, 35, 8, 16, 1, 26],
    [41, 3, 8, 44, 4, 26, 10, 50], [45, 17, 16, 12, 38, 1, 48, 34], [16, 8, 7, 42, 10, 18, 25, 27],
    [3, 26, 30, 47, 48, 46, 17, 50], [4, 2, 46, 13, 28, 43, 17, 33], [25, 7, 14, 8, 27, 41, 49, 19],
    [8, 30, 28, 10, 26, 16, 9, 44], [26, 28, 33, 15, 50, 12, 24, 43], [32, 12, 27, 14, 43, 28, 2, 50],
]}
COLUMNS = {
    "partsupp": ["ps_partkey", "ps_suppkey"],
    "part": ["p_partkey", "p_brand", "p_type", "p_size"],
    "supplier": ["s_suppkey", "s_comment"],
}


def literals(p: dict) -> dict:
    return {"brand": f"Brand#{p['m']}{p['n']}", "type": f"{p['syllable1']} {p['syllable2']}",
            **{f"s{i + 1}": size for i, size in enumerate(p["sizes"])}}


def complained(text: str) -> bool:
    """`text LIKE '%Customer%Complaints%'`."""
    at = text.find("Customer")
    return at >= 0 and text.find("Complaints", at + len("Customer")) >= 0


def _groups(host: dict, p: dict):
    """(brand code, type code, size, supplier count) of every group, in the
    answer's order; codes order as the strings do."""
    ps, part, supp = host["partsupp"], host["part"], host["supplier"]
    lit = literals(p)
    liked = np.array([complained(c) for c in population.COMMENT_POOL])
    excluded = supp["s_suppkey"][liked[supp["s_comment"]]]
    typed = np.array([t.startswith(lit["type"]) for t in population.PART_TYPES])
    chosen = ((part["p_brand"] != population.BRANDS.index(lit["brand"])) & ~typed[part["p_type"]]
              & np.isin(part["p_size"], p["sizes"]))
    pos, found = ref.lookup(part["p_partkey"], ps["ps_partkey"])
    keep = found & chosen[pos] & ~np.isin(ps["ps_suppkey"], excluded)
    at = pos[keep]
    # the group (brand, type, size) as one number, then each (group, supplier) once
    group = (part["p_brand"][at].astype(np.int64) * len(population.PART_TYPES)
             + part["p_type"][at]) * 51 + part["p_size"][at]
    base = int(ps["ps_suppkey"].max(initial=0)) + 1
    pairs = np.unique(group * base + ps["ps_suppkey"][keep])
    groups, count = np.unique(pairs // base, return_counts=True)
    order = np.lexsort((groups, -count))              # ascending group = brand, type, size
    groups, count = groups[order], count[order]
    brand_type, size = np.divmod(groups, 51)
    brand, kind = np.divmod(brand_type, len(population.PART_TYPES))
    return brand, kind, size, count


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    brand, kind, size, count = _groups(host, p)
    return [[population.BRANDS[b], population.PART_TYPES[t], int(s), int(c)]
            for b, t, s, c in zip(brand, kind, size, count)]


def ties(host: dict, p: dict) -> bool:
    """The ORDER BY ends in the grouping's three keys: no two rows can tie.
    Evaluated all the same, as the join templates' are."""
    brand, kind, size, count = _groups(host, p)
    return grouped.adjacent_ties(len(count), count, brand, kind, size)
