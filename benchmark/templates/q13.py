"""TPC-H Q13, customer distribution (specification clause 2.4.13): customers
by the number of orders they placed, those who placed none among them."""

import numpy as np

from benchmark import population
from benchmark import reference as ref
from benchmark.templates import _grouped as grouped

SQL = """SELECT c_count,
       count(*) AS custdist
FROM (
        SELECT c_custkey,
               count(o_orderkey)
        FROM {schema}.customer LEFT OUTER JOIN {schema}.orders ON
                c_custkey = o_custkey
                AND o_comment NOT LIKE '%{word1}%{word2}%'
        GROUP BY c_custkey
     ) AS c_orders (c_custkey, c_count)
GROUP BY c_count
ORDER BY custdist DESC, c_count DESC"""

# clause 2.4.13.3: WORD1 in {special, pending, unusual, express}, WORD2 in
# {packages, requests, accounts, deposits}
DOMAIN = {"word1": ["special", "pending", "unusual", "express"],
          "word2": ["packages", "requests", "accounts", "deposits"]}
COLUMNS = {"customer": ["c_custkey"], "orders": ["o_custkey", "o_comment"]}


def literals(p: dict) -> dict:
    return {"word1": p["word1"], "word2": p["word2"]}


def like(text: str, word1: str, word2: str) -> bool:
    """`text LIKE '%word1%word2%'`: word1, and word2 somewhere after it."""
    at = text.find(word1)
    return at >= 0 and text.find(word2, at + len(word1)) >= 0


def _groups(host: dict, p: dict):
    """(c_count, custdist) of every group, in the answer's order."""
    cust, orders = host["customer"], host["orders"]
    # the population's comments are a pool of strings: LIKE is decided on the pool
    liked = np.array([like(c, p["word1"], p["word2"]) for c in population.COMMENT_POOL])
    kept = ~liked[orders["o_comment"]]
    pos, found = ref.lookup(cust["c_custkey"], orders["o_custkey"][kept])
    c_count = np.bincount(pos[found], minlength=len(cust["c_custkey"]))  # no kept order: 0
    custdist = np.bincount(c_count)
    counts = np.flatnonzero(custdist)
    order = np.lexsort((-counts, -custdist[counts]))
    return counts[order], custdist[counts][order]


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    c_count, custdist = _groups(host, p)
    return [[int(c), int(d)] for c, d in zip(c_count, custdist)]


def ties(host: dict, p: dict) -> bool:
    """Whether two rows tie on the specification's ORDER BY (custdist DESC,
    c_count DESC); c_count is the grouping's key, so none can."""
    c_count, custdist = _groups(host, p)
    return grouped.adjacent_ties(len(c_count), custdist, c_count)
