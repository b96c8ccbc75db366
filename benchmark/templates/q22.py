"""TPC-H Q22, global sales opportunity (specification clause 2.4.22): customers
of seven country codes with more than the average positive balance who have
placed no order."""

import numpy as np

from benchmark import reference as ref

SQL = """SELECT cntrycode,
       count(*) AS numcust,
       sum(c_acctbal) AS totacctbal
FROM (
        SELECT substring(c_phone from 1 for 2) AS cntrycode,
               c_acctbal
        FROM {schema}.customer
        WHERE substring(c_phone from 1 for 2) IN
                ('{i1}', '{i2}', '{i3}', '{i4}', '{i5}', '{i6}', '{i7}')
          AND c_acctbal > (
                SELECT avg(c_acctbal)
                FROM {schema}.customer
                WHERE c_acctbal > 0.00
                  AND substring(c_phone from 1 for 2) IN
                        ('{i1}', '{i2}', '{i3}', '{i4}', '{i5}', '{i6}', '{i7}'))
          AND NOT EXISTS (
                SELECT *
                FROM {schema}.orders
                WHERE o_custkey = c_custkey)
     ) AS custsale
GROUP BY cntrycode
ORDER BY cntrycode"""

# clause 2.4.22.3: I1 to I7 are seven different country codes of [10, 34]. The
# harness's domain is a product of lists, so the seven-code tuples are written
# out: the validation tuple of cl. 2.4.22.4 first, then 23 drawn once
# (random.Random(22).sample(range(10, 35), 7)) and fixed here
DOMAIN = {"codes": [
    [13, 31, 23, 29, 30, 18, 17], [14, 17, 10, 29, 24, 15, 13], [33, 30, 21, 12, 17, 18, 11],
    [20, 29, 15, 27, 23, 11, 28], [10, 28, 18, 19, 23, 16, 15], [13, 28, 26, 33, 11, 20, 29],
    [18, 15, 22, 19, 30, 26, 14], [18, 34, 31, 15, 23, 11, 20], [27, 11, 23, 18, 26, 19, 30],
    [23, 22, 31, 15, 19, 28, 21], [22, 28, 10, 19, 33, 29, 27], [27, 26, 23, 31, 33, 32, 12],
    [12, 24, 21, 13, 23, 22, 11], [16, 26, 17, 22, 10, 13, 24], [16, 15, 10, 27, 22, 21, 33],
    [20, 21, 30, 16, 24, 11, 12], [27, 14, 30, 16, 32, 13, 11], [16, 23, 13, 14, 28, 24, 11],
    [13, 25, 32, 26, 14, 28, 19], [31, 33, 25, 32, 12, 21, 13], [29, 18, 13, 10, 15, 26, 21],
    [30, 12, 20, 13, 15, 29, 23], [32, 18, 15, 11, 12, 16, 21], [31, 13, 16, 27, 18, 25, 12],
]}
COLUMNS = {"customer": ["c_custkey", "c_acctbal"], "orders": ["o_custkey"]}


def literals(p: dict) -> dict:
    return {f"i{i + 1}": code for i, code in enumerate(p["codes"])}


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    cust, orders = host["customer"], host["orders"]
    # c_phone is '<10 + (c_custkey - 1) % 25>-...' (cl. 4.2.2.9): its first two characters
    code = 10 + (cust["c_custkey"] - 1) % 25
    listed = np.isin(code, p["codes"])
    balance = cust["c_acctbal"]
    positive = listed & (balance > 0)
    if not positive.any():
        return []
    # avg of a decimal(12,2) is a decimal(12,2), rounded half up
    average = ref.dec_avg(num.total(num.lift(balance[positive])), int(positive.sum()))
    keep = listed & (balance > average) & ~np.isin(cust["c_custkey"], orders["o_custkey"])
    return [
        [str(c), int((keep & (code == c)).sum()), ref.dec(num.total(num.lift(balance[keep & (code == c)])), 2)]
        for c in sorted(p["codes"]) if (keep & (code == c)).any()
    ]
