"""TPC-H Q1, pricing summary report (specification clause 2.4.1)."""

from benchmark import population
from benchmark import reference as ref

SQL = """SELECT l_returnflag, l_linestatus,
       sum(l_quantity) AS sum_qty,
       sum(l_extendedprice) AS sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       avg(l_quantity) AS avg_qty,
       avg(l_extendedprice) AS avg_price,
       avg(l_discount) AS avg_disc,
       count(*) AS count_order
FROM {schema}.lineitem
WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '{delta}' DAY
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus"""

# clause 2.4.1.3: DELTA in [60, 120]
DOMAIN = {"delta": list(range(60, 121))}
COLUMNS = {
    "lineitem": [
        "l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax",
    ]
}
SCANS = COLUMNS


def literals(p: dict) -> dict:
    return {"delta": p["delta"]}


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    li = host["lineitem"]
    keep = li["l_shipdate"] <= ref.days("1998-12-01") - p["delta"]
    rows = []
    for rf, rf_name in enumerate(population.RETURN_FLAGS):
        for ls, ls_name in enumerate(population.LINE_STATUS):
            m = keep & (li["l_returnflag"] == rf) & (li["l_linestatus"] == ls)
            n = int(m.sum())
            if n == 0:
                continue
            qty_units, price_units = num.lift(li["l_quantity"][m]), num.lift(li["l_extendedprice"][m])
            disc_price = price_units * num.lift(100 - li["l_discount"][m])
            charge = disc_price * num.lift(100 + li["l_tax"][m])
            qty, price = num.total(qty_units), num.total(price_units)
            disc = num.total(num.lift(li["l_discount"][m]))
            rows.append([
                rf_name, ls_name, ref.dec(qty, 2), ref.dec(price, 2),
                ref.dec(num.total(disc_price), 4), ref.dec(num.total(charge), 6),
                ref.dec(ref.dec_avg(qty, n), 2), ref.dec(ref.dec_avg(price, n), 2),
                ref.dec(ref.dec_avg(disc, n), 2), n,
            ])
    return rows
