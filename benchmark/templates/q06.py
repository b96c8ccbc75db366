"""TPC-H Q6, forecasting revenue change (specification clause 2.4.6)."""

from benchmark import reference as ref

SQL = """SELECT sum(l_extendedprice * l_discount) AS revenue
FROM {schema}.lineitem
WHERE l_shipdate >= DATE '{date}'
  AND l_shipdate < DATE '{date}' + INTERVAL '1' YEAR
  AND l_discount BETWEEN {discount} - 0.01 AND {discount} + 0.01
  AND l_quantity < {quantity}"""

# clause 2.4.6.3: DATE is 1 January of a year in [1993, 1997], DISCOUNT in
# [0.02, 0.09], QUANTITY in [24, 25]
DOMAIN = {
    "year": [1993, 1994, 1995, 1996, 1997],
    "discount_cents": [2, 3, 4, 5, 6, 7, 8, 9],
    "quantity": [24, 25],
}
COLUMNS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]}
# what the statement has to read, whatever implements it (scan_roofline)
SCANS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]}


def literals(p: dict) -> dict:
    return {
        "date": f"{p['year']}-01-01",
        "discount": f"0.{p['discount_cents']:02d}",
        "quantity": p["quantity"],
    }


def expect(host: dict, p: dict, num: ref.Arith) -> list:
    li = host["lineitem"]
    m = (
        (li["l_shipdate"] >= ref.days(f"{p['year']}-01-01"))
        & (li["l_shipdate"] < ref.days(f"{p['year'] + 1}-01-01"))
        & (li["l_discount"] >= p["discount_cents"] - 1)
        & (li["l_discount"] <= p["discount_cents"] + 1)
        & (li["l_quantity"] < 100 * p["quantity"])
    )
    if not m.any():
        return [[None]]
    revenue = num.lift(li["l_extendedprice"][m]) * num.lift(li["l_discount"][m])
    return [[ref.dec(num.total(revenue), 4)]]
