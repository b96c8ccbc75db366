# The TPC-H population the reference evaluates: a verbatim copy of
# trino_tpu/connectors/tpch/generator.py as of commit 7d51723 (PR 21), kept here
# so that the reference imports nothing of the program, and a later change to the
# program's generator shows as a wrong answer instead of moving both sides.
# Only this comment was added.
"""Deterministic TPC-H data generator (numpy, split-addressable).

Reference blueprint: plugin/trino-tpch (TpchConnectorFactory.java:30,
TpchPageSourceProvider.java:53 — "generates TPC-H data on the fly"). Like the
reference, data is generated deterministically per split so any worker can
produce any split without coordination; unlike dbgen we generate *dictionary
codes directly* (no string materialization on the generation path) — string
columns draw from fixed sorted vocabularies, so the device only ever sees int32
codes and generation is pure vectorized numpy.

Distributions follow dbgen's shapes (date ranges, returnflag/linestatus rules,
1..7 lineitems per order, discount 0..0.10, ...) but are not bit-identical to
dbgen; correctness tests compare against a pandas oracle over the same data.
"""

from __future__ import annotations

import datetime
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


def _days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


MIN_ORDER_DATE = _days(1992, 1, 1)
MAX_ORDER_DATE = _days(1998, 8, 2)
CURRENT_DATE = _days(1995, 6, 17)  # dbgen's CURRENTDATE used for flags

# ---------------------------------------------------------------------------- #
# Vocabularies (sorted! — code order must equal string order)
# ---------------------------------------------------------------------------- #

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]

NATIONS = [
    # (name, regionkey) — dbgen's 25 nations
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("CHINA", 2),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2),
    ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4),
    ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1),
    ("ROMANIA", 3), ("RUSSIA", 3), ("SAUDI ARABIA", 4), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1), ("VIETNAM", 2),
]

SEGMENTS = sorted(["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"])
PRIORITIES = sorted(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"])
SHIP_MODES = sorted(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"])
SHIP_INSTRUCTS = sorted(["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"])
ORDER_STATUS = ["F", "O", "P"]
RETURN_FLAGS = ["A", "N", "R"]
LINE_STATUS = ["F", "O"]

TYPE_SYLL1 = ["ECONOMY", "LARGE", "MEDIUM", "PROMO", "SMALL", "STANDARD"]
TYPE_SYLL2 = ["ANODIZED", "BRUSHED", "BURNISHED", "PLATED", "POLISHED"]
TYPE_SYLL3 = ["BRASS", "COPPER", "NICKEL", "STEEL", "TIN"]
PART_TYPES = sorted(f"{a} {b} {c}" for a in TYPE_SYLL1 for b in TYPE_SYLL2 for c in TYPE_SYLL3)

CONTAINER_SYLL1 = ["JUMBO", "LG", "MED", "SM", "WRAP"]
CONTAINER_SYLL2 = ["BAG", "BOX", "CAN", "CASE", "DRUM", "JAR", "PACK", "PKG"]
CONTAINERS = sorted(f"{a} {b}" for a in CONTAINER_SYLL1 for b in CONTAINER_SYLL2)

BRANDS = sorted(f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6))
MFGRS = sorted(f"Manufacturer#{i}" for i in range(1, 6))

COLORS = sorted(
    """almond antique aquamarine azure beige bisque black blanched blue blush brown
    burlywood burnished chartreuse chiffon chocolate coral cornflower cornsilk cream
    cyan dark deep dim dodger drab firebrick floral forest frosted gainsboro ghost
    goldenrod green grey honeydew hot indian ivory khaki lace lavender lawn lemon
    light lime linen magenta maroon medium metallic midnight mint misty moccasin
    navajo navy olive orange orchid pale papaya peach peru pink plum powder puff
    purple red rose rosy royal saddle salmon sandy seashell sienna sky slate smoke
    snow spring steel tan thistle tomato turquoise violet wheat white yellow""".split()
)

# comment vocab: bounded pools so dictionaries stay small (see module docstring)
_COMMENT_WORDS = [
    "carefully", "quickly", "slyly", "furiously", "blithely", "silent", "final",
    "ironic", "pending", "regular", "express", "special", "unusual", "even", "bold",
    "requests", "deposits", "packages", "instructions", "accounts", "theodolites",
    "foxes", "pinto", "beans", "dependencies", "excuses", "platelets", "asymptotes",
    "courts", "dolphins", "multipliers", "sauternes", "warhorses", "sheaves",
]


def _make_comments(rng: np.random.Generator, count: int) -> List[str]:
    words = rng.choice(_COMMENT_WORDS, size=(count, 4))
    return [" ".join(row) for row in words]


# pre-built comment pools (deterministic, shared by all scale factors)
_POOL_RNG = np.random.default_rng(20260728)
COMMENT_POOL = sorted(set(_make_comments(_POOL_RNG, 2000)))
PART_NAME_POOL = sorted(
    {" ".join(_POOL_RNG.choice(COLORS, size=5)) for _ in range(2000)}
)


@dataclass(frozen=True)
class TpchColumn:
    name: str
    type_name: str  # parsed by spi.types.parse_type
    vocab: Optional[Tuple[str, ...]] = None  # for varchar columns


def _v(words) -> Tuple[str, ...]:
    return tuple(words)


TPCH_TABLES: Dict[str, List[TpchColumn]] = {
    "region": [
        TpchColumn("r_regionkey", "bigint"),
        TpchColumn("r_name", "varchar(25)", _v(REGIONS)),
        TpchColumn("r_comment", "varchar(152)", _v(COMMENT_POOL)),
    ],
    "nation": [
        TpchColumn("n_nationkey", "bigint"),
        TpchColumn("n_name", "varchar(25)", _v(sorted(n for n, _ in NATIONS))),
        TpchColumn("n_regionkey", "bigint"),
        TpchColumn("n_comment", "varchar(152)", _v(COMMENT_POOL)),
    ],
    "supplier": [
        TpchColumn("s_suppkey", "bigint"),
        TpchColumn("s_name", "varchar(25)", None),  # synthesized numbered names
        TpchColumn("s_address", "varchar(40)", _v(COMMENT_POOL)),
        TpchColumn("s_nationkey", "bigint"),
        TpchColumn("s_phone", "varchar(15)", None),
        TpchColumn("s_acctbal", "decimal(12,2)"),
        TpchColumn("s_comment", "varchar(101)", _v(COMMENT_POOL)),
    ],
    "customer": [
        TpchColumn("c_custkey", "bigint"),
        TpchColumn("c_name", "varchar(25)", None),
        TpchColumn("c_address", "varchar(40)", _v(COMMENT_POOL)),
        TpchColumn("c_nationkey", "bigint"),
        TpchColumn("c_phone", "varchar(15)", None),
        TpchColumn("c_acctbal", "decimal(12,2)"),
        TpchColumn("c_mktsegment", "varchar(10)", _v(SEGMENTS)),
        TpchColumn("c_comment", "varchar(117)", _v(COMMENT_POOL)),
    ],
    "part": [
        TpchColumn("p_partkey", "bigint"),
        TpchColumn("p_name", "varchar(55)", _v(PART_NAME_POOL)),
        TpchColumn("p_mfgr", "varchar(25)", _v(MFGRS)),
        TpchColumn("p_brand", "varchar(10)", _v(BRANDS)),
        TpchColumn("p_type", "varchar(25)", _v(PART_TYPES)),
        TpchColumn("p_size", "integer"),
        TpchColumn("p_container", "varchar(10)", _v(CONTAINERS)),
        TpchColumn("p_retailprice", "decimal(12,2)"),
        TpchColumn("p_comment", "varchar(23)", _v(COMMENT_POOL)),
    ],
    "partsupp": [
        TpchColumn("ps_partkey", "bigint"),
        TpchColumn("ps_suppkey", "bigint"),
        TpchColumn("ps_availqty", "integer"),
        TpchColumn("ps_supplycost", "decimal(12,2)"),
        TpchColumn("ps_comment", "varchar(199)", _v(COMMENT_POOL)),
    ],
    "orders": [
        TpchColumn("o_orderkey", "bigint"),
        TpchColumn("o_custkey", "bigint"),
        TpchColumn("o_orderstatus", "varchar(1)", _v(ORDER_STATUS)),
        TpchColumn("o_totalprice", "decimal(12,2)"),
        TpchColumn("o_orderdate", "date"),
        TpchColumn("o_orderpriority", "varchar(15)", _v(PRIORITIES)),
        TpchColumn("o_clerk", "varchar(15)", None),
        TpchColumn("o_shippriority", "integer"),
        TpchColumn("o_comment", "varchar(79)", _v(COMMENT_POOL)),
    ],
    "lineitem": [
        TpchColumn("l_orderkey", "bigint"),
        TpchColumn("l_partkey", "bigint"),
        TpchColumn("l_suppkey", "bigint"),
        TpchColumn("l_linenumber", "integer"),
        TpchColumn("l_quantity", "decimal(12,2)"),
        TpchColumn("l_extendedprice", "decimal(12,2)"),
        TpchColumn("l_discount", "decimal(12,2)"),
        TpchColumn("l_tax", "decimal(12,2)"),
        TpchColumn("l_returnflag", "varchar(1)", _v(RETURN_FLAGS)),
        TpchColumn("l_linestatus", "varchar(1)", _v(LINE_STATUS)),
        TpchColumn("l_shipdate", "date"),
        TpchColumn("l_commitdate", "date"),
        TpchColumn("l_receiptdate", "date"),
        TpchColumn("l_shipinstruct", "varchar(25)", _v(SHIP_INSTRUCTS)),
        TpchColumn("l_shipmode", "varchar(10)", _v(SHIP_MODES)),
        TpchColumn("l_comment", "varchar(44)", _v(COMMENT_POOL)),
    ],
}

BASE_ROW_COUNTS = {
    "region": 5,
    "nation": 25,
    "supplier": 10_000,
    "customer": 150_000,
    "part": 200_000,
    "partsupp": 800_000,
    "orders": 1_500_000,
    "lineitem": None,  # derived from orders (avg 4 per order)
}

MAX_LINES_PER_ORDER = 7


def row_count(table: str, scale: float) -> int:
    if table in ("region", "nation"):
        return BASE_ROW_COUNTS[table]
    if table == "lineitem":
        # upper bound; exact count is data-dependent (orders x 1..7)
        raise ValueError("lineitem row count is derived; use order count")
    return max(1, int(BASE_ROW_COUNTS[table] * scale))


def canonical_chunk_rows(total_rows: int) -> int:
    """Generation chunk size: the table's content is defined per canonical
    chunk (seeded by chunk index), NEVER per split — so the data is identical
    under any split layout (split = a contiguous range of chunks). Small scales
    get ~64 chunks for scheduling parallelism; large scales cap chunk size."""
    return int(min(max(total_rows // 64, 64), 262_144))


def chunk_range_for_split(total_rows: int, split: int, total_splits: int):
    """(first_chunk, end_chunk, chunk_rows, n_chunks) for a split."""
    chunk = canonical_chunk_rows(total_rows)
    n_chunks = (total_rows + chunk - 1) // chunk
    first = (n_chunks * split) // total_splits
    end = (n_chunks * (split + 1)) // total_splits
    return first, end, chunk, n_chunks


def _rng(table: str, scale: float, chunk: int) -> np.random.Generator:
    # stable across processes (Python's builtin hash() is salted per process)
    import hashlib

    key = f"{table}:{round(scale * 1_000_000)}:{chunk}".encode()
    seed = int.from_bytes(hashlib.blake2s(key, digest_size=8).digest(), "little")
    return np.random.default_rng(seed)


def _retail_price(partkey: np.ndarray) -> np.ndarray:
    """dbgen's retail price formula, in cents."""
    return 90000 + ((partkey // 10) % 20001) + 100 * (partkey % 1000)


def _numbered_vocab(prefix: str, count: int, width: int = 9) -> List[str]:
    return [f"{prefix}{i:0{width}d}" for i in range(1, count + 1)]


class TpchTableData:
    """Columnar numpy arrays for one split of one table (codes for varchars)."""

    def __init__(self, columns: Dict[str, np.ndarray], count: int):
        self.columns = columns
        self.count = count


def generate_split(
    table: str, scale: float, split: int, total_splits: int
) -> TpchTableData:
    """Rows of ``table`` belonging to ``split``: the concatenation of the
    split's canonical chunks (deterministic, independent of split layout)."""
    if table == "lineitem":
        return _gen_lineitem(scale, split, total_splits)
    n = row_count(table, scale)
    first, end_chunk, chunk, _ = chunk_range_for_split(n, split, total_splits)
    gen = {
        "region": _gen_region,
        "nation": _gen_nation,
        "supplier": _gen_supplier,
        "customer": _gen_customer,
        "part": _gen_part,
        "partsupp": _gen_partsupp,
        "orders": _gen_orders,
    }[table]
    pieces = []
    count = 0
    for c in range(first, end_chunk):
        start = c * chunk
        stop = min((c + 1) * chunk, n)
        keys = np.arange(start + 1, stop + 1, dtype=np.int64)
        rng = _rng(table, scale, c)
        pieces.append(gen(keys, rng, scale))
        count += stop - start
    if not pieces:
        cols = {k: np.zeros(0, dtype=v.dtype) for k, v in gen(
            np.arange(1, 2, dtype=np.int64), _rng(table, scale, 0), scale
        ).items()}
        return TpchTableData(cols, 0)
    cols = {
        k: np.concatenate([p[k] for p in pieces]) for k in pieces[0].keys()
    }
    return TpchTableData(cols, count)


def _comment_codes(rng, n) -> np.ndarray:
    return rng.integers(0, len(COMMENT_POOL), size=n, dtype=np.int32)


def _gen_region(keys, rng, scale):
    return {
        "r_regionkey": np.arange(len(REGIONS), dtype=np.int64)[keys - 1],
        "r_name": np.arange(len(REGIONS), dtype=np.int32)[keys - 1],
        "r_comment": _comment_codes(rng, len(keys)),
    }


def _gen_nation(keys, rng, scale):
    names = sorted(n for n, _ in NATIONS)
    name_code = {n: i for i, n in enumerate(names)}
    codes = np.array([name_code[NATIONS[k - 1][0]] for k in keys], dtype=np.int32)
    regionkeys = np.array([NATIONS[k - 1][1] for k in keys], dtype=np.int64)
    return {
        "n_nationkey": keys - 1,
        "n_name": codes,
        "n_regionkey": regionkeys,
        "n_comment": _comment_codes(rng, len(keys)),
    }


def _phone_codes(keys: np.ndarray, total: int) -> np.ndarray:
    """Codes into the phone vocab: phone = '<10+nation>-<key:011d>' with
    nation = (key-1) % 25 (TPC-H country-code semantics, spec 4.2.2.9), laid
    out class-major so code order == lexicographic order (sorted-dict
    invariant). Class m holds keys {m+1, m+26, ...}."""
    m = (keys - 1) % 25
    counts = np.array([(total - c - 1) // 25 + 1 if c < total else 0 for c in range(25)])
    class_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    return (class_start[m] + (keys - 1) // 25).astype(np.int32)


def _phone_vocab(total: int) -> List[str]:
    vocab = []
    for m in range(25):
        prefix = 10 + m
        ck = m + 1
        while ck <= total:
            vocab.append(f"{prefix}-{ck:011d}")
            ck += 25
    return vocab


def _gen_supplier(keys, rng, scale):
    n = len(keys)
    total = row_count("supplier", scale)
    return {
        "s_suppkey": keys,
        "s_name": (keys - 1).astype(np.int32),  # code == key-1 into numbered vocab
        "s_address": _comment_codes(rng, n),
        # nation derived from key so the phone country code matches (Q22 shape)
        "s_nationkey": ((keys - 1) % 25).astype(np.int64),
        "s_phone": _phone_codes(keys, total),
        "s_acctbal": rng.integers(-99999, 999999, size=n, dtype=np.int64),
        "s_comment": _comment_codes(rng, n),
    }


def _gen_customer(keys, rng, scale):
    n = len(keys)
    total = row_count("customer", scale)
    return {
        "c_custkey": keys,
        "c_name": (keys - 1).astype(np.int32),
        "c_address": _comment_codes(rng, n),
        "c_nationkey": ((keys - 1) % 25).astype(np.int64),
        "c_phone": _phone_codes(keys, total),
        "c_acctbal": rng.integers(-99999, 999999, size=n, dtype=np.int64),
        "c_mktsegment": rng.integers(0, len(SEGMENTS), size=n, dtype=np.int32),
        "c_comment": _comment_codes(rng, n),
    }


def _gen_part(keys, rng, scale):
    n = len(keys)
    return {
        "p_partkey": keys,
        "p_name": rng.integers(0, len(PART_NAME_POOL), size=n, dtype=np.int32),
        "p_mfgr": ((keys - 1) % 5).astype(np.int32),
        "p_brand": rng.integers(0, len(BRANDS), size=n, dtype=np.int32),
        "p_type": rng.integers(0, len(PART_TYPES), size=n, dtype=np.int32),
        "p_size": rng.integers(1, 51, size=n, dtype=np.int32),
        "p_container": rng.integers(0, len(CONTAINERS), size=n, dtype=np.int32),
        "p_retailprice": _retail_price(keys),
        "p_comment": _comment_codes(rng, n),
    }


def _gen_partsupp(keys, rng, scale):
    n = len(keys)
    num_parts = row_count("part", scale)
    num_supps = row_count("supplier", scale)
    partkeys = (keys - 1) // 4 + 1
    partkeys = np.minimum(partkeys, num_parts)
    return {
        "ps_partkey": partkeys,
        "ps_suppkey": rng.integers(1, num_supps + 1, size=n, dtype=np.int64),
        "ps_availqty": rng.integers(1, 10000, size=n, dtype=np.int32),
        "ps_supplycost": rng.integers(100, 100001, size=n, dtype=np.int64),
        "ps_comment": _comment_codes(rng, n),
    }


def _gen_orders(keys, rng, scale):
    n = len(keys)
    num_cust = row_count("customer", scale)
    dates = rng.integers(MIN_ORDER_DATE, MAX_ORDER_DATE - 121, size=n, dtype=np.int32)
    status_code = np.where(
        dates + 100 < CURRENT_DATE,
        0,  # 'F'
        np.where(dates > CURRENT_DATE, 1, 2),  # 'O' / 'P'
    ).astype(np.int32)
    # spec 4.2.3: o_custkey skips custkey % 3 == 0 — one third of customers
    # never place orders (the population Q13/Q22 depend on). The i-th valid
    # key (0-based, skipping multiples of 3) is 3*(i//2) + i%2 + 1.
    num_valid = num_cust - num_cust // 3
    i = rng.integers(0, max(num_valid, 1), size=n, dtype=np.int64)
    custkeys = 3 * (i // 2) + (i % 2) + 1
    return {
        "o_orderkey": keys,
        "o_custkey": custkeys,
        "o_orderstatus": status_code,
        "o_totalprice": rng.integers(90000, 55555500, size=n, dtype=np.int64),
        "o_orderdate": dates,
        "o_orderpriority": rng.integers(0, len(PRIORITIES), size=n, dtype=np.int32),
        "o_clerk": rng.integers(0, max(1, int(1000 * scale)), size=n).astype(np.int32),
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": _comment_codes(rng, n),
    }


def lineitem_split_rows(scale: float, split: int, total_splits: int) -> int:
    """Exact lineitem row count of a split without generating the columns
    (draws only lines_per_order — the first draw of each chunk's rng stream)."""
    num_orders = row_count("orders", scale)
    first, end_chunk, chunk, _ = chunk_range_for_split(num_orders, split, total_splits)
    total = 0
    for c in range(first, end_chunk):
        start = c * chunk
        stop = min((c + 1) * chunk, num_orders)
        rng = _rng("lineitem", scale, c)
        total += int(rng.integers(1, MAX_LINES_PER_ORDER + 1, size=stop - start).sum())
    return total


def _gen_lineitem(scale: float, split: int, total_splits: int) -> TpchTableData:
    """Lineitems of the split's canonical chunks (consistent with _gen_orders)."""
    num_orders = row_count("orders", scale)
    first, end_chunk, chunk, _ = chunk_range_for_split(num_orders, split, total_splits)
    pieces = [
        _gen_lineitem_chunk(scale, c, chunk, num_orders) for c in range(first, end_chunk)
    ]
    if not pieces:
        ref = _gen_lineitem_chunk(scale, 0, chunk, num_orders)
        cols = {k: np.zeros(0, dtype=v.dtype) for k, v in ref.columns.items()}
        return TpchTableData(cols, 0)
    cols = {
        k: np.concatenate([p.columns[k] for p in pieces]) for k in pieces[0].columns
    }
    return TpchTableData(cols, sum(p.count for p in pieces))


def _gen_lineitem_chunk(
    scale: float, chunk_idx: int, chunk: int, num_orders: int
) -> TpchTableData:
    start = chunk_idx * chunk
    end = min((chunk_idx + 1) * chunk, num_orders)
    okeys = np.arange(start + 1, end + 1, dtype=np.int64)
    # regenerate the order dates exactly as _gen_orders does (same rng stream)
    orng = _rng("orders", scale, chunk_idx)
    n_orders = len(okeys)
    num_cust = row_count("customer", scale)
    odates = orng.integers(MIN_ORDER_DATE, MAX_ORDER_DATE - 121, size=n_orders, dtype=np.int32)

    rng = _rng("lineitem", scale, chunk_idx)
    lines_per_order = rng.integers(1, MAX_LINES_PER_ORDER + 1, size=n_orders)
    n = int(lines_per_order.sum())
    order_idx = np.repeat(np.arange(n_orders), lines_per_order)
    l_orderkey = okeys[order_idx]
    # linenumber within order
    first = np.zeros(n, dtype=bool)
    first[np.cumsum(lines_per_order)[:-1]] = True
    first[0] = True
    linenumber = (np.arange(n) - np.repeat(np.concatenate([[0], np.cumsum(lines_per_order)[:-1]]), lines_per_order) + 1).astype(np.int32)

    num_parts = row_count("part", scale)
    num_supps = row_count("supplier", scale)
    partkey = rng.integers(1, num_parts + 1, size=n, dtype=np.int64)
    suppkey = rng.integers(1, num_supps + 1, size=n, dtype=np.int64)
    quantity = rng.integers(1, 51, size=n, dtype=np.int64)
    extendedprice = quantity * _retail_price(partkey)
    discount = rng.integers(0, 11, size=n, dtype=np.int64)  # cents: 0.00..0.10
    tax = rng.integers(0, 9, size=n, dtype=np.int64)

    odate = odates[order_idx]
    shipdate = odate + rng.integers(1, 122, size=n, dtype=np.int32)
    commitdate = odate + rng.integers(30, 91, size=n, dtype=np.int32)
    receiptdate = shipdate + rng.integers(1, 31, size=n, dtype=np.int32)

    returned = receiptdate <= CURRENT_DATE
    rf = np.where(returned, np.where(rng.random(n) < 0.5, 0, 2), 1).astype(np.int32)  # A/R else N
    ls = np.where(shipdate > CURRENT_DATE, 1, 0).astype(np.int32)  # O else F

    return TpchTableData(
        {
            "l_orderkey": l_orderkey,
            "l_partkey": partkey,
            "l_suppkey": suppkey,
            "l_linenumber": linenumber,
            "l_quantity": quantity * 100,  # decimal(12,2) cents
            "l_extendedprice": extendedprice,
            "l_discount": discount,
            "l_tax": tax,
            "l_returnflag": rf,
            "l_linestatus": ls,
            "l_shipdate": shipdate,
            "l_commitdate": commitdate,
            "l_receiptdate": receiptdate,
            "l_shipinstruct": rng.integers(0, len(SHIP_INSTRUCTS), size=n, dtype=np.int32),
            "l_shipmode": rng.integers(0, len(SHIP_MODES), size=n, dtype=np.int32),
            "l_comment": _comment_codes(rng, n),
        },
        n,
    )


def vocab_for(table: str, column: str, scale: float) -> Optional[List[str]]:
    """The sorted dictionary for a varchar column (None for non-varchar)."""
    col = next(c for c in TPCH_TABLES[table] if c.name == column)
    if col.vocab is not None:
        return list(col.vocab)
    # numbered-name columns
    if column in ("s_name",):
        return _numbered_vocab("Supplier#", row_count("supplier", scale))
    if column in ("c_name",):
        return _numbered_vocab("Customer#", row_count("customer", scale))
    if column == "s_phone":
        return _phone_vocab(row_count("supplier", scale))
    if column == "c_phone":
        return _phone_vocab(row_count("customer", scale))
    if column == "o_clerk":
        return _numbered_vocab("Clerk#", max(1, int(1000 * scale)))
    return None
