"""Proof that the served SQL path runs on the chip: `python chip_smoke.py`.

One process owns the chip from start to finish. It starts the coordinator
(`CoordinatorServer(LocalQueryRunner.tpch(scale))` with a memory catalog),
loads the eight TPC-H tables into device-resident pages with CREATE TABLE AS
over HTTP, then runs TPC-H queries twice each through `StatementClient` and
compares every answer with a plain numpy evaluation of the same query over
the generator's arrays: integers and decimals exactly, doubles to 1e-9
relative. A warm repeat must compile nothing. Where four devices are present,
statements also run through the mesh tier and must answer as one chip did.

Seconds printed here are set-up facts of this run, not benchmark results.
Without a TPU the script exits non-zero before any table is generated; a
failed phase or a wrong answer ends it with the exception.
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys
import time
from decimal import Decimal

import numpy as np

# The target is TPC-H SF10 (ROADMAP R1: lineitem 60M rows) and the ladder q6,
# q1, q14, q3, q18. The scale is cut to what one v5e holds; the ladder is whole
# since PR 34 (q3 compiled for 783 s at SF1 in PR 21, when a page's columns
# rode every sort; CHANGES.md, PR 34, has what it takes now).
SCALE = 3
SCALE_CUT = (
    "3, not the target 10: at SF10 CREATE TABLE AS of lineitem ends in "
    "RESOURCE_EXHAUSTED on a 16 GB chip (its 60 splits are padded to 2M rows "
    "each, 13.6 GB before the copy that concatenates them)"
)
QUERIES = ("q06", "q01", "q14", "q03", "q18")
DROPPED: dict = {}  # {query: why}: what a later cut takes off the ladder again

TABLES = (
    "lineitem", "orders", "customer", "part", "supplier", "partsupp",
    "nation", "region",
)
# statements of __graft_entry__.dryrun_multichip (its q3 shape is dropped for
# the same compile time as q03); q6 runs on the mesh too
MESH_STATEMENTS = {
    "q1_shape": """SELECT l_returnflag, l_linestatus, sum(l_quantity), count(*)
        FROM lineitem GROUP BY l_returnflag, l_linestatus
        ORDER BY l_returnflag, l_linestatus""",
    "distributed_sort": """SELECT o_orderkey, o_totalprice FROM orders
        ORDER BY o_totalprice DESC, o_orderkey""",
}

# what the host evaluation reads; nothing else of a table is kept
ORACLE_COLUMNS = {
    "lineitem": (
        "l_orderkey", "l_partkey", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_returnflag", "l_linestatus", "l_shipdate",
    ),
    "orders": (
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority",
        "o_totalprice",
    ),
    "customer": ("c_custkey", "c_mktsegment"),
    "part": ("p_partkey", "p_type"),
}

_EPOCH = datetime.date(1970, 1, 1)


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def _iso(days: int) -> str:
    return (_EPOCH + datetime.timedelta(days=int(days))).isoformat()


# --------------------------------------------------------------------- device


def require_tpu() -> dict:
    """The device as JAX reports it; exits 2 unless the backend is a TPU."""
    import jax

    if jax.default_backend() != "tpu":
        print(
            f"chip_smoke: backend is {jax.default_backend()!r}, not 'tpu'; "
            "this script only runs on the chip",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return device_facts()


def device_facts() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def print_environment(device: dict) -> None:
    import os

    import jax
    import jaxlib
    from importlib import metadata

    print(
        f"device: platform: {device['platform']} device_kind: {device['kind']} "
        f"count: {device['count']}"
    )
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}")
    print(
        f"compile cache: {jax.config.jax_compilation_cache_dir} "
        f"(from {'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'the checkout'}), "
        f"min compile secs {jax.config.jax_persistent_cache_min_compile_time_secs}"
    )


def _memory(field: str) -> list:
    """``field`` of memory_stats() per device (None where not reported)."""
    import jax

    return [(d.memory_stats() or {}).get(field) for d in jax.devices()]


class Compilations:
    """XLA compilations of this process, by reading the counters the engine's
    own jax.monitoring listener keeps (runtime/observability.py). A compile
    request answered by the persistent cache is not a compilation."""

    def __init__(self):
        from trino_tpu.runtime import observability as obs
        from trino_tpu.runtime.metrics import REGISTRY

        with obs.compile_window():  # registers the listener
            pass
        self._requests = REGISTRY.counter("trino_tpu_xla_compiles_total")
        self._hits = REGISTRY.counter("trino_tpu_xla_persistent_cache_hits_total")
        self._mark = self._read()

    def _read(self):
        return int(self._requests.value), int(self._hits.value)

    def take(self) -> dict:
        """Counts since the previous take()."""
        now = self._read()
        requests, hits = now[0] - self._mark[0], now[1] - self._mark[1]
        self._mark = now
        return {"compiled": requests - hits, "from_cache": hits}


# ------------------------------------------------------------------- serving


class Served:
    """The coordinator in this process and a client on its HTTP port."""

    def __init__(self, runner):
        from trino_tpu.client import StatementClient
        from trino_tpu.server import CoordinatorServer

        self.runner = runner
        self.server = CoordinatorServer(runner).start()
        # one timeout bounds each request and the whole statement
        self.client = StatementClient(f"http://{self.server.address}", timeout=1000.0)

    def execute(self, sql: str):
        return self.client.execute(sql)

    def stop(self) -> None:
        self.server.stop()


def start(scale: float) -> Served:
    from trino_tpu import native
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.runtime import LocalQueryRunner

    if not native.native_available():
        raise RuntimeError(f"native page codec unavailable: {native.load_error()}")
    runner = LocalQueryRunner.tpch(scale=scale)
    runner.register_catalog("memory", MemoryConnector())
    return Served(runner)


def load(served: Served, compilations: Compilations) -> dict:
    """CTAS the eight tables into the memory catalog, then make it the
    session's default so the query texts run unchanged."""
    schema = served.runner.session.schema  # tpch.sf<scale> until the USE below
    rows = {}
    for table in TABLES:
        t0 = time.perf_counter()
        res = served.execute(
            f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.{schema}.{table}"
        )
        rows[table] = int(res.rows[0][0])
        print(
            f"load: {table} rows {rows[table]} secs {time.perf_counter() - t0:.2f} "
            f"{compilations.take()}"
        )
    served.execute("USE memory.default")
    return rows


# ------------------------------------------------------------ host evaluation


def host_columns(scale: float) -> dict:
    """The generator's arrays for ORACLE_COLUMNS, in its own encoding:
    decimals as integer cents, dates as days, strings as dictionary codes."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.connectors.tpch import generator as g

    connector = TpchConnector(scale=scale)
    out = {}
    for table, wanted in ORACLE_COLUMNS.items():
        total = connector.split_count(table, scale)
        pieces = {c: [] for c in wanted}
        for split in range(total):
            data = g.generate_split(table, scale, split, total)
            for c in wanted:
                pieces[c].append(data.columns[c])
        out[table] = {c: np.concatenate(v) for c, v in pieces.items()}
    return out


def _lookup(keys: np.ndarray, probe: np.ndarray):
    """Positions of ``probe`` in the strictly ascending ``keys``, and which
    of them were found."""
    if len(keys) > 1 and not (np.diff(keys) > 0).all():
        raise ValueError("join keys are not strictly ascending")
    pos = np.minimum(np.searchsorted(keys, probe), max(len(keys) - 1, 0))
    return pos, keys[pos] == probe if len(keys) else np.zeros(len(probe), bool)


def _group_sum(keys: np.ndarray, values: np.ndarray):
    """(distinct keys ascending, exact int64 sum of values per key)."""
    order = np.argsort(keys, kind="stable")
    k, v = keys[order], values[order]
    starts = np.flatnonzero(np.concatenate(([True], k[1:] != k[:-1])))
    return k[starts], np.add.reduceat(v, starts) if len(v) else v


def _dec(units: int, scale: int) -> Decimal:
    return Decimal(int(units)).scaleb(-scale)


def _avg(total: int, count: int) -> int:
    """Decimal avg keeps the scale and rounds half up."""
    return (2 * int(total) + count) // (2 * count)


def expect_q06(host: dict) -> list:
    li = host["lineitem"]
    m = (
        (li["l_shipdate"] >= _days("1994-01-01"))
        & (li["l_shipdate"] < _days("1995-01-01"))
        & (li["l_discount"] >= 5) & (li["l_discount"] <= 7)
        & (li["l_quantity"] < 2400)
    )
    return [[_dec((li["l_extendedprice"][m] * li["l_discount"][m]).sum(), 4)]]


def expect_q01(host: dict) -> list:
    from trino_tpu.connectors.tpch import generator as g

    li = host["lineitem"]
    keep = li["l_shipdate"] <= _days("1998-12-01") - 90
    disc_price = li["l_extendedprice"] * (100 - li["l_discount"])
    charge = disc_price * (100 + li["l_tax"])
    rows = []
    for rf, rf_name in enumerate(g.RETURN_FLAGS):
        for ls, ls_name in enumerate(g.LINE_STATUS):
            m = keep & (li["l_returnflag"] == rf) & (li["l_linestatus"] == ls)
            n = int(m.sum())
            if n == 0:
                continue
            qty = int(li["l_quantity"][m].sum())
            price = int(li["l_extendedprice"][m].sum())
            disc = int(li["l_discount"][m].sum())
            rows.append([
                rf_name, ls_name, _dec(qty, 2), _dec(price, 2),
                _dec(disc_price[m].sum(), 4), _dec(charge[m].sum(), 6),
                _dec(_avg(qty, n), 2), _dec(_avg(price, n), 2),
                _dec(_avg(disc, n), 2), n,
            ])
    return rows


def expect_q14(host: dict) -> list:
    from trino_tpu.connectors.tpch import generator as g

    li, part = host["lineitem"], host["part"]
    m = (li["l_shipdate"] >= _days("1995-09-01")) & (li["l_shipdate"] < _days("1995-10-01"))
    pos, found = _lookup(part["p_partkey"], li["l_partkey"][m])
    revenue = (li["l_extendedprice"][m] * (100 - li["l_discount"][m]))[found]
    promo_codes = np.array([v.startswith("PROMO") for v in g.PART_TYPES])
    promo = promo_codes[part["p_type"][pos[found]]]
    return [[100.0 * int(revenue[promo].sum()) / int(revenue.sum())]]


def expect_q03(host: dict) -> list:
    from trino_tpu.connectors.tpch import generator as g

    li, orders, cust = host["lineitem"], host["orders"], host["customer"]
    cutoff = _days("1995-03-15")
    building = cust["c_custkey"][cust["c_mktsegment"] == g.SEGMENTS.index("BUILDING")]
    o_keep = orders["o_orderdate"] < cutoff
    o_keep[o_keep] = _lookup(building, orders["o_custkey"][o_keep])[1]
    o_key = orders["o_orderkey"][o_keep]
    l_keep = li["l_shipdate"] > cutoff
    pos, found = _lookup(o_key, li["l_orderkey"][l_keep])
    revenue = (li["l_extendedprice"][l_keep] * (100 - li["l_discount"][l_keep]))[found]
    keys, sums = _group_sum(o_key[pos[found]], revenue)
    at = _lookup(o_key, keys)[0]
    date = orders["o_orderdate"][o_keep][at]
    prio = orders["o_shippriority"][o_keep][at]
    top = np.lexsort((keys, date, -sums))[:10]
    return [
        [int(keys[i]), _dec(sums[i], 4), _iso(date[i]), int(prio[i])] for i in top
    ]


def expect_q18(host: dict) -> list:
    li, orders = host["lineitem"], host["orders"]
    keys, qty = _group_sum(li["l_orderkey"], li["l_quantity"])
    big = qty > 15000
    keys, qty = keys[big], qty[big]
    at, found = _lookup(orders["o_orderkey"], keys)
    if not found.all():
        raise ValueError("lineitem order key without an order")
    # every order has a customer (c_custkey is 1..n), so the join keeps all
    cust = orders["o_custkey"][at]
    date, price = orders["o_orderdate"][at], orders["o_totalprice"][at]
    top = np.lexsort((keys, date, -price))[:100]
    return [
        [
            f"Customer#{int(cust[i]):09d}", int(cust[i]), int(keys[i]),
            _iso(date[i]), _dec(price[i], 2), _dec(qty[i], 2),
        ]
        for i in top
    ]


EXPECT = {
    "q06": expect_q06, "q01": expect_q01, "q14": expect_q14,
    "q03": expect_q03, "q18": expect_q18,
}


class WrongAnswer(AssertionError):
    pass


def check(name: str, got: list, want: list) -> None:
    """Integers, strings and decimals exactly; doubles to 1e-9 relative."""
    if len(got) != len(want):
        raise WrongAnswer(f"{name}: {len(got)} rows, expected {len(want)}")
    for i, (g_row, w_row) in enumerate(zip(got, want)):
        if len(g_row) != len(w_row):
            raise WrongAnswer(f"{name} row {i}: {g_row} != {w_row}")
        for g_val, w_val in zip(g_row, w_row):
            if isinstance(w_val, float):
                tolerance = 1e-9 * max(1.0, abs(w_val))
                ok = isinstance(g_val, (int, float)) and abs(g_val - w_val) <= tolerance
            elif isinstance(w_val, Decimal):
                ok = isinstance(g_val, str) and Decimal(g_val) == w_val
            else:
                ok = type(g_val) is type(w_val) and g_val == w_val
            if not ok:
                raise WrongAnswer(f"{name} row {i}: got {g_row}, expected {w_row}")


def run_query(served, name: str, sql: str, repeat: str, compilations: Compilations) -> list:
    t0 = time.perf_counter()
    rows = served.execute(sql).rows
    secs = time.perf_counter() - t0
    counts = compilations.take()
    print(f"query: {name} {repeat} rows {len(rows)} secs {secs:.3f} {counts}")
    if repeat == "warm" and counts["compiled"] + counts["from_cache"]:
        raise AssertionError(f"{name}: the warm repeat compiled {counts}")
    return rows


# --------------------------------------------------------------------- phases


def one_chip(scale: float, queries=QUERIES, also=()) -> dict:
    """Start, load, query twice, compare. Returns the answers to q06 and to
    the ``also`` statements (name -> sql, run once, for the mesh phase)."""
    from tests.tpch_corpus import TPCH_QUERIES

    compilations = Compilations()
    t0 = time.perf_counter()
    served = start(scale)
    print(f"start: server on {served.server.address} secs {time.perf_counter() - t0:.2f}")
    try:
        t0 = time.perf_counter()
        rows = load(served, compilations)
        print(f"load: eight tables secs {time.perf_counter() - t0:.2f}")
        print(f"memory: bytes_in_use after load {_memory('bytes_in_use')}")

        t0 = time.perf_counter()
        host = host_columns(scale)
        print(f"oracle: host columns generated secs {time.perf_counter() - t0:.2f}")
        for table, columns in host.items():
            n = len(next(iter(columns.values())))
            if rows[table] != n:
                raise WrongAnswer(f"{table}: loaded {rows[table]} rows, generator has {n}")

        answers = {}
        for name in queries:
            want = EXPECT[name](host)
            for repeat in ("cold", "warm"):
                answers[name] = run_query(
                    served, name, TPCH_QUERIES[name], repeat, compilations
                )
                check(name, answers[name], want)
            print(f"query: {name} equal to the host evaluation ({len(want)} rows)")
        del host
        for name, sql in dict(also).items():
            answers[name] = run_query(served, name, sql, "cold", compilations)
        return answers
    finally:
        served.stop()


def mesh(scale: float, n_devices: int, statements: dict, one_chip_answers: dict) -> None:
    """``statements`` through the mesh tier, each as one program over
    ``n_devices`` chips; every one must lower (no drop to the staged tier)
    and answer as one chip did."""
    from trino_tpu.parallel.runner import DistributedQueryRunner

    compilations = Compilations()
    runner = DistributedQueryRunner.tpch(scale=scale, n_workers=n_devices)
    served = Served(runner)
    try:
        for name, sql in statements.items():
            got = run_query(served, f"mesh {name}", sql, "cold", compilations)
            if runner.last_tier != "ici":
                raise AssertionError(
                    f"mesh {name}: ran on the {runner.last_tier!r} tier "
                    f"({runner.last_tier_reason})"
                )
            if got != one_chip_answers[name]:
                raise WrongAnswer(
                    f"mesh {name}: {got[:3]} != one chip {one_chip_answers[name][:3]}"
                )
            print(f"query: mesh {name} tier ici, equal to one chip ({len(got)} rows)")
    finally:
        served.stop()
    print(f"memory: mesh peak_bytes_in_use per device {_memory('peak_bytes_in_use')}")


def run(scale: float) -> None:
    """One chip always; the mesh tier too where four devices are present."""
    import jax
    from tests.tpch_corpus import TPCH_QUERIES

    four = len(jax.devices()) >= 4
    answers = one_chip(scale, also=MESH_STATEMENTS if four else ())
    print(f"memory: one chip peak_bytes_in_use {_memory('peak_bytes_in_use')}")
    if four:
        mesh(scale, 4, dict(MESH_STATEMENTS, q06=TPCH_QUERIES["q06"]), answers)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--scale", type=float, default=SCALE,
        help="TPC-H scale factor, at least 1 (default %(default)s)",
    )
    args = parser.parse_args(argv)
    if args.scale < 1:
        parser.error("the smoke runs at a scale of at least 1")
    device = require_tpu()
    import trino_tpu  # noqa: F401  (x64 and the compile cache rule)

    print_environment(device)
    print(f"scale: {args.scale:g}" + (f" ({SCALE_CUT})" if args.scale == SCALE else ""))
    for name, why in DROPPED.items():
        print(f"dropped for time: {name} ({why})")
    t0 = time.perf_counter()
    run(args.scale)
    print(f"total secs {time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
