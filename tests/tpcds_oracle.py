"""SQLite oracle for TPC-DS conformance: an independent engine computing
expected results over IDENTICAL generated data.

The analogue of the reference's H2QueryRunner (testing/trino-testing/.../
H2QueryRunner.java) — Trino verifies engine results against a second,
unrelated SQL engine over the same rows; we use the stdlib sqlite3 (3.39+
has window functions and FULL OUTER JOIN). No DuckDB exists in this image.

Canonical-text translation (to_sqlite_sql): DATE literals become epoch-day
integers (our storage representation, so `date +/- INTERVAL 'n' DAY`
becomes integer +/- n), casts to decimal become REAL casts, stddev/var
aggregates register as Python UDAFs. ROLLUP/GROUPING queries are outside
sqlite's dialect and are excluded by callers (covered by the pandas
families in test_tpcds.py instead).
"""

from __future__ import annotations

import datetime
import functools
import math
import re
import sqlite3
from typing import Dict, List, Optional, Tuple

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


# --------------------------------------------------------------------------- #
# data load
# --------------------------------------------------------------------------- #


def _decoded_columns(conn, table: str, scale: float):
    """Column name -> (python list incl. None) for one whole table."""
    from trino_tpu.connectors.tpcds import _TABLES, generate_split, data_valid

    nsplits = conn.split_count(table, scale)
    specs = _TABLES[table]
    acc: Dict[str, List] = {c[0]: [] for c in specs}
    for s in range(nsplits):
        data, count = generate_split(table, scale, s, nsplits)
        for name, type_name, _gen in specs:
            arr, valid = data_valid(data[name])
            d = conn.dictionary(table, name, scale)
            if d is not None:
                vals = d.decode(np.asarray(arr, dtype=np.int64))
                out = [str(v) for v in vals]
            elif type_name.startswith("decimal"):
                m = re.match(r"decimal\(\d+,(\d+)\)", type_name)
                scale_digits = int(m.group(1)) if m else 2
                out = [float(v) / (10 ** scale_digits) for v in np.asarray(arr)]
            else:
                out = [int(v) for v in np.asarray(arr)]
            if valid is not None:
                v = np.asarray(valid)
                out = [x if ok else None for x, ok in zip(out, v)]
            acc[name].extend(out)
    return acc


class _StdDev:
    """Welford aggregate; ddof chosen at registration (samp=1, pop=0)."""

    def __init__(self, ddof: int, variance: bool):
        self.ddof, self.variance = ddof, variance
        self.n, self.mean, self.m2 = 0, 0.0, 0.0

    def step(self, v):
        if v is None:
            return
        v = float(v)
        self.n += 1
        d = v - self.mean
        self.mean += d / self.n
        self.m2 += d * (v - self.mean)

    def finalize(self):
        if self.n - self.ddof <= 0:
            return None
        var = self.m2 / (self.n - self.ddof)
        return var if self.variance else math.sqrt(var)


def _make_agg(ddof: int, variance: bool):
    class Agg(_StdDev):
        def __init__(self):
            super().__init__(ddof, variance)

    return Agg


@functools.lru_cache(maxsize=4)
def tpcds_sqlite(scale: float) -> sqlite3.Connection:
    """In-memory sqlite DB with all 24 TPC-DS tables at ``scale``."""
    from trino_tpu.connectors.tpcds import _TABLES, TpcdsConnector

    conn = TpcdsConnector(scale=scale)
    con = sqlite3.connect(":memory:", check_same_thread=False)
    con.create_aggregate("stddev_samp", 1, _make_agg(1, False))
    con.create_aggregate("stddev_pop", 1, _make_agg(0, False))
    con.create_aggregate("stddev", 1, _make_agg(1, False))
    con.create_aggregate("var_samp", 1, _make_agg(1, True))
    con.create_aggregate("var_pop", 1, _make_agg(0, True))
    con.create_aggregate("variance", 1, _make_agg(1, True))
    con.create_function(
        "concat", -1,
        lambda *a: None if any(x is None for x in a) else "".join(str(x) for x in a),
    )
    for table, specs in _TABLES.items():
        cols = _decoded_columns(conn, table, scale)
        names = [c[0] for c in specs]
        decls = []
        for name, type_name, _ in specs:
            if conn.dictionary(table, name, scale) is not None:
                decls.append(f"{name} TEXT")
            elif type_name.startswith("decimal"):
                decls.append(f"{name} REAL")
            else:
                decls.append(f"{name} INTEGER")
        con.execute(f"CREATE TABLE {table} ({', '.join(decls)})")
        rows = list(zip(*[cols[n] for n in names])) if names else []
        con.executemany(
            f"INSERT INTO {table} VALUES ({', '.join('?' * len(names))})", rows
        )
    con.commit()
    return con


# --------------------------------------------------------------------------- #
# canonical text -> sqlite dialect
# --------------------------------------------------------------------------- #

_DATE_LIT = re.compile(r"\bdate\s*'(\d{4}-\d{2}-\d{2})'", re.IGNORECASE)
_CAST_DATE = re.compile(
    r"\bcast\s*\(\s*'(\d{4}-\d{2}-\d{2})'\s*as\s+date\s*\)", re.IGNORECASE
)
_BARE_DATE = re.compile(r"'(\d{4}-\d{2}-\d{2})'")
_INTERVAL_DAY = re.compile(
    r"\+\s*interval\s*'(\d+)'\s*day|\-\s*interval\s*'(\d+)'\s*day", re.IGNORECASE
)
_INTERVAL_GENERIC = re.compile(
    r"(\+|\-)\s*interval\s*'(\d+)'\s*(day|days)", re.IGNORECASE
)
_CAST_DECIMAL = re.compile(r"as\s+decimal\s*\(\s*\d+\s*,\s*\d+\s*\)", re.IGNORECASE)
_DECIMAL_LIT = re.compile(r"\bdecimal\s+'([0-9.+-]+)'", re.IGNORECASE)
_DAYS_SUFFIX = re.compile(r"(\+|\-)\s*(\d+)\s+days\b", re.IGNORECASE)
_SETOP_OPEN = re.compile(r"(UNION\s+ALL|UNION|EXCEPT|INTERSECT)(\s*)\(", re.IGNORECASE)
_SETOP_AFTER = re.compile(r"^\s*(UNION\s+ALL|UNION|EXCEPT|INTERSECT)", re.IGNORECASE)


def _matching_paren(sql: str, open_idx: int) -> int:
    depth = 0
    for i in range(open_idx, len(sql)):
        if sql[i] == "(":
            depth += 1
        elif sql[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return -1


_TOP_SETOP = re.compile(r"\b(UNION|EXCEPT|INTERSECT)\b", re.IGNORECASE)


def _has_toplevel_setop(fragment: str) -> bool:
    depth = 0
    for m in _TOP_SETOP.finditer(fragment):
        depth = fragment[: m.start()].count("(") - fragment[: m.start()].count(")")
        if depth == 0:
            return True
    return False


def _strip_setop_parens(sql: str) -> str:
    """sqlite (<=3.40) rejects parenthesized compound-select operands
    (`A UNION ALL (SELECT ...)`, `(SELECT ...) EXCEPT ...`): drop the parens
    around any SELECT whose wrapper directly touches a set operator.
    Operands that are THEMSELVES compounds keep their parens (stripping
    would re-associate the set expression) — those queries fail loudly as
    oracle errors instead of silently verifying against wrong rows."""
    changed = True
    while changed:
        changed = False
        # operand after a set keyword
        m = _SETOP_OPEN.search(sql)
        while m is not None:
            open_idx = m.end() - 1
            close_idx = _matching_paren(sql, open_idx)
            inner = sql[open_idx + 1 : close_idx].strip()
            if (
                close_idx > 0
                and inner.upper().startswith("SELECT")
                and not _has_toplevel_setop(inner)
            ):
                sql = (
                    sql[:open_idx] + " " + sql[open_idx + 1 : close_idx]
                    + " " + sql[close_idx + 1 :]
                )
                changed = True
                m = _SETOP_OPEN.search(sql)
            else:
                m = _SETOP_OPEN.search(sql, m.end())
        # operand before a set keyword: "(SELECT ...) UNION ..."
        i = sql.find("(")
        while i != -1:
            close_idx = _matching_paren(sql, i)
            if close_idx > 0:
                inner = sql[i + 1 : close_idx].strip()
                if (
                    inner.upper().startswith("SELECT")
                    and not _has_toplevel_setop(inner)
                    and _SETOP_AFTER.match(sql[close_idx + 1 :])
                ):
                    sql = (
                        sql[:i] + " " + sql[i + 1 : close_idx]
                        + " " + sql[close_idx + 1 :]
                    )
                    changed = True
                    break
            i = sql.find("(", i + 1)
    return sql


def _day_int(iso: str) -> str:
    return str((datetime.date.fromisoformat(iso) - EPOCH).days)


_ORDER_BY = re.compile(r"\bORDER\s+BY\b", re.IGNORECASE)
_ITEM_END = re.compile(r"\b(LIMIT|OFFSET|FETCH|ROWS|RANGE|GROUPS)\b|\)", re.IGNORECASE)


def _add_null_ordering(sql: str) -> str:
    """Trino treats NULL as larger than every value (ASC -> NULLS LAST,
    DESC -> NULLS FIRST); sqlite's default is the opposite. Append explicit
    null ordering to every ORDER BY item that lacks one, so LIMIT windows
    select the same rows."""
    out = []
    pos = 0
    while True:
        m = _ORDER_BY.search(sql, pos)
        if m is None:
            out.append(sql[pos:])
            break
        out.append(sql[pos : m.end()])
        i = m.end()
        depth = 0
        item_start = i
        def flush(j):
            item = sql[item_start:j]
            if item.strip() and "nulls" not in item.lower():
                suffix = (
                    " NULLS FIRST" if re.search(r"\bdesc\s*$", item.strip(), re.I)
                    else " NULLS LAST"
                )
                return item.rstrip() + suffix + " "
            return item
        while i < len(sql):
            c = sql[i]
            if c == "(":
                depth += 1
            elif c == ")":
                if depth == 0:
                    break
                depth -= 1
            elif c == "," and depth == 0:
                out.append(flush(i))
                out.append(",")
                item_start = i + 1
            elif depth == 0:
                mm = _ITEM_END.match(sql, i)
                if mm is not None and sql[i] != ")":
                    break
            i += 1
        out.append(flush(i))
        pos = i
    return "".join(out)


def to_sqlite_sql(sql: str) -> str:
    sql = _CAST_DATE.sub(lambda m: _day_int(m.group(1)), sql)
    sql = _DATE_LIT.sub(lambda m: _day_int(m.group(1)), sql)
    # bare 'YYYY-MM-DD' literals compare against integer-day date columns
    sql = _BARE_DATE.sub(lambda m: _day_int(m.group(1)), sql)
    sql = _INTERVAL_GENERIC.sub(lambda m: f"{m.group(1)} {m.group(2)}", sql)
    sql = _DAYS_SUFFIX.sub(lambda m: f"{m.group(1)} {m.group(2)}", sql)
    sql = _CAST_DECIMAL.sub("as REAL", sql)
    sql = _DECIMAL_LIT.sub(lambda m: m.group(1), sql)
    sql = _strip_setop_parens(sql)
    sql = _add_null_ordering(sql)
    return sql


def oracle_rows(con: sqlite3.Connection, canonical_sql: str) -> List[Tuple]:
    cur = con.execute(to_sqlite_sql(canonical_sql))
    return [tuple(r) for r in cur.fetchall()]


# --------------------------------------------------------------------------- #
# comparison
# --------------------------------------------------------------------------- #


def _norm(v):
    if isinstance(v, datetime.date) and not isinstance(v, datetime.datetime):
        return (v - EPOCH).days
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    if isinstance(v, float) and math.isnan(v):
        return None
    if isinstance(v, str):
        return v.rstrip()  # CHAR(n) padding differences are not result bugs
    return v


def _close(a, b, tol=1e-6):
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) or isinstance(b, float):
        try:
            fa, fb = float(a), float(b)
        except (TypeError, ValueError):
            return a == b
        if abs(fa - fb) <= max(tol, tol * abs(fb)):
            return True
        # Trino decimal semantics round avg/division results (HALF_UP) to
        # the result scale; sqlite computes REAL throughout. Accept ONLY
        # when the engine value is itself a k-decimal number and the
        # difference is within half an ulp at that scale (so 123.44 vs a
        # true 123.40 still fails — the tolerance never exceeds the scale
        # the engine actually rounded to).
        for k in range(1, 6):
            scaled = fa * 10 ** k
            if abs(scaled - round(scaled)) <= 1e-6:
                return abs(fa - fb) <= 0.5 * 10 ** -k + 1e-9
        return False
    return a == b


def rows_match(
    actual: List[Tuple], expected: List[Tuple], ordered: bool
) -> Optional[str]:
    """None when equal; a short diff string otherwise. Unordered comparison
    sorts both sides by a stable repr key."""
    a = [tuple(_norm(v) for v in r) for r in actual]
    e = [tuple(_norm(v) for v in r) for r in expected]
    if len(a) != len(e):
        return f"row count {len(a)} != {len(e)}"
    if not ordered:
        key = lambda r: tuple("\0" if v is None else str(v) for v in r)
        a, e = sorted(a, key=key), sorted(e, key=key)
    for i, (ra, re_) in enumerate(zip(a, e)):
        if len(ra) != len(re_):
            return f"row {i}: arity {len(ra)} != {len(re_)}"
        for j, (va, ve) in enumerate(zip(ra, re_)):
            if not _close(va, ve):
                return f"row {i} col {j}: {va!r} != {ve!r}"
    return None
