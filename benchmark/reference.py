"""The plain reference's tools: the population's columns on the host, exact
integer arithmetic (or float32, for the control), and the comparison that
decides `correct`. Nothing here imports the program; numpy only.

A template's `expect(host, params, num)` evaluates its statement over these
columns with `num`, an `Arith`: `EXACT` is the reference, `FLOAT32` is the
control (the same evaluation with every decimal product and sum carried in
float32, the nearest precision below the exact int64 and float64 that the
configuration states, and the TPU's native float).
"""

from __future__ import annotations

import datetime
import math
from decimal import Decimal

import numpy as np

from benchmark import population

_EPOCH = datetime.date(1970, 1, 1)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def add_months(iso_date: str, months: int) -> str:
    d = datetime.date.fromisoformat(iso_date)
    month = d.month - 1 + months
    return d.replace(year=d.year + month // 12, month=month % 12 + 1).isoformat()


def host_columns(scale: float, wanted: dict) -> dict:
    """{table: {column: array}} for `wanted` = {table: [columns]}, in the
    generator's encoding: decimals as integer cents, dates as days since
    1970, strings as codes into the sorted vocabulary."""
    out = {}
    for table, columns in wanted.items():
        base = population.row_count("orders" if table == "lineitem" else table, scale)
        n_chunks = -(-base // population.canonical_chunk_rows(base))
        pieces = {c: [] for c in columns}
        for split in range(n_chunks):  # chunk by chunk: only `columns` are kept
            data = population.generate_split(table, scale, split, n_chunks)
            for c in columns:
                pieces[c].append(data.columns[c])
        out[table] = {c: np.concatenate(v) for c, v in pieces.items()}
    return out


class Arith:
    """How decimal products and sums are carried. Operands are integer units
    (cents, or cents times 100 - discount, ...)."""

    def __init__(self, name: str, dtype):
        self.name, self.dtype = name, dtype

    def lift(self, units: np.ndarray) -> np.ndarray:
        return units.astype(self.dtype)

    def total(self, values: np.ndarray) -> int:
        """Sum as integer units: exact in int64; in float32 numpy's pairwise
        sum, rounded to the nearest unit at the end."""
        if len(values) == 0:
            return 0
        return int(round(float(values.sum(dtype=self.dtype)))) if self.dtype != np.int64 \
            else int(values.sum(dtype=np.int64))


EXACT = Arith("exact", np.int64)
FLOAT32 = Arith("float32", np.float32)


def dec(units: int, scale: int) -> Decimal:
    return Decimal(int(units)).scaleb(-scale)


def dec_avg(total: int, count: int) -> int:
    """A decimal avg keeps the scale and rounds half up."""
    return (2 * int(total) + count) // (2 * count)


def lookup(keys: np.ndarray, probe: np.ndarray):
    """Positions of `probe` in the strictly ascending `keys`, and which were found."""
    if len(keys) > 1 and not (np.diff(keys) > 0).all():
        raise ValueError("join keys are not strictly ascending")
    if len(keys) == 0:
        return np.zeros(len(probe), np.int64), np.zeros(len(probe), bool)
    pos = np.minimum(np.searchsorted(keys, probe), len(keys) - 1)
    return pos, keys[pos] == probe


def as_client(rows: list) -> list:
    """Reference rows in the form the client delivers: decimals as strings."""
    return [[format(v, "f") if isinstance(v, Decimal) else v for v in row] for row in rows]


# ---------------------------------------------------------------- comparison

DOUBLE_REL_LIMIT = 1e-9  # PERF.md, "How correct is decided": readings and limit


class Comparison:
    """Every number that decides `correct`, each beside its limit. Exact cells
    (integers, strings, decimals) must be equal; a double may lie within
    DOUBLE_REL_LIMIT of the reference, relative to max(1, |reference|)."""

    LIMITS = {
        "unanswered": 0,          # statements that failed or never returned rows
        "statements_wrong_shape": 0,   # row or column count differs
        "exact_cells_wrong": 0,
        "double_rel_gap": DOUBLE_REL_LIMIT,
    }

    def __init__(self):
        self.limits = dict(self.LIMITS)   # a runner may hold a number of its own (`hold`)
        self.values = {k: 0 for k in self.LIMITS}
        self.values["double_rel_gap"] = 0.0
        self.compared = 0
        self.double_gaps = []     # every double's gap, for the control's readings
        self.first_wrong = None

    def unanswered(self, what: str) -> None:
        self.values["unanswered"] += 1
        self._note(what)

    def hold(self, name: str, value, limit, what: str = None) -> None:
        """One more number beside its limit, from outside the rows: a runner's
        guarantee (`runners/<name>.py`: `check`)."""
        self.values[name], self.limits[name] = value, limit
        if value > limit:
            self._note(what or f"{name} {value} over its limit {limit}")

    def _note(self, what: str) -> None:
        if self.first_wrong is None:
            self.first_wrong = what[:400]

    def rows(self, label: str, got: list, want: list, want_as_client: list = None) -> bool:
        """Whether `got` is right. `got` is as the client received it: decimals
        as strings, doubles as floats, integers and strings as themselves.
        `want_as_client`, where given, is `as_client(want)`: an answer equal to
        it cell for cell (a sort's millions of rows) needs no second look."""
        self.compared += 1
        if want_as_client is not None and got == want_as_client:
            self.double_gaps.extend(0.0 for row in want for w in row if isinstance(w, float))
            return True
        if len(got) != len(want) or any(len(g) != len(w) for g, w in zip(got, want)):
            self.values["statements_wrong_shape"] += 1
            self._note(f"{label}: {len(got)} rows, the reference has {len(want)}")
            return False
        right = True
        for i, (g_row, w_row) in enumerate(zip(got, want)):
            for g, w in zip(g_row, w_row):
                if isinstance(w, float):
                    numeric = isinstance(g, (int, float)) and not isinstance(g, bool)
                    gap = abs(g - w) / max(1.0, abs(w)) if numeric else math.inf
                    gap = gap if gap < 1e300 else 1e300  # NaN and inf too; JSON has neither
                    self.double_gaps.append(gap)
                    self.values["double_rel_gap"] = max(self.values["double_rel_gap"], gap)
                    ok = gap <= DOUBLE_REL_LIMIT
                elif isinstance(w, Decimal):
                    try:
                        ok = isinstance(g, str) and Decimal(g) == w
                    except ArithmeticError:
                        ok = False
                    self.values["exact_cells_wrong"] += not ok
                else:
                    ok = type(g) is type(w) and g == w
                    self.values["exact_cells_wrong"] += not ok
                if not ok:
                    right = False
                    self._note(f"{label} row {i}: got {g!r}, the reference has {w!r}")
        return right

    @property
    def correct(self) -> bool:
        return self.compared > 0 and all(
            self.values[k] <= limit for k, limit in self.limits.items()
        )

    def report(self) -> dict:
        out = {
            k: {"value": self.values[k], "limit": limit} for k, limit in self.limits.items()
        }
        out["statements_compared"] = {"value": self.compared, "at_least": 1}
        if self.first_wrong is not None:
            out["first_wrong"] = self.first_wrong
        return out
