"""What the join templates (q03, q05, q10, q18) share: grouped sums in the
reference's arithmetic, dates as the client delivers them, and the check for
rows that tie on an ORDER BY. numpy only; nothing of the program."""

import datetime

import numpy as np

from benchmark import reference as ref

_EPOCH = datetime.date(1970, 1, 1)


def iso(days: int) -> str:
    """A date column's value (days since 1970) as the client delivers it."""
    return (_EPOCH + datetime.timedelta(days=int(days))).isoformat()


def totals(units: np.ndarray, group: np.ndarray, groups: int, num: ref.Arith) -> np.ndarray:
    """Per-group sums of `units` as integer units (int64): exact in int64; in
    float32 accumulated in float32 and rounded to the nearest unit at the end,
    as `Arith.total` does for one sum."""
    out = np.zeros(groups, dtype=num.dtype)
    np.add.at(out, group, units.astype(num.dtype))
    return out if num.dtype == np.int64 else np.rint(out.astype(np.float64)).astype(np.int64)


def discounted(li: dict, rows, num: ref.Arith) -> np.ndarray:
    """l_extendedprice * (1 - l_discount) of lineitem's `rows`, in units of 1e-4."""
    return num.lift(li["l_extendedprice"][rows]) * num.lift(100 - li["l_discount"][rows])


def adjacent_ties(first: int, *keys) -> bool:
    """Whether two neighbours among the first `first` + 1 rows (the rows kept
    and the first row cut) are equal on every one of `keys`, which are in the
    answer's order."""
    n = min(first + 1, len(keys[0]))
    same = np.ones(max(n - 1, 0), dtype=bool)
    for k in keys:
        same &= k[1:n] == k[:n - 1]
    return bool(same.any())
