"""BENCH_*.json schema audit: every checked-in bench record must say what
hardware, what code, and what schema produced it — and carry result
fingerprints so a perf number can never drift apart from the answer it
measured.

Four requirements per file:

- ``schema_version`` — top-level int >= 1 (>= 3 engages the strict v3
  shape: ``bench`` in the v3 family ("ladder", "hostpath_ab"),
  platform/device labels, per-entry median/MAD/samples/fingerprint — the
  contract tools/bench_regress.py compares).
- ``git_sha`` — non-empty commit label.
- ``platform`` — an accelerator-platform label. The historical files
  disagree on spelling, so ``platform`` or ``backend`` is accepted, at the
  top level or under ``detail``/``result`` (r10+ put a host string in
  "platform" and the jax backend in "backend" — the backend is the label
  that matters).
- ``fingerprints`` — at least one result-fingerprint field anywhere in the
  record (key matching ``fingerprint``, case-insensitive).

The r01–r16 files predate one or more of these rules.  Their gaps are
WAIVED file-by-file in ``LEGACY_EXCEPTIONS`` below — an audit record, not a
loophole: the table is keyed by exact filename, so every NEW file gets full
enforcement, and deleting a legacy file retires its waiver with it.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import FrozenSet, List, Optional

REQUIREMENTS = ("schema_version", "git_sha", "platform", "fingerprints")

_ALL = frozenset(REQUIREMENTS)

# filename -> requirements waived for that file (the round-19 audit of every
# checked-in record; see module docstring). Nothing else is ever waived.
LEGACY_EXCEPTIONS: dict = {
    "BENCH_r01.json": _ALL,
    "BENCH_r04.json": _ALL,
    "BENCH_r05.json": _ALL,
    "BENCH_r06_ooc_ab.json": _ALL,
    "BENCH_r07_exchange_ab.json": _ALL,
    "BENCH_r09_concurrency.json": frozenset({"platform", "fingerprints"}),
    "BENCH_r10_stats_ab.json": frozenset({"git_sha", "fingerprints"}),
    "BENCH_r11_cache_ab.json": frozenset({"fingerprints"}),
    "BENCH_r12_sanity_ab.json": frozenset({"fingerprints"}),
    "BENCH_r14_megakernel_ab.json": _ALL,
    "BENCH_r15_vector_ab.json": frozenset({"fingerprints"}),
}

_FP_KEY = re.compile("fingerprint", re.IGNORECASE)

# the v3 bench family: a schema_version >= 3 record must declare which v3
# bench produced it and satisfy the same strict per-entry shape (median/MAD
# dispersion, raw samples, a result fingerprint) — "ladder" is bench.py
# run_ladder, "hostpath_ab" is bench.py run_hostpath_ab (r19), "fleet_ab"
# is bench.py run_fleet_ab (r20: the multi-process coordinator fleet
# scaling replay)
V3_BENCH_FAMILY = ("ladder", "hostpath_ab", "fleet_ab")


def _has_fingerprint(obj) -> bool:
    if isinstance(obj, dict):
        return any(
            _FP_KEY.search(k) or _has_fingerprint(v) for k, v in obj.items()
        )
    if isinstance(obj, list):
        return any(_has_fingerprint(v) for v in obj)
    return False


def _platform_label(record: dict) -> Optional[str]:
    scopes = [record]
    for key in ("detail", "result"):
        if isinstance(record.get(key), dict):
            scopes.append(record[key])
    for scope in scopes:
        for key in ("backend", "platform"):
            v = scope.get(key)
            if isinstance(v, str) and v:
                return v
    return None


def _ladder_problems(record: dict) -> List[str]:
    """The strict v3+ shape (bench.py run_ladder / run_hostpath_ab)."""
    problems = []
    if record.get("bench") not in V3_BENCH_FAMILY:
        problems.append(
            f"schema_version >= 3 requires bench in {V3_BENCH_FAMILY} "
            f"(got {record.get('bench')!r})"
        )
    for key in ("platform", "device"):
        if not isinstance(record.get(key), str) or not record.get(key):
            problems.append(f"missing hardware label {key!r}")
    if "hardware_verified" not in record:
        problems.append("missing 'hardware_verified'")
    results = record.get("results")
    if not isinstance(results, dict) or not results:
        problems.append("missing 'results'")
        return problems
    for name, r in sorted(results.items()):
        if not isinstance(r, dict):
            problems.append(f"results[{name!r}] not an object")
            continue
        for field in ("median_secs", "mad_secs"):
            if not isinstance(r.get(field), (int, float)):
                problems.append(f"results[{name!r}] missing {field!r}")
        if not isinstance(r.get("samples"), list) or not r.get("samples"):
            problems.append(f"results[{name!r}] missing 'samples'")
        if not isinstance(r.get("fingerprint"), str) or not r.get("fingerprint"):
            problems.append(f"results[{name!r}] missing 'fingerprint'")
    return problems


def validate_record(record, waived: FrozenSet[str] = frozenset()) -> List[str]:
    if not isinstance(record, dict):
        return ["not a JSON object"]
    problems = []
    sv = record.get("schema_version")
    if "schema_version" not in waived and (
        not isinstance(sv, int) or sv < 1
    ):
        problems.append(f"missing/invalid schema_version (got {sv!r})")
    if "git_sha" not in waived and not (
        isinstance(record.get("git_sha"), str) and record.get("git_sha")
    ):
        problems.append("missing git_sha")
    if "platform" not in waived and _platform_label(record) is None:
        problems.append("missing platform label ('platform' or 'backend')")
    if "fingerprints" not in waived and not _has_fingerprint(record):
        problems.append("no result fingerprints anywhere in the record")
    if isinstance(sv, int) and sv >= 3:
        problems.extend(_ladder_problems(record))
    return problems


def validate_file(path: str) -> List[str]:
    name = os.path.basename(path)
    try:
        with open(path) as f:
            record = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{name}: unreadable ({e})"]
    waived = LEGACY_EXCEPTIONS.get(name, frozenset())
    return [f"{name}: {p}" for p in validate_record(record, waived)]


def bench_files(root: Optional[str] = None) -> List[str]:
    root = root or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return sorted(glob.glob(os.path.join(root, "BENCH_*.json")))


def main(argv: Optional[List[str]] = None) -> int:
    paths = list(sys.argv[1:] if argv is None else argv) or bench_files()
    problems: List[str] = []
    for p in paths:
        problems.extend(validate_file(p))
    for p in problems:
        print(p)
    if not problems:
        print(f"bench_schema: {len(paths)} file(s) ok")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
