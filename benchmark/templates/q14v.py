"""TPC-H Q14 with the one parameter the specification validates it with
(clause 2.4.14.3: DATE = 1995-09-01): Q14's text, columns and reference, and a
domain of that one tuple, so that every seed sends the same statement."""

from benchmark.templates.q14 import COLUMNS, SQL, expect, literals  # noqa: F401

DOMAIN = {"year": [1995], "month": [9]}
