"""TPC-H Q1 with the one parameter the specification validates it with
(clause 2.4.1.3: DELTA = 90): Q1's text, columns and reference, and a domain
of that one tuple, so that every seed sends the same statement."""

from benchmark.templates.q01 import COLUMNS, SCANS, SQL, expect, literals  # noqa: F401

DOMAIN = {"delta": [90]}
