"""DECIMAL(p>18) — the Int128 long-decimal representation, vs exact oracles.

ref: spi/type/Int128.java:23, Int128Math.java, DecimalType MAX_PRECISION 38,
operator/aggregation/DecimalSumAggregation. TPU formulation: two int64 limbs
on a trailing axis (ops/int128.py); aggregation decomposes to four exact
32-bit limb sums at plan time (planner/rules.py
decompose_long_decimal_aggregates).
"""

import decimal
import random

import pytest

from trino_tpu.runtime import LocalQueryRunner

D = decimal.Decimal


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner()


def q(runner, sql):
    return runner.execute(sql).rows


class TestLiteralsAndArithmetic:
    def test_literal_roundtrip(self, runner):
        assert q(runner, "SELECT 12345678901234567890123456.78") == [
            (D("12345678901234567890123456.78"),)
        ]

    def test_add_carries_across_limb(self, runner):
        # 10**20 - 0.01 + 0.01 crosses the 2**64 boundary
        assert q(runner, "SELECT 99999999999999999999.99 + 0.01") == [
            (D("100000000000000000000.00"),)
        ]

    def test_subtract_negative(self, runner):
        assert q(
            runner, "SELECT 1.00 - 99999999999999999999.99"
        ) == [(D("-99999999999999999999998.99").scaleb(0) + D("99999999999999999900000.00"),)] or q(
            runner, "SELECT 1.00 - 99999999999999999999.99"
        ) == [(D("-99999999999999999998.99"),)]

    def test_multiply_exact_128bit(self, runner):
        got = q(runner, "SELECT 12345678901234567890.55 * 1000000000.1")
        assert got == [(D("12345678902469135780673456789.055"),)]

    def test_mixed_short_long(self, runner):
        got = q(
            runner,
            "SELECT CAST(2 AS bigint) * x FROM (VALUES (99999999999999999999.99)) t(x)",
        )
        assert got == [(D("199999999999999999999.98"),)]

    def test_negate_abs(self, runner):
        got = q(
            runner,
            "SELECT abs(x), -x FROM (VALUES (-12345678901234567890.55)) t(x)",
        )
        assert got == [
            (D("12345678901234567890.55"), D("12345678901234567890.55"))
        ]

    def test_random_arithmetic_vs_python(self, runner):
        rng = random.Random(42)
        for _ in range(8):
            a = rng.randrange(-(10**24), 10**24)
            b = rng.randrange(-(10**24), 10**24)
            got = q(runner, f"SELECT {a}.0 + {b}.0, {a}.0 - {b}.0")
            assert got == [(D(a + b), D(a - b))]


class TestComparisonsAndOrdering:
    def test_filter_and_compare(self, runner):
        got = q(
            runner,
            "SELECT x FROM (VALUES (123456789012345678901.5), (2.5), "
            "(-99999999999999999999999.5)) t(x) WHERE x > 100.0",
        )
        assert got == [(D("123456789012345678901.5"),)]

    def test_order_by_long_decimal(self, runner):
        got = q(
            runner,
            "SELECT x FROM (VALUES (123456789012345678901.5), (2.5), "
            "(-99999999999999999999999.5), (CAST(NULL AS decimal(25,1)))) t(x) "
            "ORDER BY x DESC NULLS LAST",
        )
        assert got == [
            (D("123456789012345678901.5"),),
            (D("2.5"),),
            (D("-99999999999999999999999.5"),),
            (None,),
        ]

    def test_group_by_long_decimal_key(self, runner):
        got = q(
            runner,
            "SELECT x, count(*) FROM (VALUES (123456789012345678901.5), "
            "(123456789012345678901.5), (2.5)) t(x) GROUP BY x ORDER BY x",
        )
        assert got == [(D("2.5"), 1), (D("123456789012345678901.5"), 2)]


class TestAggregation:
    def test_sum_beyond_int64(self, runner):
        # 3 * 8e18 overflows int64; the limb decomposition must not
        vals = ",".join(["(8000000000000000000.00)"] * 3)
        got = q(
            runner,
            f"SELECT sum(CAST(x AS decimal(38,2))) FROM (VALUES {vals}) t(x)",
        )
        assert got == [(D("24000000000000000000.00"),)]

    def test_sum_avg_grouped(self, runner):
        got = q(
            runner,
            "SELECT k, sum(CAST(x AS decimal(38,2))), avg(CAST(x AS decimal(38,2))) "
            "FROM (VALUES (1, 1.00), (1, 2.00), (2, 5.55)) t(k, x) "
            "GROUP BY k ORDER BY k",
        )
        assert got == [(1, D("3.00"), D("1.50")), (2, D("5.55"), D("5.55"))]

    def test_sum_nulls_and_empty(self, runner):
        got = q(
            runner,
            "SELECT sum(x) FROM (VALUES (99999999999999999999.99), "
            "(CAST(NULL AS decimal(22,2)))) t(x)",
        )
        assert got == [(D("99999999999999999999.99"),)]
        got = q(
            runner,
            "SELECT sum(x) FROM (VALUES (99999999999999999999.99)) t(x) WHERE x < 0.0",
        )
        assert got == [(None,)]

    def test_min_max_global_and_grouped(self, runner):
        got = q(
            runner,
            "SELECT max(x), min(x) FROM (VALUES (123456789012345678901.5), "
            "(2.5), (-99999999999999999999999.5)) t(x)",
        )
        assert got == [
            (D("123456789012345678901.5"), D("-99999999999999999999999.5"))
        ]
        got = q(
            runner,
            "SELECT k, max(x), min(x) FROM (VALUES (1, 123456789012345678901.5), "
            "(1, 2.5), (2, -99999999999999999999999.5)) t(k, x) "
            "GROUP BY k ORDER BY k",
        )
        assert got == [
            (1, D("123456789012345678901.5"), D("2.5")),
            (2, D("-99999999999999999999999.5"), D("-99999999999999999999999.5")),
        ]

    def test_random_sums_vs_python(self, runner):
        rng = random.Random(7)
        vals = [rng.randrange(-(10**22), 10**22) for _ in range(40)]
        rows = ",".join(f"({v}.00)" for v in vals)
        got = q(runner, f"SELECT sum(x) FROM (VALUES {rows}) t(x)")
        assert got == [(D(sum(vals)).scaleb(0).quantize(D("0.01")),)]

    def test_distributed_partial_final_split(self, runner):
        # the limb sums must survive the partial/final exchange split
        from trino_tpu.parallel.runner import DistributedQueryRunner

        dist = DistributedQueryRunner.tpch(scale=0.001, n_workers=2)
        got = dist.execute(
            "SELECT sum(CAST(l_extendedprice AS decimal(38,2)) * 1000000000000.0) "
            "FROM lineitem"
        ).rows
        local = LocalQueryRunner.tpch(scale=0.001)
        exp = local.execute(
            "SELECT sum(CAST(l_extendedprice AS decimal(38,2)) * 1000000000000.0) "
            "FROM lineitem"
        ).rows
        assert got == exp
        assert got[0][0] is not None and abs(got[0][0]) > 10**18


class TestCastsAndFunctions:
    def test_cast_long_to_short_and_back(self, runner):
        got = q(
            runner,
            "SELECT CAST(CAST(123456.78 AS decimal(38,2)) AS decimal(10,2))",
        )
        assert got == [(123456.78,)]

    def test_cast_long_to_double_bigint(self, runner):
        got = q(
            runner,
            "SELECT CAST(x AS double), CAST(x AS bigint) FROM "
            "(VALUES (CAST(1234567.49 AS decimal(38,2)))) t(x)",
        )
        assert got == [(1234567.49, 1234567)]

    def test_long_rescale(self, runner):
        got = q(
            runner,
            "SELECT CAST(x AS decimal(38,4)) FROM "
            "(VALUES (99999999999999999999.99)) t(x)",
        )
        assert got == [(D("99999999999999999999.9900"),)]

    def test_case_and_coalesce(self, runner):
        got = q(
            runner,
            "SELECT CASE WHEN x > 0.0 THEN x ELSE -x END, "
            "coalesce(CAST(NULL AS decimal(38,2)), 12345678901234567890123456.78) "
            "FROM (VALUES (-99999999999999999999999.5)) t(x)",
        )
        assert got == [
            (D("99999999999999999999999.5"), D("12345678901234567890123456.78"))
        ]

    def test_out_of_range_narrowing_is_null(self, runner):
        # long -> short casts of unrepresentable values yield NULL, never a
        # silently truncated number (Trino raises; documented deviation)
        got = q(
            runner,
            "SELECT try_like_marker FROM (SELECT CAST(99999999999999999999.99 "
            "AS decimal(18,2)) AS try_like_marker) t",
        )
        assert got == [(None,)]


class TestShortDecimalDecode:
    """DECIMAL(p<=18) decodes to float only while float division is exact."""

    def test_scaled_magnitude_of_2_53_or_more_is_exact(self, runner):
        # TPC-H q1's sum_charge at SF1: decoded through float it came out
        # as ...685326
        rows = q(runner, "SELECT x FROM (VALUES 58957128388.685325, -0.000001, NULL) t(x)")
        assert rows == [(D("58957128388.685325"),), (D("-0.000001"),), (None,)]
        assert all(isinstance(r[0], D) for r in rows[:2])

    def test_below_it_stays_float(self, runner):
        assert q(runner, "SELECT 5.25") == [(5.25,)]
        assert isinstance(q(runner, "SELECT 5.25")[0][0], float)
