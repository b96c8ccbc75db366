"""Pallas kernel tests (interpret mode on CPU; what Mosaic made of each kernel
on the chip is recorded in CHANGES.md, PR 21)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trino_tpu.ops.pallas_kernels import BLOCK, q6_fused, q6_reference


def _inputs(n, seed=0, null_rate=0.0):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.integers(8000, 10000, n, dtype=np.int32)),
        jnp.asarray(rng.integers(0, 11, n, dtype=np.int32)),
        jnp.asarray(rng.integers(0, 5100, n, dtype=np.int32)),
        jnp.asarray(rng.integers(0, 10**7, n, dtype=np.int32)),
        jnp.asarray((rng.random(n) >= null_rate).astype(np.int32)),
    )


PRED = (8766, 9131, 5, 7, 2400)


class TestQ6Kernel:
    def test_matches_xla(self):
        args = _inputs(BLOCK * 3)
        got = int(q6_fused(*args, *PRED, interpret=True))
        want = int(q6_reference(*args, *PRED))
        assert got == want

    def test_unaligned_length_padded(self):
        args = _inputs(BLOCK * 2 + 12345)
        got = int(q6_fused(*args, *PRED, interpret=True))
        want = int(q6_reference(*args, *PRED))
        assert got == want

    def test_mask_excludes_rows(self):
        args = _inputs(BLOCK, null_rate=0.3)
        got = int(q6_fused(*args, *PRED, interpret=True))
        want = int(q6_reference(*args, *PRED))
        assert got == want

    def test_empty_selection(self):
        args = _inputs(BLOCK)
        # impossible date range selects nothing
        got = int(q6_fused(*args, 0, 0, 5, 7, 2400, interpret=True))
        assert got == 0

    def test_exact_at_int32_product_limit(self):
        # products near int32 max exercise the low/high split recombination
        n = BLOCK
        sd = jnp.full(n, 9000, dtype=jnp.int32)
        disc = jnp.full(n, 7, dtype=jnp.int32)
        qty = jnp.zeros(n, dtype=jnp.int32)
        ep = jnp.full(n, 300_000_000, dtype=jnp.int32)  # 7*3e8 > 2^31? no: 2.1e9 < 2^31-1
        mask = jnp.ones(n, dtype=jnp.int32)
        got = int(q6_fused(sd, disc, qty, ep, mask, *PRED, interpret=True))
        assert got == n * 7 * 300_000_000


from trino_tpu.ops.pallas_kernels import grouped_sum_i32, grouped_sum_i64


class TestGroupedSums:
    def _case(self, n, G, seed=0, lo=-(10**12), hi=10**12):
        rng = np.random.default_rng(seed)
        vals = rng.integers(lo, hi, n, dtype=np.int64)
        gid = rng.integers(0, G, n, dtype=np.int32)
        w = rng.random(n) < 0.8
        want = np.zeros(G, dtype=np.int64)
        np.add.at(want, gid[w], vals[w])
        return jnp.asarray(vals), jnp.asarray(w), jnp.asarray(gid), want

    def test_sum_i64_matches_numpy(self):
        vals, w, gid, want = self._case(BLOCK * 2 + 777, 12)
        got = np.asarray(grouped_sum_i64(vals, w, gid, 12, interpret=True))
        np.testing.assert_array_equal(got, want)

    def test_sum_i64_extreme_magnitudes(self):
        # per-element values near int64 extremes: limb split must stay exact
        # (mod-2^64 wraparound identical to int64 accumulation)
        vals, w, gid, _ = self._case(BLOCK, 5, lo=-(2**62), hi=2**62)
        vnp, wnp, gnp = np.asarray(vals), np.asarray(w), np.asarray(gid)
        want = np.zeros(5, dtype=np.int64)
        np.add.at(want, gnp[wnp], vnp[wnp])
        got = np.asarray(grouped_sum_i64(vals, w, gid, 5, interpret=True))
        np.testing.assert_array_equal(got, want)

    def test_sum_i64_single_group_and_empty_groups(self):
        vals, w, gid, want = self._case(BLOCK, 1)
        got = np.asarray(grouped_sum_i64(vals, w, gid, 1, interpret=True))
        np.testing.assert_array_equal(got, want)
        # group domain larger than any observed gid: tail groups are zero
        got = np.asarray(grouped_sum_i64(vals, w, gid, 7, interpret=True))
        assert got[1:].tolist() == [0] * 6

    def test_sum_i32_count(self):
        rng = np.random.default_rng(3)
        n, G = BLOCK + 99, 9
        gid = rng.integers(0, G, n, dtype=np.int32)
        w = rng.random(n) < 0.5
        want = np.zeros(G, dtype=np.int64)
        np.add.at(want, gid[w], 1)
        got = np.asarray(
            grouped_sum_i32(
                jnp.asarray(w.astype(np.int32)), jnp.asarray(w), jnp.asarray(gid),
                G, interpret=True,
            )
        )
        np.testing.assert_array_equal(got, want)

    def test_sum_i32_negative_values(self):
        rng = np.random.default_rng(4)
        n, G = BLOCK, 4
        vals = rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int64).astype(np.int32)
        gid = rng.integers(0, G, n, dtype=np.int32)
        w = np.ones(n, dtype=bool)
        want = np.zeros(G, dtype=np.int64)
        np.add.at(want, gid, vals.astype(np.int64))
        got = np.asarray(
            grouped_sum_i32(jnp.asarray(vals), jnp.asarray(w), jnp.asarray(gid),
                            G, interpret=True)
        )
        np.testing.assert_array_equal(got, want)


class TestPallasAggregationEngine:
    """Executor integration: pallas_aggregation=interpret must give identical
    results to the XLA direct path on a real GROUP BY query."""

    Q1ISH = (
        "SELECT l_returnflag, l_linestatus, sum(l_quantity), "
        "sum(l_extendedprice * (1 - l_discount)), avg(l_discount), count(*) "
        "FROM lineitem WHERE l_shipdate <= DATE '1998-09-02' "
        "GROUP BY l_returnflag, l_linestatus "
        "ORDER BY l_returnflag, l_linestatus"
    )

    def test_q1_parity(self):
        from trino_tpu.runtime import LocalQueryRunner

        runner = LocalQueryRunner.tpch(scale=0.01)
        runner.session.set("pallas_aggregation", "off")
        want = runner.execute(self.Q1ISH).rows
        runner.session.set("pallas_aggregation", "interpret")
        got = runner.execute(self.Q1ISH).rows
        assert got == want
