"""Megakernel-plane tests (ops/megakernels.py): the fused hash-join /
partial-agg / repartition-epilogue Pallas kernels under interpret mode on
CPU, bit-identical against the serial op-chain oracle.

Every fused kernel here executes through ``pl.pallas_call(...,
interpret=True)`` (the pallas_interpret=auto resolution on a CPU backend),
so tier-1 exercises the fused path's exact arithmetic against the serial
formulation — the contract ISSUE 12 pins. Launch accounting: a fused
join+agg books ONE device program where the serial walk books two (join
node + aggregation node), asserted below via the device-programs counter.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trino_tpu.ops import megakernels as MK
from trino_tpu.spi.page import Column, Page
from trino_tpu.spi.types import BIGINT, DOUBLE

SCALE = 0.0005


@pytest.fixture(scope="module")
def runner():
    from trino_tpu.runtime import LocalQueryRunner

    return LocalQueryRunner.tpch(scale=SCALE)


def _ab(runner, sql):
    """rows with pallas_fusion off vs on (+ pallas launch delta for on)."""
    runner.session.set("pallas_fusion", False)
    want = runner.execute(sql).rows
    runner.session.set("pallas_fusion", True)
    p0 = MK.pallas_launches()
    got = runner.execute(sql).rows
    dp = MK.pallas_launches() - p0
    runner.session.set("pallas_fusion", False)
    return want, got, dp


class TestFusedJoinShapes:
    """The join-heavy fragment shapes the megakernel plane targets."""

    def test_q5_shape_join_agg_fused(self, runner):
        """Dictionary group key over a join chain: the join->partial-agg
        fusion fires (ONE kernel does build/probe/group-accumulate) and the
        result is bit-identical to the serial chain."""
        want, got, dp = _ab(runner, """
            SELECT n_name, sum(l_extendedprice), count(*)
            FROM lineitem
            JOIN orders ON l_orderkey = o_orderkey
            JOIN customer ON o_custkey = c_custkey
            JOIN nation ON c_nationkey = n_nationkey
            GROUP BY n_name ORDER BY n_name""")
        assert got == want
        assert dp >= 2  # at least probe + expand kernels ran

    def test_q3_shape(self, runner):
        want, got, dp = _ab(runner, """
            SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS rev,
                   o_orderdate, o_shippriority
            FROM customer, orders, lineitem
            WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
              AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'
              AND l_shipdate > DATE '1995-03-15'
            GROUP BY l_orderkey, o_orderdate, o_shippriority
            ORDER BY rev DESC, o_orderdate, l_orderkey LIMIT 10""")
        assert got == want
        assert dp >= 2

    def test_q13_shape_left_join(self, runner):
        want, got, dp = _ab(runner, """
            SELECT c_custkey, count(o_orderkey) AS cnt
            FROM customer LEFT JOIN orders ON c_custkey = o_custkey
            GROUP BY c_custkey ORDER BY cnt DESC, c_custkey LIMIT 10""")
        assert got == want
        assert dp >= 2

    def test_right_join_swaps(self, runner):
        want, got, dp = _ab(runner, """
            SELECT n_name, count(*) FROM orders
            RIGHT JOIN customer ON o_custkey = c_custkey
            JOIN nation ON c_nationkey = n_nationkey
            GROUP BY n_name ORDER BY 1""")
        assert got == want
        assert dp >= 2

    def test_fewer_device_programs_per_fragment(self, runner):
        """The acceptance metric: with fusion on, the join+agg fragment
        dispatches STRICTLY fewer device programs (one megakernel node
        program replaces the join-node + aggregation-node programs)."""
        from trino_tpu.runtime.device_scheduler import program_launches

        sql = """
            SELECT n_name, sum(o_totalprice)
            FROM orders JOIN customer ON o_custkey = c_custkey
            JOIN nation ON c_nationkey = n_nationkey
            GROUP BY n_name ORDER BY n_name"""
        runner.session.set("pallas_fusion", False)
        runner.execute(sql)  # warm
        n0 = program_launches()
        want = runner.execute(sql).rows
        serial = program_launches() - n0
        runner.session.set("pallas_fusion", True)
        runner.execute(sql)  # warm
        n0 = program_launches()
        got = runner.execute(sql).rows
        fused = program_launches() - n0
        runner.session.set("pallas_fusion", False)
        assert got == want
        assert fused < serial, (fused, serial)


class TestMegakernelEdgeCases:
    def test_null_sentinel_keys(self, runner):
        """NULL join keys never match (inner) and left-join rows with NULL
        keys still emit their null-padded row — on both paths."""
        sql_inner = """
            SELECT a.x, b.y FROM
              (SELECT IF(t % 3 = 0, CAST(NULL AS BIGINT), t) AS x
               FROM UNNEST(sequence(1, 200)) AS u(t)) a
            JOIN
              (SELECT IF(t % 5 = 0, CAST(NULL AS BIGINT), t) AS k, t AS y
               FROM UNNEST(sequence(1, 300)) AS v(t)) b
            ON a.x = b.k ORDER BY 1, 2"""
        want, got, dp = _ab(runner, sql_inner)
        assert got == want
        assert dp >= 2
        sql_left = sql_inner.replace("JOIN", "LEFT JOIN", 1)
        want, got, dp = _ab(runner, sql_left)
        assert got == want
        assert dp >= 2

    def test_dictionary_encoded_keys(self, runner):
        """Varchar join keys translate probe codes through the build
        dictionary LUT; probe values absent from the build vocabulary are
        real-but-unmatched, same as the serial path."""
        want, got, dp = _ab(runner, """
            SELECT c_name, n.n_name
            FROM customer c JOIN nation n ON c.c_mktsegment = n.n_name
            ORDER BY 1, 2""")
        # c_mktsegment values never appear in nation names: empty result
        # on both paths, via the LUT miss (-1 codes), not via luck
        assert got == want == []
        want, got, dp = _ab(runner, """
            SELECT s.n_name, count(*)
            FROM (SELECT n_name FROM nation) s
            JOIN (SELECT n_name FROM nation WHERE n_regionkey > 1) t
              ON s.n_name = t.n_name
            GROUP BY s.n_name ORDER BY 1""")
        assert got == want
        assert dp >= 2

    def test_empty_build_and_probe_sides(self, runner):
        for pred_side in ("o_custkey < 0", "c_custkey < 0"):
            want, got, dp = _ab(runner, f"""
                SELECT o_orderkey, c_name
                FROM (SELECT * FROM orders WHERE {pred_side.startswith('o') and pred_side or 'TRUE'}) o
                JOIN (SELECT * FROM customer WHERE {pred_side.startswith('c') and pred_side or 'TRUE'}) c
                ON o.o_custkey = c.c_custkey ORDER BY 1 LIMIT 5""")
            assert got == want == []
            assert dp >= 2

    def test_capacity_class_boundary_shapes(self):
        """Probe/build capacities pinned to the pow2/capacity-class edges
        from capstore.capacity_class (1024 exact, 1025 promotes, 4096
        exact): the fused probe+expand kernels against the serial
        _jit_join_match/_jit_join_expand oracle at the kernel level —
        padding and inactive rows ride through both paths identically."""
        import trino_tpu.runtime.executor as E
        from trino_tpu.runtime.capstore import capacity_class

        assert capacity_class(1024) == 1024 and capacity_class(1025) == 4096
        rng = np.random.default_rng(7)
        for n, m in ((1023, 1024), (1024, 1025), (1025, 4096), (4096, 512)):
            pk = jnp.asarray(rng.integers(0, 300, n))
            pv = jnp.asarray(rng.random(n) < 0.9)
            pa = jnp.asarray(rng.random(n) < 0.8)
            bk = jnp.asarray(rng.integers(0, 300, m))
            bv = jnp.asarray(rng.random(m) < 0.9)
            ba = jnp.asarray(rng.random(m) < 0.7)
            probe_page = Page(
                (Column(BIGINT, pk, pv),
                 Column(DOUBLE, jnp.asarray(rng.random(n)), jnp.ones(n, bool))),
                pa,
            )
            build_page = Page(
                (Column(BIGINT, bk, bv),
                 Column(BIGINT, jnp.asarray(rng.integers(0, 99, m)),
                        jnp.ones(m, bool))),
                ba,
            )
            pkeys, bkeys, luts = ((pk, pv),), ((bk, bv),), (None,)
            emit, count, lo, perm_b, _ = E._jit_join_match(
                False, pkeys, bkeys, luts, pa, ba
            )
            cap = E._round_capacity(max(int(jnp.sum(emit)), 1))
            want = E._jit_join_expand(
                cap, False, emit, count, lo, perm_b, probe_page, build_page
            )
            pr = MK.probe_phase(pkeys, bkeys, luts, pa, ba, False, True)
            assert pr is not None, (n, m)
            got, dest = MK.expand_phase(
                pr, pkeys, bkeys, luts, probe_page, build_page, cap,
                ("pk", "pv_col", "bk", "bpay"), None, None, None, True,
            )
            assert dest is None
            np.testing.assert_array_equal(
                np.asarray(got.active), np.asarray(want.active), str((n, m))
            )
            for gc, wc in zip(got.columns[:2], want.columns[:2]):
                # probe side: identical gathers everywhere (same probe_idx)
                np.testing.assert_array_equal(
                    np.asarray(gc.valid), np.asarray(wc.valid))
                np.testing.assert_array_equal(
                    np.asarray(gc.data), np.asarray(wc.data))
            act = np.asarray(got.active)
            for gc, wc in zip(got.columns[2:], want.columns[2:]):
                # build side: valid masks identical; data compared where
                # valid (unmatched slots gather arbitrary rows on each path)
                np.testing.assert_array_equal(
                    np.asarray(gc.valid), np.asarray(wc.valid))
                sel = act & np.asarray(gc.valid)
                np.testing.assert_array_equal(
                    np.asarray(gc.data)[sel], np.asarray(wc.data)[sel])

    def test_bucket_cap_retry_on_duplicate_heavy_keys(self, runner):
        """> DEFAULT_BUCKET_CAP duplicates per key (3 distinct keys x 120
        build rows each — the orders-status shape, synthetic so the
        interpret-mode probe table stays MBs instead of the GBs the full
        orders x lineitem cross product faults in): the probe phase retries
        at the larger 4x-spaced bucket class (3 launches: probe, retried
        probe, expand), still bit-identical."""
        sql = """
            SELECT b.s, count(*)
            FROM (SELECT t % 3 AS s FROM UNNEST(sequence(1, 360)) AS u(t)) a
            JOIN (SELECT t % 3 AS s FROM UNNEST(sequence(1, 360)) AS w(t)) b
              ON a.s = b.s
            GROUP BY b.s ORDER BY 1
        """
        want, got, dp = _ab(runner, sql)
        assert got == want
        assert dp >= 3

    def test_bucket_skew_falls_back(self, runner, monkeypatch):
        """Pathological skew (table beyond the entry limit) falls back to
        the serial path with the labeled counter ticked — and the query
        still answers correctly."""
        monkeypatch.setattr(MK, "TABLE_ENTRY_LIMIT", 1024)
        f0 = MK.pallas_fallbacks("bucket_skew")
        want, got, _dp = _ab(runner, """
            SELECT count(*)
            FROM orders JOIN lineitem ON o_orderstatus = l_linestatus""")
        assert got == want
        assert MK.pallas_fallbacks("bucket_skew") > f0

    def test_int128_limb_payload_rides_fused_pipeline(self, runner):
        """Long-decimal (int128 two-limb) values through the fused
        join->project->sort-agg pipeline: the limb columns gather/cosort on
        axis 0 exactly like the serial path, and the sum exercises the limb
        accumulator carry on values wider than int64."""
        sql = """
            SELECT o_custkey, sum(CAST(o_totalprice AS DECIMAL(38, 2)) * 100000000)
            FROM orders JOIN customer ON o_custkey = c_custkey
            GROUP BY o_custkey ORDER BY 2 DESC, 1 LIMIT 10"""
        want, got, dp = _ab(runner, sql)
        assert got == want
        assert dp >= 2

    def test_int64_accumulator_wraparound_identity(self, runner):
        """Sums near the int64 edge: fused and serial must wrap identically
        (mod-2^64 accumulation — the limb-recombination contract)."""
        big = (1 << 62) - 1
        sql = f"""
            SELECT b.g, sum(a.v)
            FROM (SELECT t % 5 AS k, {big} - t AS v
                  FROM UNNEST(sequence(1, 100)) AS u(t)) a
            JOIN (SELECT t AS k, t % 2 AS g
                  FROM UNNEST(sequence(0, 4)) AS w(t)) b ON a.k = b.k
            GROUP BY b.g ORDER BY b.g"""
        want, got, dp = _ab(runner, sql)
        assert got == want
        assert dp >= 2


class TestFusedRepartitionEpilogue:
    def _page(self, n=4096, seed=0):
        rng = np.random.default_rng(seed)
        return Page(
            (
                Column(BIGINT, jnp.asarray(rng.integers(0, 500, n)),
                       jnp.asarray(rng.random(n) < 0.9)),
                Column(DOUBLE, jnp.asarray(rng.random(n)),
                       jnp.ones(n, dtype=bool)),
            ),
            jnp.asarray(rng.random(n) < 0.8),
        )

    def test_fused_epilogue_bit_identical(self):
        """hash -> stable cosort -> offsets as ONE kernel == the standalone
        jit epilogue, including NULL-key routing and the inactive tail."""
        from trino_tpu.ops.repartition import _jit_repartition_epilogue

        page = self._page()
        sp, off, cnt = MK.fused_epilogue(page, (0,), 8, interpret=True)
        sp2, off2, cnt2 = _jit_repartition_epilogue(8, (0,), page)
        np.testing.assert_array_equal(np.asarray(off), np.asarray(off2))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt2))
        for c1, c2 in zip(sp.columns, sp2.columns):
            np.testing.assert_array_equal(np.asarray(c1.data), np.asarray(c2.data))
            np.testing.assert_array_equal(np.asarray(c1.valid), np.asarray(c2.valid))
        np.testing.assert_array_equal(np.asarray(sp.active), np.asarray(sp2.active))

    def test_attached_dest_frames_identical(self):
        """A megakernel-attached dest yields the exact frames of the
        standalone hash program, and the attachment is consumed."""
        from trino_tpu.ops.repartition import (
            _jit_partition_dest,
            repartition_frames,
        )

        page = self._page(seed=1)
        frames0, counts0 = repartition_frames(page, (0,), 8)
        dest = _jit_partition_dest(8, (0,), page)
        MK.attach_epilogue(page, dest, (0,), 8, keys=("k",))
        frames1, counts1 = repartition_frames(page, (0,), 8)
        assert frames0 == frames1
        assert list(counts0) == list(counts1)
        assert "_megakernel_epilogue" not in page.__dict__

    def test_mismatched_attachment_ignored(self):
        from trino_tpu.ops.repartition import (
            _jit_partition_dest,
            repartition_frames,
        )

        page = self._page(seed=2)
        frames0, _ = repartition_frames(page, (0,), 8)
        MK.attach_epilogue(page, _jit_partition_dest(4, (0,), page), (0,), 4)
        frames1, _ = repartition_frames(page, (0,), 8)  # different spec
        assert frames0 == frames1

    def test_hint_flows_through_projection_to_frames(self, runner):
        """End to end: a repartition_hint on the executor makes the fused
        root compute dest in-kernel, the attachment survives the projection
        rewrap, and the exchange frames are bit-identical to the unhinted
        path."""
        import trino_tpu.sql.parser as P
        from trino_tpu.planner import LogicalPlanner, optimize
        from trino_tpu.ops.repartition import repartition_frames
        from trino_tpu.runtime.executor import PlanExecutor

        sql = ("SELECT o_orderkey, c_name FROM orders "
               "JOIN customer ON o_custkey = c_custkey")
        stmt = P.parse_statement(sql)
        planner = LogicalPlanner(runner.metadata, runner.session)
        plan = optimize(planner.plan(stmt), runner.metadata, runner.session)
        runner.session.set("pallas_fusion", True)
        try:
            ex = PlanExecutor(plan, runner.metadata, runner.session)
            rel = ex.eval(plan.root.source)
            frames0, counts0 = repartition_frames(rel.page, (0,), 4)

            ex2 = PlanExecutor(plan, runner.metadata, runner.session)
            ex2.repartition_hint = ((rel.symbols[0],), 4)
            rel2 = ex2.eval(plan.root.source)
            att = rel2.page.__dict__.get("_megakernel_epilogue")
            assert att and att["n_parts"] == 4
            frames1, counts1 = repartition_frames(rel2.page, (0,), 4)
            assert frames0 == frames1
            assert list(counts0) == list(counts1)
        finally:
            runner.session.set("pallas_fusion", False)


class TestKnobContract:
    def test_knob_off_path_untouched(self, runner, monkeypatch):
        """pallas_fusion off (the default): the megakernel plane is never
        consulted — asserted by poisoning its entry points — and zero
        pallas launches happen. The off path is the HEAD path."""
        def boom(*a, **k):  # pragma: no cover - would fail the test
            raise AssertionError("megakernel path entered with knob off")

        monkeypatch.setattr(MK, "probe_phase", boom)
        monkeypatch.setattr(MK, "expand_phase", boom)
        p0 = MK.pallas_launches()
        runner.session.set("pallas_fusion", False)
        rows = runner.execute("""
            SELECT n_name, count(*) FROM customer
            JOIN nation ON c_nationkey = n_nationkey
            GROUP BY n_name ORDER BY 1""").rows
        assert rows
        assert MK.pallas_launches() == p0

    def test_default_is_off(self, runner):
        assert not runner.session.get("pallas_fusion")

    def test_pallas_interpret_resolution(self):
        from trino_tpu import knobs

        assert knobs.resolve_pallas_interpret("auto", "cpu") is True
        assert knobs.resolve_pallas_interpret("auto", "tpu") is False
        assert knobs.resolve_pallas_interpret("on", "tpu") is True
        assert knobs.resolve_pallas_interpret("off", "cpu") is False

    def test_pallas_aggregation_policy_central(self):
        from trino_tpu import knobs

        assert knobs.resolve_pallas_aggregation("auto") == "off"
        assert knobs.resolve_pallas_aggregation(None) == "off"
        assert knobs.resolve_pallas_aggregation("force") == "tpu"
        assert knobs.resolve_pallas_aggregation("interpret") == "interpret"


@pytest.mark.slow
class TestCorpusBitIdentity:
    def test_tpch_22_corpus_fused_matches_serial(self, runner):
        """Every TPC-H query, fused vs serial, bit-identical rows under
        interpret mode (the full-corpus acceptance sweep)."""
        from tests.tpch_corpus import TPCH_QUERIES

        for name, sql in sorted(TPCH_QUERIES.items()):
            runner.session.set("pallas_fusion", False)
            want = runner.execute(sql).rows
            runner.session.set("pallas_fusion", True)
            got = runner.execute(sql).rows
            runner.session.set("pallas_fusion", False)
            assert got == want, name


class TestCorpusSample:
    """Tier-1 slice of the corpus sweep (the full 22 runs under -m slow):
    the three join-heaviest shapes plus the densest multi-join."""

    @pytest.mark.parametrize("name", ["q03", "q05", "q13", "q21"])
    def test_fused_matches_serial(self, runner, name):
        from tests.tpch_corpus import TPCH_QUERIES

        want, got, _dp = _ab(runner, TPCH_QUERIES[name])
        assert got == want
