"""Tensor workload plane: VECTOR columns, MXU similarity lowering, fused
top-k, and SQL-surfaced model scoring (ops/tensor.py, ISSUE 13).

Coverage contract (the ugly lanes the issue names explicitly):

- NULL vectors and ALL-NULL pages through scan, similarity, and top-k
- dimension-1 and non-pow2 dimensions
- ties at rank k in the fused top-k — must match the serial oracle's stable
  order BIT-identically
- empty scan partitions
- OOC and FTE execution of a fused top-k query, the FTE one under
  ``task_stall`` chaos
- the plane gated off by default with the off-path byte-identical
- model scoring (linear matmul + GBDT ensemble) against host oracles
"""

import json

import numpy as np
import pytest

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.ops import tensor as T
from trino_tpu.runtime.device_scheduler import program_launches
from trino_tpu.runtime.local import LocalQueryRunner
from trino_tpu.spi.types import VectorType, parse_type, vector_type

SCALE = 0.0005


def _vec_literal(vals):
    return "ARRAY[" + ", ".join(f"CAST({v} AS double)" for v in vals) + "]"


def _rng_rows(rows, dim, null_ids=(), seed=7):
    rng = np.random.RandomState(seed)
    data = np.round(rng.uniform(-1, 1, size=(rows, dim)), 6)
    out = []
    for i in range(rows):
        if i in null_ids:
            out.append((i, None))
        else:
            out.append((i, data[i]))
    return out


def _make_emb(runner, name, rows, dim, null_ids=(), seed=7):
    runner.execute(
        f"CREATE TABLE memory.default.{name} (id bigint, v vector({dim}))"
    )
    entries = _rng_rows(rows, dim, null_ids, seed)
    values = ", ".join(
        f"({i}, NULL)" if v is None else f"({i}, {_vec_literal(v)})"
        for i, v in entries
    )
    runner.execute(f"INSERT INTO memory.default.{name} VALUES {values}")
    return {i: v for i, v in entries}


@pytest.fixture()
def runner():
    r = LocalQueryRunner.tpch(scale=SCALE)
    r.register_catalog("memory", MemoryConnector())
    return r


def _fusion(runner, on: bool):
    runner.session.set("tensor_plane", on)
    runner.session.set("vector_topk_fusion", on)


# --------------------------------------------------------------------------- #
# the type + layout
# --------------------------------------------------------------------------- #


class TestVectorType:
    def test_parse_display_roundtrip(self):
        t = parse_type("vector(8)")
        assert t == VectorType(dimension=8)
        assert t.display() == "vector(8)"
        assert parse_type(t.display()) == t
        assert t.storage_lanes == 8
        assert not t.is_orderable and not t.is_comparable

    def test_bad_dimension(self):
        with pytest.raises(ValueError):
            parse_type("vector(0)")
        with pytest.raises(ValueError):
            parse_type("vector")

    def test_plancodec_roundtrip(self):
        from trino_tpu.runtime import plancodec

        t = vector_type(5)
        assert plancodec.decode(plancodec.encode(t)) == t

    def test_order_by_vector_column_rejected(self, runner):
        _make_emb(runner, "tv", 4, 3)
        with pytest.raises(Exception):
            runner.execute("SELECT id FROM memory.default.tv ORDER BY v")

    def test_serde_v1_roundtrip(self, runner):
        from trino_tpu.runtime.serde import deserialize_page, serialize_page
        from trino_tpu.spi.connector import SchemaTableName

        _make_emb(runner, "ts1", 6, 5, null_ids=(2,))
        table = runner.catalogs.get("memory").table(
            SchemaTableName("default", "ts1")
        )
        page = table.pages[0]
        out = deserialize_page(serialize_page(page))
        assert out.to_pylist() == page.to_pylist()
        col = out.columns[1]
        assert isinstance(col.type, VectorType)
        assert np.asarray(col.data).shape == (6, 5)

    def test_serde_v2_roundtrip(self, runner):
        from trino_tpu.runtime.serde import LazyPageFrame, serialize_page_slices
        from trino_tpu.spi.connector import SchemaTableName

        _make_emb(runner, "ts2", 6, 3, null_ids=(0,))
        table = runner.catalogs.get("memory").table(
            SchemaTableName("default", "ts2")
        )
        page = table.pages[0]
        cols = [
            (c.type, np.asarray(c.data), np.asarray(c.valid), c.dictionary)
            for c in page.columns
        ]
        frames = serialize_page_slices(
            cols, np.asarray([0]), np.asarray([6])
        )
        out = LazyPageFrame(frames[0]).to_page(capacity=8)
        got = out.to_pylist()
        assert got == page.to_pylist()
        assert np.asarray(out.columns[1].data).shape == (8, 3)

    def test_insert_length_mismatch_raises(self, runner):
        runner.execute(
            "CREATE TABLE memory.default.tlen (id bigint, v vector(3))"
        )
        with pytest.raises(Exception) as ei:
            runner.execute(
                "INSERT INTO memory.default.tlen VALUES (1, ARRAY[1.0, 2.0])"
            )
        assert "vector(3)" in str(ei.value)

    def test_cast_array_column_to_vector_null_degradation(self, runner):
        # expression-level CAST has no per-row error channel: a wrong-length
        # or NULL-element array degrades to a NULL row (documented)
        got = runner.execute(
            "SELECT k, cosine_similarity("
            "  CAST(ARRAY[CAST(1.0 AS double),"
            "       IF(k = 1, CAST(NULL AS double), 1.0)] AS vector(2)),"
            "  ARRAY[1.0, 1.0])"
            " FROM (SELECT sequential_number AS k FROM TABLE(sequence(1, 2)))"
            " ORDER BY k"
        ).rows
        assert got[0][1] is None  # NULL element -> NULL vector row
        assert got[1][1] == pytest.approx(1.0)


# --------------------------------------------------------------------------- #
# similarity family correctness
# --------------------------------------------------------------------------- #


class TestSimilarityFunctions:
    @pytest.mark.parametrize("dim", [1, 3, 5, 7, 16])
    def test_against_numpy(self, runner, dim):
        data = _make_emb(runner, f"sim{dim}", 12, dim, null_ids=(4,))
        q = np.round(np.linspace(-0.5, 0.9, dim), 6)
        rows = runner.execute(
            f"SELECT id, dot_product(v, {_vec_literal(q)}),"
            f" cosine_similarity(v, {_vec_literal(q)}),"
            f" l2_distance(v, {_vec_literal(q)}), vector_norm(v)"
            f" FROM memory.default.sim{dim} ORDER BY id"
        ).rows
        for rid, dot, cos, l2, norm in rows:
            v = data[rid]
            if v is None:
                assert dot is None and cos is None and l2 is None and norm is None
                continue
            assert dot == pytest.approx(float(v @ q), rel=1e-12)
            assert cos == pytest.approx(
                float(v @ q) / (np.linalg.norm(v) * np.linalg.norm(q)),
                rel=1e-9,
            )
            assert l2 == pytest.approx(float(np.linalg.norm(v - q)), rel=1e-12)
            assert norm == pytest.approx(float(np.linalg.norm(v)), rel=1e-12)

    def test_vector_vector_rowwise(self, runner):
        # two vector COLUMNS (the embedding-join shape): einsum path
        runner.execute(
            "CREATE TABLE memory.default.pair (id bigint, a vector(3), b vector(3))"
        )
        runner.execute(
            "INSERT INTO memory.default.pair VALUES"
            " (1, ARRAY[1.0, 0.0, 2.0], ARRAY[3.0, 1.0, 0.5]),"
            " (2, ARRAY[0.0, 0.0, 0.0], ARRAY[1.0, 1.0, 1.0]),"
            " (3, NULL, ARRAY[1.0, 1.0, 1.0])"
        )
        rows = runner.execute(
            "SELECT id, dot_product(a, b), l2_distance(a, b)"
            " FROM memory.default.pair ORDER BY id"
        ).rows
        assert rows[0][1] == pytest.approx(4.0)
        assert rows[1][1] == pytest.approx(0.0)
        assert rows[2][1] is None and rows[2][2] is None

    def test_dimension_mismatch_is_analysis_error(self, runner):
        _make_emb(runner, "mm", 3, 4)
        with pytest.raises(Exception) as ei:
            runner.execute(
                "SELECT dot_product(v, ARRAY[1.0, 2.0]) FROM memory.default.mm"
            )
        assert "do not match" in str(ei.value)

    def test_non_numeric_argument_rejected(self, runner):
        with pytest.raises(Exception):
            runner.execute("SELECT vector_norm('abc')")

    def test_empty_array_literal_is_analysis_error(self, runner):
        _make_emb(runner, "emptyq", 3, 3)
        with pytest.raises(Exception) as ei:
            runner.execute(
                "SELECT dot_product(v, ARRAY[]) FROM memory.default.emptyq"
            )
        assert "dimension" in str(ei.value)

    def test_value_changing_cast_not_folded(self, runner):
        # CAST(ARRAY[1.9] AS array(bigint)) changes element values — the
        # constant fold must NOT see through it (analysis-time fold and the
        # runtime CAST path must never disagree); folding stops and the
        # runtime path answers (here: the unsupported-cast error, the same
        # error the standalone expression raises)
        from trino_tpu.ops.tensor import fold_constant_array
        from trino_tpu.planner.logical_planner import (
            ExpressionTranslator,
            LogicalPlanner,
            Scope,
        )
        from trino_tpu.sql import parse_statement

        planner = LogicalPlanner(runner.metadata, runner.session)
        translator = ExpressionTranslator(planner, Scope([], None))
        stmt = parse_statement(
            "SELECT CAST(ARRAY[1.9, 2.9] AS array(bigint))"
        )
        expr = translator.translate(
            stmt.query.body.select_items[0].expression
        )
        assert fold_constant_array(expr) is None
        # value-preserving target still folds
        stmt2 = parse_statement("SELECT CAST(ARRAY[1.5, 2.5] AS array(double))")
        expr2 = translator.translate(
            stmt2.query.body.select_items[0].expression
        )
        assert fold_constant_array(expr2) == (1.5, 2.5)

    def test_constant_array_establishes_dimension_in_either_order(self, runner):
        # the constant literal can sit in EITHER argument slot and still
        # drive the coercion of a dimension-less array expression
        rows = runner.execute(
            "SELECT dot_product(ARRAY[1.0, 2.0], CAST(v AS array(double)))"
            " FROM (SELECT CAST(ARRAY[3.0, 4.0] AS vector(2)) AS v)"
        ).rows
        assert rows == [(11.0,)]

    def test_non_numeric_array_elements_never_fold(self, runner):
        # strings/temporals must not silently fold to float lanes — the
        # fold and the runtime cast path agree (both reject)
        for sql in (
            "SELECT CAST(ARRAY['a'] AS vector(1))",
            "SELECT dot_product(ARRAY['a'], ARRAY['b'])",
            "SELECT CAST(ARRAY[DATE '2020-01-01'] AS vector(1))",
        ):
            with pytest.raises(Exception) as ei:
                runner.execute(sql)
            assert "could not convert" not in str(ei.value)

    def test_null_literal_needs_dimension(self, runner):
        with pytest.raises(Exception) as ei:
            runner.execute("SELECT vector_norm(NULL)")
        assert "dimension" in str(ei.value)
        assert runner.execute(
            "SELECT vector_norm(CAST(NULL AS vector(4)))"
        ).rows == [(None,)]


# --------------------------------------------------------------------------- #
# fused top-k vs the serial oracle
# --------------------------------------------------------------------------- #


def _topk_sql(table, q, k, desc=True, extra_cols=""):
    order = "DESC" if desc else "ASC"
    return (
        f"SELECT id{extra_cols} FROM memory.default.{table} "
        f"ORDER BY cosine_similarity(v, {_vec_literal(q)}) {order} LIMIT {k}"
    )


class TestFusedTopK:
    def _ab(self, runner, sql):
        """(serial rows+launches, fused rows+launches) for one statement."""
        _fusion(runner, False)
        n0 = program_launches()
        serial = runner.execute(sql).rows
        serial_n = program_launches() - n0
        _fusion(runner, True)
        explain = runner.explain(sql)
        n0 = program_launches()
        fused = runner.execute(sql).rows
        fused_n = program_launches() - n0
        _fusion(runner, False)
        return serial, serial_n, fused, fused_n, explain

    @pytest.mark.parametrize("dim,k", [(1, 3), (5, 4), (7, 10), (16, 1)])
    def test_bit_identity_and_fewer_programs(self, runner, dim, k):
        _make_emb(runner, f"tk{dim}", 24, dim, null_ids=(3, 11))
        q = np.round(np.linspace(0.1, 1.0, dim), 6)
        sql = _topk_sql(f"tk{dim}", q, k)
        serial, serial_n, fused, fused_n, explain = self._ab(runner, sql)
        assert fused == serial  # bit-identical incl. NULL placement
        assert "VectorTopN" in explain
        assert fused_n < serial_n, (fused_n, serial_n)

    def test_ties_at_rank_k_match_serial_stable_order(self, runner):
        # duplicate vectors on both sides of the rank-k boundary: the fused
        # program must pick the SAME winners in the SAME order as the
        # serial stable sort
        runner.execute(
            "CREATE TABLE memory.default.ties (id bigint, v vector(2))"
        )
        vals = []
        for i in range(20):
            v = [1.0, 1.0] if i % 3 == 0 else ([0.5, 0.5] if i % 3 == 1
                                               else [0.1, 0.9])
            vals.append(f"({i}, {_vec_literal(v)})")
        runner.execute(
            "INSERT INTO memory.default.ties VALUES " + ", ".join(vals)
        )
        # cosine of [1,1] and [0.5,0.5] against [1,1] TIE at 1.0 — rank k
        # cuts through the tie class
        sql = _topk_sql("ties", [1.0, 1.0], 9)
        serial, _, fused, _, _ = self._ab(runner, sql)
        assert fused == serial

    def test_all_null_page(self, runner):
        _make_emb(runner, "alln", 6, 3, null_ids=tuple(range(6)))
        sql = _topk_sql("alln", [1.0, 0.0, 0.0], 4)
        serial, _, fused, _, _ = self._ab(runner, sql)
        assert fused == serial
        assert len(serial) == 4  # NULL scores still rank (Trino NULL order)

    def test_k_exceeds_rows_and_limit_zero(self, runner):
        _make_emb(runner, "small", 3, 4)
        for k in (10, 0):
            sql = _topk_sql("small", [1.0, 0.0, 0.0, 0.0], k)
            serial, _, fused, _, _ = self._ab(runner, sql)
            assert fused == serial
            assert len(serial) == (3 if k else 0)

    def test_empty_scan_partition(self, runner):
        runner.execute(
            "CREATE TABLE memory.default.none (id bigint, v vector(3))"
        )
        sql = _topk_sql("none", [1.0, 0.0, 0.0], 5)
        serial, _, fused, _, _ = self._ab(runner, sql)
        assert serial == fused == []

    def test_secondary_order_key_and_score_output(self, runner):
        _make_emb(runner, "sec", 16, 3, null_ids=(2,))
        sql = (
            "SELECT id, dot_product(v, ARRAY[1.0, 2.0, 3.0]) AS s"
            " FROM memory.default.sec ORDER BY s DESC, id ASC LIMIT 6"
        )
        serial, serial_n, fused, fused_n, explain = self._ab(runner, sql)
        assert fused == serial
        assert "VectorTopN" in explain
        assert fused_n < serial_n

    def test_off_path_plan_unchanged(self, runner):
        _make_emb(runner, "off", 8, 3)
        sql = _topk_sql("off", [1.0, 0.0, 0.0], 3)
        _fusion(runner, False)
        base = runner.explain(sql)
        assert "VectorTopN" not in base
        # only the master gate on: fusion must stay off
        runner.session.set("tensor_plane", True)
        assert runner.explain(sql) == base
        runner.session.set("tensor_plane", False)
        runner.session.set("vector_topk_fusion", True)
        assert runner.explain(sql) == base
        runner.session.set("vector_topk_fusion", False)

    def test_unprojected_secondary_key_falls_back_labeled(self, runner):
        # ORDER BY similarity, <column not in the scoring projection>:
        # push_topn_through_project keeps the column in the project in this
        # engine, so force the shape at the rule level instead
        from trino_tpu.planner.optimizer import fuse_vector_topn
        from trino_tpu.planner.plan import (
            Ordering,
            ProjectNode,
            TopNNode,
            ValuesNode,
        )
        from trino_tpu.spi.types import DOUBLE
        from trino_tpu.sql.ir import Call, Constant, Reference

        leaf = ValuesNode(symbols=("a",), rows=((1,),))
        score = Call(
            "vector_norm",
            (Constant(vector_type(2), (1.0, 2.0)),),
            DOUBLE,
        )
        top = TopNNode(
            source=ProjectNode(
                source=leaf, assignments=(("s", score),)
            ),
            count=3,
            orderings=(Ordering("s"), Ordering("a")),  # 'a' unprojected
        )
        before = T.topk_fallbacks("unprojected_order_key")
        _fusion(runner, True)
        try:
            out = fuse_vector_topn(top, runner.session)
        finally:
            _fusion(runner, False)
        assert isinstance(out, TopNNode)  # declined, shape unchanged
        assert T.topk_fallbacks("unprojected_order_key") == before + 1

    def test_composes_with_device_batching_and_result_cache(self, runner):
        # the issue's composition contract: the plane shares the structural
        # fingerprint with the batching + cache planes — all knob
        # combinations must stay bit-identical, and a fused query's result
        # must be servable from the result tier
        _make_emb(runner, "comp", 16, 4, null_ids=(7,))
        sql = _topk_sql("comp", [0.3, 0.1, 0.9, 0.2], 5)
        _fusion(runner, False)
        base = runner.execute(sql).rows
        for batching in (False, True):
            runner.session.set("device_batching", batching)
            for fusion in (False, True):
                _fusion(runner, fusion)
                assert runner.execute(sql).rows == base, (batching, fusion)
        runner.session.set("device_batching", False)
        _fusion(runner, True)
        runner.session.set("result_cache", True)
        assert runner.execute(sql).rows == base
        hit = runner.execute(sql)
        assert hit.rows == base
        assert hit.query_stats.get("cacheHitTier") == "result"
        runner.session.set("result_cache", False)
        _fusion(runner, False)

    def test_fused_over_computed_vectors_from_relational_columns(self, runner):
        # the analytics + vector search composition: vectors assembled from
        # relational columns inside the query, no vector table at all
        sql = (
            "SELECT l_orderkey, l_linenumber FROM lineitem "
            "ORDER BY l2_distance(CAST(ARRAY[CAST(l_quantity AS double),"
            " l_discount, l_tax] AS vector(3)), ARRAY[10.0, 0.05, 0.05]) ASC,"
            " l_orderkey, l_linenumber LIMIT 7"
        )
        serial, serial_n, fused, fused_n, explain = self._ab(runner, sql)
        assert fused == serial
        assert "VectorTopN" in explain
        assert fused_n < serial_n


# --------------------------------------------------------------------------- #
# distributed: staged/FTE (with chaos) + OOC
# --------------------------------------------------------------------------- #

_DIST_SQL = (
    "SELECT l_orderkey, l_linenumber FROM lineitem "
    "ORDER BY cosine_similarity(CAST(ARRAY[CAST(l_quantity AS double),"
    " l_extendedprice, l_discount] AS vector(3)), ARRAY[1.0, 0.5, 0.1]) DESC,"
    " l_orderkey, l_linenumber LIMIT 10"
)


class TestDistributedAndOoc:
    def test_fte_fused_topk_under_task_stall_chaos(self):
        from trino_tpu.parallel.runner import DistributedQueryRunner
        from trino_tpu.runtime.failure import ChaosInjector

        dist = DistributedQueryRunner.tpch(scale=SCALE)
        dist.session.set("retry_policy", "TASK")
        dist.session.set("target_partition_rows", 200)
        expected = dist.execute(_DIST_SQL).rows
        dist.session.set("tensor_plane", True)
        dist.session.set("vector_topk_fusion", True)
        plan = dist.plan_distributed(_DIST_SQL)
        fused_fragments = [
            f for f in plan.fragments
            if "VectorTopN" in type(f.root).__name__
            or any(
                "VectorTopN" in type(n).__name__
                for n in _walk_nodes(f.root)
            )
        ]
        assert fused_fragments, "no fused fragment in the distributed plan"
        assert dist.execute(_DIST_SQL).rows == expected
        with ChaosInjector() as chaos:
            chaos.arm("task_stall", times=1, delay=1.0)
            got = dist.execute(_DIST_SQL).rows
        assert got == expected

    def test_ooc_fused_topk(self):
        from trino_tpu.runtime.ooc import execute_out_of_core

        runner = LocalQueryRunner.tpch(scale=SCALE)
        ref = runner.execute(_DIST_SQL).rows
        for on in (False, True):
            _fusion(runner, on)
            try:
                plan = runner.plan_sql(_DIST_SQL)
                names, page = execute_out_of_core(
                    plan, runner.metadata, runner.session,
                    n_buckets=4, split_batch=2,
                )
            finally:
                _fusion(runner, False)
            act = np.asarray(page.active)
            got = [
                tuple(r) for r, a in zip(page.to_pylist(), act) if a
            ]
            assert got == ref, f"ooc fusion={on} diverged"


def _walk_nodes(node):
    yield node
    for s in node.sources:
        yield from _walk_nodes(s)


@pytest.mark.slow
class TestFusedTopKSweep:
    """The wide sweep (slow tier): larger row counts, the dim x k grid,
    fused vs serial bit-identity + strictly-fewer-launches on every cell."""

    @pytest.mark.parametrize("dim", [1, 2, 7, 32, 64])
    @pytest.mark.parametrize("k", [1, 17, 100])
    def test_sweep(self, dim, k):
        from trino_tpu.spi.connector import ColumnMetadata, SchemaTableName
        from trino_tpu.spi.page import Column, Page
        from trino_tpu.spi.types import BIGINT
        import jax.numpy as jnp

        runner = LocalQueryRunner.tpch(scale=SCALE)
        mem = MemoryConnector()
        runner.register_catalog("memory", mem)
        rows = 5000
        name = SchemaTableName("default", "sweep")
        vtype = vector_type(dim)
        mem.create_table(name, [
            ColumnMetadata("id", BIGINT), ColumnMetadata("v", vtype),
        ])
        rng = np.random.RandomState(dim * 1000 + k)
        vecs = rng.standard_normal((rows, dim))
        valid = np.ones(rows, dtype=np.bool_)
        valid[::97] = False  # sprinkle NULL vectors through the sweep
        mem.insert(name, Page(
            (
                Column.from_numpy(BIGINT, np.arange(rows, dtype=np.int64)),
                Column.from_numpy(vtype, vecs, valid),
            ),
            jnp.ones((rows,), dtype=bool),
        ))
        q = np.round(rng.standard_normal(dim), 6)
        sql = (
            "SELECT id FROM memory.default.sweep "
            f"ORDER BY dot_product(v, {_vec_literal(q)}) DESC LIMIT {k}"
        )
        _fusion(runner, False)
        n0 = program_launches()
        serial = runner.execute(sql).rows
        serial_n = program_launches() - n0
        _fusion(runner, True)
        n0 = program_launches()
        fused = runner.execute(sql).rows
        fused_n = program_launches() - n0
        _fusion(runner, False)
        assert fused == serial
        assert fused_n < serial_n


# --------------------------------------------------------------------------- #
# model scoring
# --------------------------------------------------------------------------- #


class TestModelScoring:
    def _enable(self, runner):
        runner.session.set("tensor_plane", True)
        runner.session.set("model_scoring", True)

    def test_gate_off_by_default(self, runner):
        with pytest.raises(Exception) as ei:
            runner.execute(
                "SELECT * FROM TABLE(linear_score("
                " input => TABLE(SELECT 1 AS x),"
                " features => DESCRIPTOR(x),"
                " weights => ARRAY[1.0], bias => 0.0))"
            )
        assert "disabled" in str(ei.value)

    def test_linear_matches_sql_arithmetic(self, runner):
        self._enable(runner)
        rows = runner.execute(
            "SELECT * FROM TABLE(linear_score("
            " input => TABLE(SELECT n_nationkey, n_regionkey FROM nation),"
            " features => DESCRIPTOR(n_nationkey, n_regionkey),"
            " weights => ARRAY[0.25, -2.0], bias => 3.0))"
        ).rows
        assert len(rows) == 25
        for nk, rk, score in rows:
            assert score == pytest.approx(3.0 + 0.25 * nk - 2.0 * rk, rel=1e-12)

    def test_linear_null_feature_scores_null(self, runner):
        self._enable(runner)
        rows = runner.execute(
            "SELECT * FROM TABLE(linear_score("
            " input => TABLE(SELECT CAST(NULL AS double) AS x, 1.0 AS y),"
            " features => DESCRIPTOR(x, y),"
            " weights => ARRAY[1.0, 1.0], bias => 0.0))"
        ).rows
        assert rows[0][-1] is None

    def test_linear_weight_arity_error(self, runner):
        self._enable(runner)
        with pytest.raises(Exception) as ei:
            runner.execute(
                "SELECT * FROM TABLE(linear_score("
                " input => TABLE(SELECT 1 AS x),"
                " features => DESCRIPTOR(x),"
                " weights => ARRAY[1.0, 2.0], bias => 0.0))"
            )
        assert "weights" in str(ei.value)

    def test_gbdt_matches_host_oracle(self, runner):
        self._enable(runner)
        model = {
            "bias": 0.25,
            "trees": [
                # depth 1 and depth 2 trees: exercises the depth padding
                {"feature": [0], "threshold": [7.5], "leaf": [-1.0, 2.0]},
                {
                    "feature": [1, 0, 0],
                    "threshold": [1.5, 3.5, 11.5],
                    "leaf": [0.1, 0.2, 0.3, 0.4],
                },
            ],
        }
        rows = runner.execute(
            "SELECT * FROM TABLE(gbdt_score("
            " input => TABLE(SELECT n_nationkey, n_regionkey FROM nation),"
            " features => DESCRIPTOR(n_nationkey, n_regionkey),"
            f" model => '{json.dumps(model)}'))"
        ).rows
        assert len(rows) == 25
        spec = T.gbdt_model_spec(model)
        feats = np.asarray([[nk, rk] for nk, rk, _ in rows], dtype=np.float64)
        oracle = T.gbdt_reference_score(spec, feats)
        got = np.asarray([s for _, _, s in rows])
        np.testing.assert_allclose(got, oracle, rtol=1e-12)

    def test_gbdt_bad_model_errors(self, runner):
        self._enable(runner)
        for bad in (
            '{"trees": []}',
            '{"trees": [{"feature": [0, 1], "threshold": [1.0],'
            ' "leaf": [1.0, 2.0]}]}',
            "not json",
        ):
            with pytest.raises(Exception):
                runner.execute(
                    "SELECT * FROM TABLE(gbdt_score("
                    " input => TABLE(SELECT 1 AS x),"
                    " features => DESCRIPTOR(x),"
                    f" model => '{bad}'))"
                )

    def test_scoring_composes_with_fused_topk(self, runner):
        # the full ISSUE pitch: inference + vector search + relational in
        # one statement, one plan
        self._enable(runner)
        runner.session.set("vector_topk_fusion", True)
        sql = (
            "SELECT id, score FROM TABLE(linear_score("
            " input => TABLE(SELECT n_nationkey AS id,"
            "   CAST(n_nationkey AS double) AS x, CAST(n_regionkey AS double)"
            "   AS y FROM nation),"
            " features => DESCRIPTOR(x, y),"
            " weights => ARRAY[1.0, -3.0], bias => 0.0))"
            " ORDER BY score DESC LIMIT 5"
        )
        on = runner.execute(sql).rows
        runner.session.set("vector_topk_fusion", False)
        off = runner.execute(sql).rows
        assert on == off
        scores = [s for _, s in on]
        assert scores == sorted(scores, reverse=True)
