"""How a sparsely live page is made dense (ops/kernels.live_indices and
runtime/executor._jit_compact with its callers): a compaction moves rows and
computes nothing, so every case is held to numpy's ``flatnonzero`` and a plain
take, exactly, on every array a page carries."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import kernels as K
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime import executor as E
from trino_tpu.runtime.metrics import REGISTRY
from trino_tpu.runtime.tracing import TRACER
from trino_tpu.spi.page import Column, Page
from trino_tpu.spi.types import (
    BIGINT,
    DOUBLE,
    VARCHAR,
    ArrayType,
    DecimalType,
    MapType,
    RowType,
    VectorType,
)

CAP = 65536  # 32 blocks of 2048; 1024 kept is the index path, 8192 the sort


def _mask(name: str, cap: int = CAP) -> np.ndarray:
    rng = np.random.default_rng(len(name))
    m = np.zeros(cap, dtype=bool)
    if name == "full":
        m[:] = True
    elif name == "first":
        m[0] = True
    elif name == "last":
        m[-1] = True
    elif name == "one_block":  # every live row inside one 2048-row block
        m[5 * 2048 + 7 : 5 * 2048 + 907] = True
    elif name == "one_pct":
        m[rng.choice(cap, cap // 100, replace=False)] = True
    elif name == "quarter":
        m[rng.choice(cap, cap // 4, replace=False)] = True
    elif name == "loaded_half":  # a table's rows fill the front of its page
        m[rng.choice(cap // 2, cap // 50, replace=False)] = True
    else:
        assert name == "empty"
    return m


MASKS = ["empty", "full", "first", "last", "one_block", "one_pct", "quarter", "loaded_half"]


_jit_live_indices = jax.jit(K.live_indices, static_argnums=1)


def _live_indices(mask: np.ndarray, new_cap: int) -> np.ndarray:
    return np.asarray(_jit_live_indices(jnp.asarray(mask), new_cap))


class TestLiveIndices:
    @pytest.mark.parametrize("new_cap", [1024, 8192])
    @pytest.mark.parametrize("name", MASKS)
    def test_equals_flatnonzero(self, name, new_cap):
        mask = _mask(name)
        want = np.flatnonzero(mask)[:new_cap]
        got = _live_indices(mask, new_cap)
        assert got.dtype == np.int32 and got.shape == (new_cap,)
        assert np.array_equal(got[: len(want)], want)
        # past the live count: the capacity, which is no row
        assert np.all(got[len(want) :] == CAP)

    @pytest.mark.parametrize("cap", [1, 255, 257, 2049, 70001])
    @pytest.mark.parametrize("new_cap", [16, 1024])
    def test_capacity_is_no_multiple_of_a_row_or_a_block(self, cap, new_cap):
        mask = np.random.default_rng(cap).random(cap) < 0.03
        mask[-1] = True
        want = np.flatnonzero(mask)[:new_cap]
        got = _live_indices(mask, new_cap)
        assert got.shape == (new_cap,)
        assert np.array_equal(got[: len(want)], want)
        assert np.all(got[len(want) :] == cap)

    def test_both_branches_ran(self):
        # the parametrised sizes above sit on either side of the choice
        assert 1024 * K.LIVE_INDEX_SHARE <= CAP < 8192 * K.LIVE_INDEX_SHARE

    def test_no_sort_and_no_scatter_over_the_page(self):
        text = str(jax.make_jaxpr(lambda a: K.live_indices(a, 1024))(jnp.zeros(CAP, bool)))
        assert " sort[" not in text
        # the one scatter places the 256 rows' starts, not the page's entries
        assert text.count(" = scatter") == 1 and "i32[256,1]" in text


def _layout_page(layout: str, cap: int, active: np.ndarray) -> Page:
    """A page of ``cap`` rows in one of the layouts a page carries, NULLs
    among the live rows, every value distinct from its neighbours'."""
    rng = np.random.default_rng(cap)
    valid = rng.random(cap) < 0.8
    i64 = rng.integers(-(2**62), 2**62, cap)
    if layout == "flat":
        words = ["alpha", "beta", "gamma", "delta", None]
        cols = (
            Column.from_numpy(BIGINT, i64, valid),
            Column.from_numpy(DOUBLE, rng.random(cap), rng.random(cap) < 0.9),
            Column.from_strings([words[i % 5] for i in range(cap)], VARCHAR),
            Column.from_numpy(DecimalType(12, 2), i64 // 2**20),
        )
    elif layout == "lanes":
        limbs = np.stack([i64, rng.integers(0, 2**62, cap)], axis=1)
        cols = (
            Column(DecimalType(38, 2), jnp.asarray(limbs), jnp.asarray(valid)),
            Column(VectorType(dimension=4), jnp.asarray(rng.random((cap, 4))), jnp.asarray(valid)),
            Column.from_numpy(BIGINT, i64),
        )
    else:
        assert layout == "nested"
        arrays = [
            None if i % 7 == 0 else [i, None, i + 2][: i % 4] for i in range(cap)
        ]
        maps = [None if i % 5 == 0 else {f"k{i % 3}": i} for i in range(cap)]
        rows = [None if i % 11 == 0 else (i, f"s{i % 4}") for i in range(cap)]
        cols = (
            Column.from_nested(ArrayType(element=BIGINT), arrays),
            Column.from_nested(MapType(key=VARCHAR, value=BIGINT), maps),
            Column.from_nested(RowType(fields=(("a", BIGINT), ("b", VARCHAR))), rows),
            Column.from_numpy(BIGINT, i64, valid),
        )
    return Page(cols, jnp.asarray(active))


def _assert_compacted(page: Page, out: Page, new_cap: int) -> None:
    """Every array of ``out`` holds the live rows of ``page`` in row order."""
    live = np.flatnonzero(np.asarray(page.active))[:new_cap]
    mask = np.asarray(out.active)
    assert mask.shape == (min(new_cap, page.capacity),)
    assert mask[: len(live)].all() and not mask[len(live) :].any()
    before = jax.tree_util.tree_leaves(page.columns)
    after = jax.tree_util.tree_leaves(out.columns)
    assert len(before) == len(after)
    for b, a in zip(before, after):
        assert a.dtype == b.dtype and a.shape[1:] == b.shape[1:]
        assert np.array_equal(np.asarray(a)[: len(live)], np.asarray(b)[live])
    for b, a in zip(page.columns, out.columns):
        assert a.type == b.type and a.dictionary is b.dictionary


class TestJitCompact:
    @pytest.mark.parametrize("name", ["one_pct", "one_block", "empty", "first", "last"])
    @pytest.mark.parametrize("layout", ["flat", "lanes", "nested"])
    def test_sparse_pages_take_the_index_path(self, layout, name):
        cap = 16384 if layout == "nested" else CAP
        page = _layout_page(layout, cap, _mask(name, cap))
        assert E._compact_path(1024, page) == "index"
        _assert_compacted(page, E._jit_compact(1024, page), 1024)

    @pytest.mark.parametrize("name", ["quarter", "full"])
    @pytest.mark.parametrize("layout,path", [("flat", "sort"), ("lanes", "index"), ("nested", "index")])
    def test_dense_targets(self, layout, path, name):
        cap = 16384
        page = _layout_page(layout, cap, _mask(name, cap))
        assert E._compact_path(cap, page) == path
        _assert_compacted(page, E._jit_compact(cap, page), cap)

    def test_the_two_paths_agree_on_the_rows_kept(self):
        page = _layout_page("flat", CAP, _mask("one_pct"))
        n = int(np.asarray(page.active).sum())
        by_index = E._jit_compact(1024, page)
        # the parent's program: one stable sort that carries every column
        key = (~page.active).astype(jnp.int8)
        payloads = [a for c in page.columns for a in (c.data, c.valid)]
        _, by_sort = K.cosort([key], payloads + [page.active])
        for got, want in zip(jax.tree_util.tree_leaves(by_index.columns), by_sort):
            assert np.array_equal(np.asarray(got)[:n], np.asarray(want)[:n])
        assert by_index.to_pylist() == page.to_pylist()

    def test_decoded_rows_of_every_layout(self):
        for layout in ("lanes", "nested"):
            page = _layout_page(layout, 16384, _mask("loaded_half", 16384))
            assert E._jit_compact(1024, page).to_pylist() == page.to_pylist()

    def test_a_target_above_the_capacity_is_cut_to_it(self):
        page = _layout_page("flat", 64, np.arange(64) % 3 == 0)
        out = E._compact(page, 22)  # the capacity class of 22 rows is 1024
        assert out.capacity == 64
        _assert_compacted(page, out, 64)

    def test_the_choice_reads_shapes_only(self):
        flat = _layout_page("flat", CAP, _mask("quarter"))
        assert [E._compact_path(c, flat) for c in (1024, 4096, 8192, CAP)] == [
            "index", "index", "sort", "sort",
        ]
        assert E._compact_path(4096, flat) == E._compact_path(
            4096, _layout_page("flat", CAP, _mask("empty"))
        )


def _compactions(path: str) -> float:
    return REGISTRY.counter(E.COMPACTIONS_COUNTER, {"path": path}).value


class TestCallers:
    def test_maybe_compact_keeps_row_order_and_sorted_by(self):
        order = np.arange(CAP, dtype=np.int64) * 3
        page = Page((Column.from_numpy(BIGINT, order),), jnp.asarray(_mask("one_pct")))
        rel = E.Relation(page, ("k",), sorted_by=("k",))
        before = _compactions("index")
        with TRACER.span("test") as root:
            out = E._maybe_compact(rel)
        assert out.sorted_by == ("k",) and out.symbols == ("k",)
        got = np.asarray(out.page.columns[0].data)[np.asarray(out.page.active)]
        assert np.array_equal(got, order[_mask("one_pct")]) and np.all(np.diff(got) > 0)
        spans = {s.name: s.attributes for s in TRACER.spans(root.trace_id)}
        assert spans["compact"] == {
            "capacity_in": CAP, "live_rows": CAP // 100, "capacity_out": 1024,
            "columns": 1, "path": "index", "gather": "packed", "words": 3,
        }
        assert spans["sync:compact"]["value"] == CAP // 100
        assert _compactions("index") == before + 1

    @pytest.mark.parametrize(
        "cap,live,path,form",
        [
            (1 << 20, 10, "index", "plain"),       # one row in 1,024 of a long page: a gather an array
            (CAP, CAP // 100, "index", "packed"),  # one in 64: the columns' words as one matrix
            (16384, 16384 // 4, "sort", "packed"),
        ],
    )
    def test_the_span_names_the_form_of_the_gather(self, cap, live, path, form):
        mask = np.zeros(cap, dtype=bool)
        mask[np.random.default_rng(cap).choice(cap, live, replace=False)] = True
        page = _layout_page("flat", cap, mask)
        with TRACER.span("test") as root:
            out = E._compact(page, live)
        (span,) = [s.attributes for s in TRACER.spans(root.trace_id) if s.name == "compact"]
        arrays = [a for c in page.columns for a in (c.data, c.valid)]
        gathers, words = K.gather_shape(arrays)
        assert (span["path"], span["gather"], span["words"]) == (path, form, words)
        assert form == K.gather_form(cap, span["capacity_out"], gathers, words)
        # the program is the form the span names: a gather an array, or one of them all
        # beside each double, which rides along as it is
        text = E._jit_compact.lower(span["capacity_out"], page).as_text()
        moved = text.count('"stablehlo.gather"(') - (2 if path == "index" else 0)  # live_indices' own
        doubles = sum(a.dtype == jnp.float64 for a in arrays)
        assert moved == (1 + doubles if form == "packed" else len(arrays))
        _assert_compacted(page, out, span["capacity_out"])

    def test_a_nested_page_packs_its_flat_column_alone(self):
        page = _layout_page("nested", 16384, _mask("one_pct", 16384))
        with TRACER.span("test") as root:
            E._compact(page, 163)
        (span,) = [s.attributes for s in TRACER.spans(root.trace_id) if s.name == "compact"]
        assert (span["gather"], span["words"]) == ("packed", 3)  # its one flat column: a bigint's two words and its mask

    @pytest.mark.parametrize("name", ["quarter", "full"])
    def test_maybe_compact_leaves_a_quarter_live_alone(self, name):
        mask = _mask(name)
        mask[0] = True  # a row more than a quarter
        rel = E.Relation(Page((Column.from_numpy(BIGINT, np.arange(CAP)),), jnp.asarray(mask)), ("k",))
        assert E._maybe_compact(rel) is rel

    def test_force_dense_on_an_interleaved_page_sorts(self):
        cap = 16384
        mask = np.arange(cap) % 5 != 0  # four fifths live, never a prefix
        page = _layout_page("flat", cap, mask)
        rel = E.Relation(page, ("a", "b", "c", "d"), sorted_by=("a",))
        before = _compactions("sort")
        out = E._force_dense(rel)
        assert _compactions("sort") == before + 1
        assert out.sorted_by == ("a",)
        _assert_compacted(page, out.page, cap)
        assert out.page.to_pylist() == page.to_pylist()

    def test_force_dense_keeps_a_dense_prefix(self):
        page = _layout_page("flat", 4096, np.arange(4096) < 1000)
        rel = E.Relation(page, ("a", "b", "c", "d"))
        assert E._force_dense(rel) is rel

    def test_the_spills_partitions_are_the_pages_rows(self, monkeypatch):
        from trino_tpu.ops import repartition as R

        monkeypatch.setenv(R.DEVICE_REPARTITION_ENV, "0")  # the per-partition path
        runner = LocalQueryRunner.tpch(scale=0.01)
        ex = E.PlanExecutor(runner.plan_sql("SELECT 1"), runner.metadata, runner.session)
        cap = 16384
        page = _layout_page("flat", cap, _mask("quarter", cap))
        rel = E.Relation(page, ("a", "b", "c", "d"))
        pid = np.asarray(R.partition_ids(R.hash_key_columns([page.columns[0]]), 4))
        rows = page.to_pylist()
        live = np.flatnonzero(np.asarray(page.active))
        seen = 0
        for p, blob in enumerate(ex._hash_partition_spill(rel, ("a",), 4)):
            part = ex._unspill(blob, rel)
            want = [rows[i] for i, at in enumerate(live) if pid[at] == p]
            assert part.page.to_pylist() == want
            seen += len(want)
        assert seen == len(rows) and ex.spill_count == 4


Q06 = (
    "SELECT sum(l_extendedprice * l_discount), count(*), min(l_quantity), "
    "max(l_extendedprice), avg(l_discount) FROM lineitem "
    "WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1994-03-01' "
    "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"
)


class TestGlobalAggregate:
    @pytest.fixture(scope="class")
    def runner(self):
        return LocalQueryRunner.tpch(scale=0.01)

    def _aggregation(self, runner, sql):
        from trino_tpu.planner.plan import AggregationNode, visit_plan

        plan = runner.plan_sql(sql)
        found = []
        visit_plan(plan.root, lambda n: found.append(n) if isinstance(n, AggregationNode) else None)
        ex = E.PlanExecutor(plan, runner.metadata, runner.session)
        return ex, found[0]

    def test_a_sum_under_the_mask_equals_the_compacted_one(self, runner):
        ex, node = self._aggregation(runner, Q06)
        assert not node.group_keys
        rel = ex.eval(node.source)
        n = int(np.asarray(rel.page.active).sum())
        assert rel.capacity > 8192 and 0 < n * 64 < rel.capacity  # about 1% live
        with TRACER.span("test") as root:
            masked = E.aggregate_relation(rel, node, ex.types)
        names = [s.name for s in TRACER.spans(root.trace_id)]
        assert "compact" not in names and "sync:compact" not in names
        dense = E.Relation(E._compact(rel.page, n), rel.symbols)
        compacted = E.aggregate_relation(dense, node, ex.types)
        assert masked.page.to_pylist() == compacted.page.to_pylist()
        assert masked.page.to_pylist()[0][1] == n

    def test_the_statement_syncs_nothing(self, runner):
        with TRACER.span("test") as root:
            rows = runner.execute(Q06).rows
        names = [s.name for s in TRACER.spans(root.trace_id)]
        assert len(rows) == 1 and "compact" not in names
        assert not [n for n in names if n.startswith("sync:")]

    @pytest.mark.parametrize(
        "select",
        [
            "l_orderkey, sum(l_quantity)",  # grouped: the sort path wants few rows
            "approx_percentile(l_quantity, 0.5)",  # re-sorts the rows
            "array_agg(l_orderkey)",  # lays rows out in lanes
            "arbitrary(l_quantity)",  # scatters
        ],
    )
    def test_who_still_compacts(self, runner, select):
        sql = Q06.replace(Q06[len("SELECT ") : Q06.index(" FROM")], select)
        if select.startswith("l_orderkey"):
            sql += " GROUP BY l_orderkey"
        with TRACER.span("test") as root:
            runner.execute(sql)
        compact = [s for s in TRACER.spans(root.trace_id) if s.name == "compact"]
        assert compact and all(s.attributes["path"] == "index" for s in compact)
