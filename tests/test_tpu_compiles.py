"""Compiles for the chip that is not attached (the `on-chip-measurement` guide,
section 2): the sort family's programs at the join deployment's shapes, held to
the budget ISSUE 34 set: no program over 90 s. Six programs, all in this one
file; the topology is described inside a fixture and nothing touches the TPU's
library while a module is imported. A compile that passes is not a chip run."""

import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from trino_tpu.runtime import executor as E
from trino_tpu.spi.page import Column, Dictionary, Page
from trino_tpu.spi.types import BIGINT, DATE, VARCHAR, decimal_type

BUDGET_S = 90.0


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache and cannot be read
    # back without a chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _column(type_, rows: int, sharding, dtype, dictionary=None) -> Column:
    return Column(
        type_, jax.ShapeDtypeStruct((rows,), dtype, sharding=sharding),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=sharding), dictionary,
    )


def _compile_seconds(jitted, *args) -> float:
    start = time.perf_counter()
    jitted.lower(*args).compile()
    return time.perf_counter() - start


def test_group_sort_of_seven_keys(one_chip):
    """Q10's GROUP BY (cl. 2.4.10): a bigint, a decimal and five dictionary-coded
    strings, and the revenue they carry, at 131,072 rows."""
    rows = 131072
    names = Dictionary.from_strings([f"s{i:06d}" for i in range(2000)])
    cols = [
        _column(BIGINT, rows, one_chip, jnp.int64),
        _column(VARCHAR, rows, one_chip, jnp.int32, names),
        _column(decimal_type(12, 2), rows, one_chip, jnp.int64),
        _column(VARCHAR, rows, one_chip, jnp.int32, names),
        _column(VARCHAR, rows, one_chip, jnp.int32, names),
        _column(VARCHAR, rows, one_chip, jnp.int32, names),
        _column(VARCHAR, rows, one_chip, jnp.int32, names),
        _column(decimal_type(18, 4), rows, one_chip, jnp.int64),
    ]
    symbols = tuple(f"c{i}" for i in range(len(cols)))
    page = Page(tuple(cols), jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip))
    assert _compile_seconds(E._jit_group_sort, symbols[:7], symbols, symbols, page) < BUDGET_S


def test_dense_compaction_of_four_columns(one_chip):
    """A quarter of 524,288 rows kept (the `sort` path of `_compact_path`)."""
    rows = 524288
    cols = tuple(_column(BIGINT, rows, one_chip, jnp.int64) for _ in range(4))
    page = Page(cols, jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip))
    assert E._compact_path(rows // 4, page) == "sort"
    assert _compile_seconds(E._jit_compact, rows // 4, page) < BUDGET_S


def test_q14s_sparse_compaction_at_sf3(one_chip):
    """One row in 72 of `lineitem`'s stored page kept (PR 35): the `index` path,
    the four columns' eight words as one matrix beside the page."""
    rows, kept = 18_874_368, 262_144
    cols = (
        _column(BIGINT, rows, one_chip, jnp.int64), _column(decimal_type(12, 2), rows, one_chip, jnp.int64),
        _column(decimal_type(12, 2), rows, one_chip, jnp.int64), _column(DATE, rows, one_chip, jnp.int32),
    )
    page = Page(cols, jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip))
    assert E._compact_path(kept, page) == "index"
    start = time.perf_counter()
    compiled = E._jit_compact.lower(kept, page).compile()
    assert time.perf_counter() - start < BUDGET_S
    # the matrix (604 MB), the words cut from 64-bit columns and what `live_indices` holds
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 8 * 4 * rows


@pytest.mark.parametrize(
    "probe,build",
    [
        pytest.param(524288, 131072, id="half_a_million_probes"),
        # the cells' largest shape (PR 37): `lineitem`'s stored page against `orders`', a merge of
        # 24,117,248 rows in three operands, three more on the way back, `perm_b` by a sort
        pytest.param(18_874_368, 5_242_880, id="lineitem_against_orders_at_sf3"),
    ],
)
def test_join_match_of_one_bigint_key(one_chip, probe, build):
    """One bigint key of unknown range: two words and the tag."""

    def key(rows):
        return (jax.ShapeDtypeStruct((rows,), jnp.int64, sharding=one_chip),
                jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip))

    seconds = _compile_seconds(
        E._jit_join_match, False, (key(probe),), (key(build),), (None,),
        key(probe)[1], key(build)[1],
    )
    assert seconds < BUDGET_S


def test_q21s_grouped_min_and_max_at_sf3(one_chip):
    """TPC-H Q21's decorrelated EXISTS (PR 36): min, max and count of l_suppkey
    by l_orderkey over `lineitem`'s stored page, 4.5M groups in 5,242,880
    slots. As scatters the program compiled for 76 s on the chip and each
    extreme ran 2.07 s; read off the sorted segments it holds no scatter, and
    its reads of the slots are three gathers (the key, the counts, the
    extremes), not one for each aggregate, key and validity byte."""
    from trino_tpu.planner.plan import Aggregation

    rows, slots = 18_874_368, 5_242_880
    page = Page(
        (_column(BIGINT, rows, one_chip, jnp.int64), _column(BIGINT, rows, one_chip, jnp.int64)),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip))
    aggregations = tuple(
        (name, Aggregation(function=name, args=("v",), output_type=BIGINT)) for name in ("min", "max", "count"))
    start = time.perf_counter()
    compiled = E._jit_aggregate.lower(
        ("k",), aggregations, ("k", "v"), slots, 0, page,
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    assert time.perf_counter() - start < BUDGET_S
    text = compiled.as_text()
    assert "scatter(" not in text and text.count(" gather(") == 3


def test_grouping_lineitem_by_the_key_it_is_stored_by(one_chip):
    """`_jit_presorted_group` over `lineitem`'s stored page (PR 36): with a flat
    `lax.associative_scan` in `K.last_active_prev` it did not compile inside
    900 s; the doubling loop does in seconds."""
    rows = 18_874_368
    page = Page(
        (_column(BIGINT, rows, one_chip, jnp.int64), _column(BIGINT, rows, one_chip, jnp.int64)),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip))
    assert _compile_seconds(E._jit_presorted_group, ("k",), ("k", "v"), ("k", "v"), page) < BUDGET_S


@pytest.mark.parametrize("rows", [16, 1_048_576])
def test_a_page_holding_a_double_is_sorted(one_chip, rows):
    """TPC-H Q8's answer: (o_year, mkt_share) ORDER BY o_year, where
    mkt_share is a double, in its 16 slots and at a million. Packed into 32-bit
    words, the double's bitcast was refused (UNIMPLEMENTED: "While rewriting
    computation to not contain X64 element types"); it is gathered as it is."""
    from trino_tpu.planner.plan import Ordering
    from trino_tpu.spi.types import DOUBLE

    page = Page(
        (_column(BIGINT, rows, one_chip, jnp.int64), _column(DOUBLE, rows, one_chip, jnp.float64)),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip))
    assert _compile_seconds(E._jit_sort, (Ordering("y"),), ("y", "share"), None, page) < BUDGET_S


def _scatter_updates(text: str) -> list:
    """The number of updates of every scatter in an optimized HLO text: the
    length of its last operand, looked up where that operand is defined."""
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    updates = []
    for operands in re.findall(r" scatter\(([^)]*)\)", text):
        dims = shapes[operands.split(",")[-1].strip().lstrip("%")]
        updates.append(int(dims.split(",")[0]) if dims else 1)
    return updates


@pytest.mark.parametrize(
    "probe,build,out,unique",
    [
        # Q3: `lineitem` x `orders`, 93,314 rows out in 131,072 slots at SF3: the walk of `live_indices`
        pytest.param(16_777_216, 524_288, 131_072, True, id="q3_unique"),
        # Q5: `lineitem` x `supplier`, 3.6M rows out: `live_indices`' sort of the positions
        pytest.param(18_874_368, 8_192, 4_194_304, True, id="q5_unique"),
        # the witness: the general form at Q3's shape scatters over every probe row
        pytest.param(16_777_216, 524_288, 131_072, False, id="q3_general"),
    ],
)
def test_a_unique_expansion_scatters_nothing_over_the_probe(one_chip, probe, build, out, unique):
    """`_jit_join_expand` at the join cell's shapes: in the unique form the
    slots are the emitting rows in order (`K.unique_slots`), and no scatter's
    updates number the probe's rows; the general form's `expand_probe_slots`
    makes one update for every probe row (its `.at[start].max`)."""

    def ints(rows):
        return jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)

    probe_page = Page(
        (_column(BIGINT, probe, one_chip, jnp.int64), _column(decimal_type(12, 2), probe, one_chip, jnp.int64),
         _column(decimal_type(12, 2), probe, one_chip, jnp.int64)),
        jax.ShapeDtypeStruct((probe,), jnp.bool_, sharding=one_chip))
    build_page = Page(
        (_column(BIGINT, build, one_chip, jnp.int64), _column(DATE, build, one_chip, jnp.int32)),
        jax.ShapeDtypeStruct((build,), jnp.bool_, sharding=one_chip))
    start = time.perf_counter()
    compiled = E._jit_join_expand.lower(
        out, unique, ints(probe), ints(probe), ints(probe), ints(build), probe_page, build_page
    ).compile()
    assert time.perf_counter() - start < BUDGET_S
    updates = _scatter_updates(compiled.as_text())
    # the walk of `live_indices` scatters its row starts, one update for 256 rows
    assert (probe not in updates) if unique else (probe in updates)
