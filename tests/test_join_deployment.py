"""The join deployment (`tpch_joins_1chip`: TPC-H Q3, Q5, Q10, Q18) on the CPU at
SF0.01: the statements in the specification's text against the templates' plain
reference, the sort family's kernels against numpy, the shape of the programs
the TPU's compiler is handed (how many sorts, how many operands each), the
operators' span attributes and counters, and the program names the benchmark's
readers look for."""

import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference as ref
from benchmark.layer_metrics import _operators as readers
from benchmark.templates import q03, q05, q10, q18
from benchmark.traffic import draw_params
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.ops import int128 as i128
from trino_tpu.ops import kernels as K
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime import executor as E
from trino_tpu.runtime.metrics import REGISTRY
from trino_tpu.runtime.tracing import TRACER
from trino_tpu.spi.page import Column, Page
from trino_tpu.spi.types import BIGINT, DATE, DOUBLE, INTEGER, decimal_type

SCALE = 0.01
TEMPLATES = {"q03": q03, "q05": q05, "q10": q10, "q18": q18}
TABLES = ("lineitem", "orders", "customer", "supplier", "nation", "region")


@pytest.fixture(scope="module")
def runner():
    r = LocalQueryRunner.tpch(scale=SCALE)
    r.memory = MemoryConnector()
    r.register_catalog("memory", r.memory)
    for table in TABLES:
        r.execute(f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.{r.session.schema}.{table}")
    return r


@pytest.fixture(scope="module")
def host():
    wanted: dict = {}
    for module in TEMPLATES.values():
        for table, columns in module.COLUMNS.items():
            wanted.setdefault(table, [])
            wanted[table] += [c for c in columns if c not in wanted[table]]
    return ref.host_columns(SCALE, wanted)


@pytest.fixture(scope="module")
def client(runner):
    """The served path, as the benchmark drives it: decimals arrive as exact strings."""
    from trino_tpu.client import StatementClient
    from trino_tpu.server import CoordinatorServer

    server = CoordinatorServer(runner).start()
    yield StatementClient(f"http://{server.address}", timeout=600.0)
    server.stop()


def run(runner, module, params):
    return runner.execute(module.SQL.format(schema="memory.default", **module.literals(params)))


# --------------------------------------------- the system against the reference


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 4_000_000_000])
@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_statement_equals_the_plain_reference(client, host, name, seed):
    module = TEMPLATES[name]
    for params in draw_params(module.DOMAIN, random.Random(f"{seed}:params:{name}"), 2):
        want = module.expect(host, params, ref.EXACT)
        comparison = ref.Comparison()
        got = run(client, module, params).rows
        assert comparison.rows(f"{name}{params}", got, want, ref.as_client(want)), comparison.report()
        assert comparison.correct
        assert not module.ties(host, params)  # else the row-for-row comparison is not decided


def test_q18_with_rows_to_return(client, host):
    # at SF0.01 no order passes the specification's quantities: one that some pass
    params = {"quantity": 250}
    want = q18.expect(host, params, ref.EXACT)
    assert 10 < len(want) <= 100
    assert run(client, q18, params).rows == ref.as_client(want)


# ---------------------------------------------------- the kernels against numpy


def _random_key(rng, kind: str, n: int):
    """(values, what numpy orders them by) of one sort key with duplicates."""
    if kind == "small":
        v = rng.integers(-3, 4, size=n).astype(np.int64)
    elif kind == "wide":
        v = rng.integers(-(2**62), 2**62, size=n, dtype=np.int64)
        v[::5] = v[0]
    elif kind == "int32":
        v = rng.integers(-(2**31), 2**31, size=n, dtype=np.int64).astype(np.int32)
        v[::4] = 7
    elif kind == "bool":
        v = rng.random(n) < 0.5
    else:  # a double, zeros of both signs and NaNs among them
        v = rng.normal(size=n)
        v[::7], v[1::7], v[2::7] = 0.0, -0.0, np.nan
    order = np.asarray(K.order_key(jnp.asarray(v))) if v.dtype.kind == "f" else v
    return v, order


KINDS = ["wide", "small", "int32", "double", "bool", "small", "wide"]


@pytest.mark.parametrize("keys", [1, 3, 5, 7])
def test_cosort_is_numpys_stable_lexsort(keys):
    rng = np.random.default_rng(keys)
    n = 3001
    drawn = [_random_key(rng, kind, n) for kind in KINDS[:keys]]
    payloads = [
        rng.integers(0, 100, size=n).astype(np.int32), rng.random(n) < 0.5, rng.normal(size=n),
        rng.integers(-(2**62), 2**62, size=n), rng.integers(-100, 100, size=n).astype(np.int8),
        rng.integers(0, 5, size=(n, 2)), rng.random(n) < 0.1,
    ]
    sorted_keys, sorted_payloads = jax.jit(K.cosort)(
        [jnp.asarray(v) for v, _ in drawn], [jnp.asarray(p) for p in payloads]
    )
    order = np.lexsort([o for _, o in drawn])  # least significant first, as cosort takes them
    for got, (v, _) in zip(sorted_keys, drawn):
        assert got.dtype == v.dtype and np.array_equal(np.asarray(got), v[order], equal_nan=True)
    for got, p in zip(sorted_payloads, payloads):
        assert got.dtype == p.dtype and np.array_equal(np.asarray(got), p[order], equal_nan=True)


def _group_page(rng, keys: int, n: int):
    """A page of `keys` group keys (a bigint, dictionary codes, a date, Int128
    limbs, a double, ...) with nulls, inactive rows and duplicates, and one
    aggregated column; beside it what numpy groups by."""
    cols, by = [], []
    for j in range(keys):
        valid = rng.random(n) > 0.15
        kind = j % 5
        if kind == 0:
            v = rng.integers(0, 6, size=n).astype(np.int64) * (2**33)
            cols.append(Column.from_numpy(BIGINT, v, valid))
        elif kind == 1:
            words = [f"w{k:02d}" for k in rng.integers(0, 9, size=n)]
            c = Column.from_strings([w if ok else None for w, ok in zip(words, valid)])
            v = np.asarray(c.data).astype(np.int64)
            cols.append(c)
        elif kind == 2:
            v = rng.integers(-5, 5, size=n).astype(np.int32)
            cols.append(Column.from_numpy(DATE, v, valid))
        elif kind == 3:
            ints = [int(x) * (2**70) + int(y) for x, y in zip(rng.integers(-2, 3, size=n), rng.integers(0, 2, size=n))]
            cols.append(Column.from_numpy(decimal_type(38, 2), i128.np_from_ints(ints), valid))
            v = np.array(ints, dtype=object)
        else:
            v = rng.integers(-2, 3, size=n).astype(np.float64)
            cols.append(Column.from_numpy(DOUBLE, v, valid))
        by.append((v, valid))
    cols.append(Column.from_numpy(INTEGER, np.arange(n, dtype=np.int32)))
    active = rng.random(n) > 0.2
    return Page(tuple(cols), jnp.asarray(active)), by, active


@pytest.mark.parametrize("keys", [1, 3, 5, 7])
def test_group_sort_orders_and_bounds_groups_as_numpy_does(keys):
    rng = np.random.default_rng(100 + keys)
    n = 2048
    page, by, active = _group_page(rng, keys, n)
    symbols = tuple(f"k{j}" for j in range(keys)) + ("x",)
    out, new_group, num_groups = E._jit_group_sort(symbols[:-1], symbols, symbols, page)
    # numpy: active rows first; key by key nulls before values, values ascending; stable
    sort_by = []
    for v, valid in reversed(by):
        ranks = np.zeros(n, dtype=np.int64)
        distinct = sorted(set(v[valid].tolist()))
        ranks[valid] = [distinct.index(x) for x in v[valid].tolist()]
        sort_by += [ranks, valid]
    order = np.lexsort(sort_by + [~active])
    live = int(active.sum())
    assert np.array_equal(np.asarray(out.active), active[order])
    assert np.array_equal(np.asarray(out.columns[-1].data)[:live], order[:live])
    tuples = [
        tuple((bool(valid[i]), v[i] if valid[i] else None) for v, valid in by) for i in order[:live]
    ]
    starts = [i for i in range(live) if i == 0 or tuples[i] != tuples[i - 1]]
    assert int(num_groups) == len(starts) == len(set(tuples))
    assert np.flatnonzero(np.asarray(new_group)).tolist() == starts


def _random_match(columns: int, n: int = 700, m: int = 300):
    rng = np.random.default_rng(columns)
    probe = [rng.integers(0, 40, size=n).astype(np.int64) for _ in range(columns)]
    build = [rng.integers(0, 40, size=m).astype(np.int64) for _ in range(columns)]
    probe[0][:3], build[0][:3] = K.INT64_MAX, K.INT64_MAX  # the old sentinel is a key like any other
    return build, rng.random(m) > 0.3, probe, rng.random(n) > 0.2, None


def _narrowed_match():
    """A column the dynamic filter narrowed to six bits beside one left at its type's width."""
    build, ba, probe, pa, _ = _random_match(2)
    probe[0][:3], build[0][:3] = 7, 7  # inside [0, 2**6), as every active row has to be
    return build, ba, probe, pa, (6, None)


def _no_active_build():
    build, ba, probe, pa, _ = _random_match(1)
    return build, np.zeros_like(ba), probe, pa, None


def _one_key_twenty_builds():
    build, ba, probe, pa, _ = _random_match(1)
    build[0][:], probe[0][:] = np.arange(len(ba)) + 100, 17
    shared = np.arange(5, len(ba), 14)[:20]
    build[0][shared], ba[shared] = 17, True
    return build, ba, probe, pa, None


def _probes_outside(offset: int):
    build, ba, probe, pa, _ = _random_match(1)
    return build, ba, [probe[0] % 40 + offset], pa, None


def _key_zero_beside_inactive_builds():
    """An inactive build's key is zeroed in the merge: it stands in key 0's run and must not count."""
    build, ba, probe, pa, _ = _random_match(1)
    probe[0][::2], pa[:20] = 0, True
    ba[:150] = False
    build[0][150:160] = [0, 5, 0, 0, 9, 0, 1, 0, 0, 2]
    return build, ba, probe, pa, None


def _extremes():
    build, ba, probe, pa, _ = _random_match(1)
    ends = np.array([K.INT64_MIN, K.INT64_MAX, 0, -1], dtype=np.int64)
    rng = np.random.default_rng(5)
    return [ends[rng.integers(0, 4, size=len(ba))]], ba, [ends[rng.integers(0, 4, size=len(pa))]], pa, None


MATCHES = {
    "one_column": lambda: _random_match(1),
    "two_columns": lambda: _random_match(2),
    "narrowed_column": _narrowed_match,
    "no_active_build": _no_active_build,
    "one_key_twenty_builds": _one_key_twenty_builds,
    "probes_below_every_build": lambda: _probes_outside(-1000),
    "probes_above_every_build": lambda: _probes_outside(1000),
    "key_zero_beside_inactive_builds": _key_zero_beside_inactive_builds,
    "int64_min_beside_max": _extremes,
    # the widest build side whose lo and count share a word: 16 bits each, the word's top bit in use
    "one_full_rank_word": lambda: _random_match(1, n=500, m=65_535),
    # 2 * bits(m) > 32: lo and count travel back as two words (K.rank_words)
    "two_rank_words": lambda: _random_match(1, n=500, m=70_000),
}


@pytest.mark.parametrize("case", sorted(MATCHES))
def test_join_match_counts_and_places_matches_as_numpy_does(case):
    build, ba, probe, pa, key_bits = MATCHES[case]()
    n, m = len(pa), len(ba)
    assert K.rank_words(m) == (2 if case == "two_rank_words" else 1)
    perm_b, lo, hi, count = jax.jit(K.join_match, static_argnums=4)(
        [jnp.asarray(b) for b in build], jnp.asarray(ba), [jnp.asarray(p) for p in probe], jnp.asarray(pa), key_bits
    )
    perm_b, lo, hi, count = np.asarray(perm_b), np.asarray(lo), np.asarray(hi), np.asarray(count)
    assert perm_b.min() >= 0 and perm_b.max() < m and np.array_equal(hi, lo + count)
    for i in range(n):
        same = ba.copy()
        for b, p in zip(build, probe):
            same &= b == p[i]
        want = np.flatnonzero(same).tolist()
        assert count[i] == (len(want) if pa[i] else 0)
        if pa[i]:
            assert perm_b[lo[i]: lo[i] + count[i]].tolist() == want  # ties in row order


@pytest.mark.parametrize("case", sorted(c for c in MATCHES if c not in ("two_columns", "narrowed_column")))
def test_semijoin_mask_is_membership_among_the_active_builds(case):
    (build,), ba, (probe,), pa, _ = MATCHES[case]()
    mask = jax.jit(K.semijoin_mask)(jnp.asarray(build), jnp.asarray(ba), jnp.asarray(probe), jnp.asarray(pa))
    assert np.array_equal(np.asarray(mask), pa & np.isin(probe, build[ba]))


def _gather_zoo(rng, n: int) -> list:
    """A 64-bit integer, a double, a boolean, an 8-bit and a 32-bit integer, a
    float, a two-dimensional array, and more flags than one word holds; the
    double and the two-dimensional array ride along beside the matrix."""
    return [
        rng.integers(-(2**62), 2**62, size=n), rng.normal(size=n), rng.random(n) < 0.5,
        rng.integers(-100, 100, size=n).astype(np.int8), rng.integers(0, 9, size=n).astype(np.int32),
        rng.normal(size=n).astype(np.float32), rng.integers(0, 9, size=(n, 2)),
    ] + [rng.random(n) < 0.5 for _ in range(40)]


def _gathers(lowered_text: str) -> list:
    """The operand type of every gather instruction in a lowered program."""
    return re.findall(r'"stablehlo\.gather"\([^)]*\)[^\n]*?:\s*\(tensor<([^>]*)>', lowered_text)


GATHER_N = 65536
ZOO_SHAPE = (46, 7)  # the zoo's packable arrays (all but the double): 46 gathers of their own, or 7 words
# the fewest rows moved at which the zoo travels packed: about one row in 3,100 of 65,536
ZOO_CROSSOVER = next(m for m in range(1, GATHER_N) if K.gather_form(GATHER_N, m, *ZOO_SHAPE) == "packed")


@pytest.mark.parametrize("order", ["ascending", "shuffled"])
@pytest.mark.parametrize(
    "m", [5, ZOO_CROSSOVER - 1, ZOO_CROSSOVER, ZOO_CROSSOVER + 1, GATHER_N // 72, GATHER_N // 8, GATHER_N, 3 * GATHER_N]
)
def test_gather_rows_is_a_gather_of_each_array(m, order):
    """On both sides of the crossover and at it, whichever form is taken."""
    rng = np.random.default_rng(9)
    arrays = _gather_zoo(rng, GATHER_N)
    assert K.gather_shape(arrays) == ZOO_SHAPE and 5 < ZOO_CROSSOVER < GATHER_N // 72
    idx = rng.integers(0, GATHER_N, size=m).astype(np.int32)
    idx = np.sort(idx) if order == "ascending" else idx
    program = jax.jit(K.gather_rows)
    args = ([jnp.asarray(a) for a in arrays], jnp.asarray(idx))
    moved = program(*args)
    for got, a in zip(moved, arrays):
        assert got.dtype == a.dtype and np.array_equal(np.asarray(got), a[idx])
    # the rule is the program: the matrix and the two arrays that ride along, or a gather an array
    form = K.gather_form(GATHER_N, m, *ZOO_SHAPE)
    assert form == ("plain" if m < ZOO_CROSSOVER else "packed")
    assert len(_gathers(program.lower(*args).as_text())) == (3 if form == "packed" else len(arrays))


def test_each_form_alone_is_a_gather_of_each_array():
    """The packed form where the rule would not take it (five rows of 4,096)
    and the plain one where it would not either."""
    rng = np.random.default_rng(10)
    arrays = _gather_zoo(rng, 4096)
    for idx in (rng.integers(0, 4096, size=5), rng.permutation(4096)):
        moved = jax.jit(K._gather_packed)([jnp.asarray(a) for a in arrays], jnp.asarray(idx))
        for got, a in zip(moved, arrays):
            assert got.dtype == a.dtype and np.array_equal(np.asarray(got), a[idx])
    assert K.gather_rows([], jnp.arange(3)) == []
    (alone,) = K.gather_rows([jnp.asarray(arrays[4])], jnp.arange(4096)[::-1])
    assert np.array_equal(np.asarray(alone), arrays[4][::-1])


GATHER_FORMS = {
    # (n, m, gathers, words): form. The probe's table (chiprun_out/pr35_probe, PR 35) on both sides:
    "q14_compaction": ((18_874_368, 262_144, 11, 8), "packed"),         # one row in 72: 11.5 ms against 42.6
    "q14_as_arrays": ((18_874_368, 262_144, 8, 8), "packed"),           # ISSUE 35's count: eight arrays
    "one_in_288": ((18_874_368, 65_536, 11, 8), "packed"),              # 8.7 against 12.9
    "one_in_256": ((16_777_216, 65_536, 11, 8), "packed"),              # 8.1 against 12.1
    "one_in_1024": ((16_777_216, 16_384, 11, 8), "plain"),              # 7.3 against 5.8
    "one_in_1152": ((18_874_368, 16_384, 11, 8), "plain"),              # 8.1 against 6.0
    "one_in_18432": ((18_874_368, 1_024, 11, 8), "plain"),              # 8.0 against 4.1
    "small_page_one_in_64": ((1_048_576, 16_384, 11, 8), "packed"),     # 1.5 against 2.8
    "small_page_one_in_1024": ((1_048_576, 1_024, 11, 8), "plain"),     # 1.4 against 1.3
    "q3_expansion": ((16_777_216, 131_072, 11, 8), "packed"),           # one row in 128
    "topn": ((524_288, 16, 11, 8), "plain"),                            # ten rows of a long page
    "group_starts": ((18_874_368, 64, 3, 3), "plain"),                  # a few groups of a long page
    "a_sort": ((18_874_368, 18_874_368, 11, 8), "packed"),              # every row moves
    "a_dense_compaction": ((4_194_304, 1_048_576, 22, 15), "packed"),
    "one_word": ((1_048_576, 1_048_576, 1, 1), "plain"),                # nothing to travel with
    "nothing": ((0, 3, 0, 0), "plain"),
}


@pytest.mark.parametrize("case", sorted(GATHER_FORMS))
def test_gather_form_is_what_costs_less_by_the_static_shapes(case):
    shape, form = GATHER_FORMS[case]
    assert K.gather_form(*shape) == form


def test_gather_shape_counts_gathers_and_words():
    S = jax.ShapeDtypeStruct
    q14 = [S((64,), t) for t in (jnp.int64, jnp.bool_, jnp.int64, jnp.bool_, jnp.int64, jnp.bool_, jnp.int32, jnp.bool_)]
    assert K.gather_shape(q14) == (11, 8)
    assert K.gather_shape(q14 + [S((64, 2), jnp.int64)]) == (11, 8)  # limbs are rows already
    assert K.gather_shape([S((64,), jnp.bool_)] * 33) == (33, 2)
    # a double is gathered as it is in either form: the TPU's compiler cannot bitcast it
    assert K.gather_shape([S((64,), jnp.int8), S((64,), jnp.float64)]) == (1, 1)
    assert K.gather_shape([]) == (0, 0)


# -------------------------------- what the TPU's compiler is handed (PERF.md, PR 34)


def _sorts(lowered_text: str) -> list:
    """Operand count of every sort instruction in a lowered program."""
    return [len(re.findall(r"%", m.group(1))) for m in re.finditer(r'"stablehlo\.sort"\(([^)]*)\)', lowered_text)]


def test_q10s_group_sort_is_one_sort_of_three_operands(runner):
    """Seven group keys and fifteen columns were 15 sorts of about 30 operands
    each in one program (ISSUE 34); the compile wall cannot come back unseen."""
    calls = []
    real = E._jit_group_sort._jit

    def spy(*args, **kwargs):
        calls.append(args)
        return real(*args, **kwargs)

    E._jit_group_sort._jit = spy
    try:
        run(runner, q10, {"month": "1993-10"})
    finally:
        E._jit_group_sort._jit = real
    (args,) = calls
    assert len(args[0]) == 7  # the specification's seven keys
    text = E._jit_group_sort.lower(*args).as_text()
    assert _sorts(text) == [3]
    assert text.count("stablehlo.while") == 1  # the passes are a loop, not copies


PROGRAM_SHAPES = {
    # program: (sort instructions at most, operands of all of them together at most)
    # a join's match: the merge of n + m rows (the key's words and the tag), the ranks' way back in
    # probe order (the probe's row number and lo and count: two payload words, or one where the
    # build side is small enough for both to share it), and the builds' places where they are
    # dense among the probes (the positions and an operand nothing reads)
    "join_match": (3, 8),
    "join_match_two_columns": (3, 10),  # two bigint keys of unknown range are four words
    "compact": (1, 2),                  # the positions, and an operand nothing reads (K.live_indices)
    "order_by": (1, 3),
    # a semi-join: the merge, and one word back (the count); no perm_b, so no third sort
    "semijoin": (2, 5),
}


@pytest.mark.parametrize("program", sorted(PROGRAM_SHAPES))
def test_sort_family_programs_hold_few_small_sorts(program):
    n, m = 4096, 1024
    big, small = jnp.zeros(n, jnp.int64), jnp.zeros(m, jnp.int64)
    on, som = jnp.ones(n, bool), jnp.ones(m, bool)
    page = Page(tuple(Column(BIGINT, big, on) for _ in range(4)), on)
    if program == "join_match":
        text = E._jit_join_match.lower(False, ((big, on),), ((small, som),), (None,), on, som).as_text()
    elif program == "join_match_two_columns":
        text = E._jit_join_match.lower(
            False, ((big, on), (big, on)), ((small, som), (small, som)), (None, None), on, som
        ).as_text()
    elif program == "compact":
        text = E._jit_compact.lower(n // 2, page).as_text()
    elif program == "order_by":
        from trino_tpu.planner.plan import Ordering

        orderings = (Ordering("a", False, False), Ordering("b", True, False))
        text = E._jit_sort.lower(orderings, ("a", "b", "c", "d"), 10, page).as_text()
    else:
        col = Column(BIGINT, big, on)
        text = E._jit_semijoin.lower(col, Column(BIGINT, small, som), None, page, som, False).as_text()
    most, operands = PROGRAM_SHAPES[program]
    sorts = _sorts(text)
    assert 1 <= len(sorts) <= most and sum(sorts) <= operands, sorts


def _sort_rows(lowered_text: str) -> list:
    """Rows of every sort instruction in a lowered program (its first operand's length)."""
    return [int(r) for r in re.findall(r'"stablehlo\.sort"\(.*?\}\) : \(tensor<(\d+)x', lowered_text, flags=re.S)]


def test_a_match_sorts_each_probe_row_once():
    """At (n, m) = (4096, 1024) no sort of the match or of the semi-join holds
    more than n + m rows: 5,120 where the merge of [lo-queries, builds,
    hi-queries] held 9,216 (ISSUE 37). The key is a bigint of unknown range,
    and the build side wide enough (m = 70,000) for two rank words as well."""
    for n, m in ((4096, 1024), (4096, 70_000)):
        big, small = jnp.zeros(n, jnp.int64), jnp.zeros(m, jnp.int64)
        on, som = jnp.ones(n, bool), jnp.ones(m, bool)
        page = Page(tuple(Column(BIGINT, big, on) for _ in range(2)), on)
        match = E._jit_join_match.lower(True, ((big, on),), ((small, som),), (None,), on, som).as_text()
        semi = E._jit_semijoin.lower(Column(BIGINT, big, on), Column(BIGINT, small, som), None, page, som, False).as_text()
        assert sorted(_sort_rows(match)) == [n + m] * 3 and _sorts(match) == [3, 1 + K.rank_words(m), 2]
        assert _sort_rows(semi) == [n + m] * 2 and _sorts(semi) == [3, 2]


def test_q14s_compaction_moves_its_columns_in_one_gather():
    """`_jit_compact` lowered for a page of q14's shape at SF3 (shapes alone, no
    data: 18,874,368 rows of a bigint, two decimal(12,2) and a date, 262,144
    kept): the row gather of `live_indices`, its slots' rows, and ONE gather
    of the columns' eight words where there were eight gathers (ISSUE 35)."""
    n, m = 18_874_368, 262_144
    S = jax.ShapeDtypeStruct
    on = S((n,), jnp.bool_)
    page = Page(
        (
            Column(BIGINT, S((n,), jnp.int64), on), Column(decimal_type(12, 2), S((n,), jnp.int64), on),
            Column(decimal_type(12, 2), S((n,), jnp.int64), on), Column(DATE, S((n,), jnp.int32), on),
        ),
        on,
    )
    finds = _gathers(jax.jit(K.live_indices, static_argnums=1).lower(on, m).as_text())
    gathers = _gathers(E._jit_compact.lower(m, page).as_text())
    assert len(finds) == 2 and f"{n // 256}x256xui8" in finds
    assert sorted(gathers) == sorted(finds + [f"8x{n}xi32"])


# --------------------------------------------------------- spans and counters


def _counter(name, **labels):
    return REGISTRY.counter(name, labels).value


CASES = {
    # PR 36: the memory catalog sees that lineitem rises by l_orderkey, the probe's order outlives
    # the joins, and Q3's GROUP BY l_orderkey, ... sorts nothing
    "q03": ({"segment": "BUILDING", "day": 15}, 2, "presorted", 3),
    "q05": ({"region": "ASIA", "year": 1994}, 5, "direct", 1),
    "q10": ({"month": "1993-10"}, 3, "sort", 7),
    "q18": ({"quantity": 250}, 2, "sort", 5),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_operator_spans_and_counters(runner, name):
    params, joins, path, keys = CASES[name]
    before = {
        side: _counter(E.JOIN_ROWS_COUNTER, side=side) for side in ("probe", "build", "out")
    }
    grouped_before = _counter(E.GROUP_ROWS_COUNTER, path=path)
    res = run(runner, TEMPLATES[name], params)
    spans = TRACER.spans(res.trace_id)
    join_spans = [s.attributes for s in spans if s.name == "op:JoinNode"]
    assert len(join_spans) == joins
    for a in join_spans:
        assert {"probe_rows", "build_rows", "rows_out", "capacity_out", "key_types",
                "probe_types", "build_types", "sort_passes"} <= set(a)
        assert 0 <= a["rows_out"] <= a["capacity_out"] and a["probe_rows"] <= a["probe_capacity"]
        assert a["key_types"] and all(t == "bigint" for t in a["key_types"])
        # inner joins: the dynamic filter's range packs the keys into one word
        assert a["key_bits"] and sum(a["key_bits"]) <= 32
    for side in before:
        grown = _counter(E.JOIN_ROWS_COUNTER, side=side) - before[side]
        rows = {"probe": "probe_rows", "build": "build_rows", "out": "rows_out"}[side]
        semis = [s.attributes for s in spans if s.name == "op:SemiJoinNode"]
        assert grown == sum(a[rows] for a in join_spans + semis)
    aggregations = [s.attributes for s in spans if s.name == "op:AggregationNode"]
    top = aggregations[0]  # the statement's own GROUP BY closes first among equals
    assert top["path"] == path and top["keys"] == keys and len(top["key_types"]) == keys
    assert top["rows_in"] <= top["capacity_in"] and top["agg_types"]
    if path == "sort":
        assert top["groups"] >= 1 and top["sort_passes"] >= 1
    assert _counter(E.GROUP_ROWS_COUNTER, path=path) - grouped_before == sum(
        a["rows_in"] for a in aggregations if a["path"] == path
    )
    ordering = [s.attributes for s in spans if s.name in ("op:TopNNode", "op:SortNode")]
    assert len(ordering) == 1
    assert ordering[0]["rows_out"] <= ordering[0]["rows_in"] and ordering[0]["keys"] in (1, 2)
    # every sort-family operator states the passes its sort holds: one for a
    # join's merge sort, the packed key words of a group sort or an ORDER BY
    semis = [s.attributes for s in spans if s.name == "op:SemiJoinNode"]
    assert all(a["sort_passes"] == 1 for a in join_spans + semis)
    assert all(a["sort_passes"] >= 1 for a in ordering + [
        a for a in aggregations if a["path"] == "sort"
    ])
    if name == "q18":
        (semi,) = [s.attributes for s in spans if s.name == "op:SemiJoinNode"]
        assert semi["rows_out"] == semi["probe_rows"] and semi["key_types"] == ["bigint"]
    # the attributes are counts the executor held already: no read was added for them
    syncs = [s.name for s in spans if s.name.startswith("sync:")]
    assert set(syncs) <= {"sync:compact", "sync:join_capacity", "sync:num_groups",
                          "sync:dynamic_filter", "sync:scan_pack", "sync:presorted_check"}


# ------------------------------------------- the names the benchmark's readers use


@pytest.mark.parametrize("program", readers.JOIN_PROGRAMS + readers.GROUP_PROGRAMS)
def test_the_readers_program_names_are_the_executors(program):
    assert program.startswith("jit_")
    function = getattr(E, program[len("jit_"):])
    assert getattr(function, "__wrapped__", function).__name__ == program[len("jit_"):]


def test_the_readers_span_names_are_the_executors():
    from trino_tpu.planner import plan

    for name in readers.JOIN_SPANS + readers.GROUP_SPANS:
        assert name.startswith(E.OP_PREFIX) and hasattr(plan, name[len(E.OP_PREFIX):])


# ------------------------------------- what the plans needed (PR 34, the chip's findings)


def test_the_memory_catalog_bounds_distinct_values_by_the_columns_ranges(runner):
    """Without them the join order of Q5 met customers and suppliers on the
    nation code alone: 108 million rows at SF3, RESOURCE_EXHAUSTED on the chip."""
    from trino_tpu.spi.connector import SchemaTableName

    connector = runner.memory
    customer = connector.table(SchemaTableName("default", "customer"))
    assert customer.rows == 1500 and customer.spans["c_custkey"] == (1, 1500)
    assert customer.spans["c_nationkey"] == (0, 24) and "c_name" not in customer.spans
    from trino_tpu.spi.connector import TableHandle

    stats = connector.metadata().get_table_statistics(
        TableHandle("memory", SchemaTableName("default", "customer"))
    )
    assert stats.row_count == 1500 and stats.column("c_nationkey").ndv == 25
    assert stats.column("c_custkey").ndv == 1500 and stats.column("c_acctbal").ndv is None
    # PR 36: a dictionary-coded column holds no more distinct values than its dictionary has strings
    assert customer.codes["c_mktsegment"] == 5 and stats.column("c_mktsegment").ndv == 5
    assert stats.column("c_name").ndv == 1500
    lineitem = connector.table(SchemaTableName("default", "lineitem"))
    low, high = lineitem.spans["l_orderkey"]
    assert 1 <= low < high <= 15000 * 4 and lineitem.spans["l_suppkey"] == (1, 100)


def test_q5_joins_the_fact_table_before_it_meets_customers_at_sf3_statistics():
    """The order `join_graph_order` gives under the statistics the memory
    catalog holds at SF3 (rows, and a key's range as the bound of its distinct
    values): region, nation, supplier, then lineitem and orders, customer last."""
    from trino_tpu.planner import stats as S
    from trino_tpu.spi.connector import SchemaTableName

    r = LocalQueryRunner.tpch(scale=0.001)
    memory = MemoryConnector()
    r.register_catalog("memory", memory)
    facts = {
        "lineitem": (17993932, {"l_orderkey": (1, 18000000), "l_suppkey": (1, 30000)}),
        "orders": (4500000, {"o_orderkey": (1, 18000000), "o_custkey": (1, 449999), "o_orderdate": (8035, 10440)}),
        "customer": (450000, {"c_custkey": (1, 450000), "c_nationkey": (0, 24)}),
        "supplier": (30000, {"s_suppkey": (1, 30000), "s_nationkey": (0, 24)}),
        "nation": (25, {"n_nationkey": (0, 24), "n_regionkey": (0, 4)}),
        "region": (5, {"r_regionkey": (0, 4)}),
    }
    for table, (rows, spans) in facts.items():
        r.execute(f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.{r.session.schema}.{table} WHERE false")
        stored = memory.table(SchemaTableName("default", table))
        stored.rows, stored.spans = rows, dict(spans)
    orders = []
    real = S.join_graph_order

    def spy(leaves, leaf_conjuncts, equi_edges, estimator):
        order = real(leaves, leaf_conjuncts, equi_edges, estimator)
        orders.append([str(leaves[i].table).split(".")[-1] for i in order])
        return order

    S.join_graph_order = spy
    try:
        r.execute("EXPLAIN " + q05.SQL.format(schema="memory.default", region="ASIA", date="1994-01-01"))
    finally:
        S.join_graph_order = real
    assert orders == [["region", "nation", "supplier", "lineitem", "orders", "customer"]]
    # the same tables without the ranges: the nation code looks like a key
    for table in facts:
        memory.table(SchemaTableName("default", table)).spans = {}
    S.join_graph_order = spy
    try:
        r.execute("EXPLAIN " + q05.SQL.format(schema="memory.default", region="ASIA", date="1994-01-01"))
    finally:
        S.join_graph_order = real
    assert orders[1].index("customer") < orders[1].index("lineitem")


def test_q18s_semi_join_is_decided_on_orders(runner):
    """`o_orderkey IN (...)` goes below the joins to the side that has the key."""
    res = run(runner, q18, {"quantity": 250})
    spans = TRACER.spans(res.trace_id)
    (semi,) = [s.attributes for s in spans if s.name == "op:SemiJoinNode"]
    assert semi["probe_capacity"] == 16384  # the page of `orders`, not lineitem x orders x customer
    joins = [s.attributes for s in spans if s.name == "op:JoinNode"]
    assert all(a["rows_out"] < 2000 for a in joins)  # every join works on the large orders only
    text = "\n".join(row[0] for row in runner.execute(
        "EXPLAIN " + q18.SQL.format(schema="memory.default", quantity=313)).rows)
    assert text.index("- Join[INNER") < text.index("- SemiJoin") < text.index("memory.default.orders")


@pytest.mark.parametrize("kind", ["LEFT", "RIGHT", "FULL"])
def test_a_semi_join_stays_above_an_outer_join(runner, kind):
    sql = (
        "SELECT count(*) FROM memory.default.orders o {kind} JOIN memory.default.customer c "
        "ON o_custkey = c_custkey WHERE o_orderkey IN (SELECT l_orderkey FROM memory.default.lineitem "
        "WHERE l_quantity > 49)"
    ).format(kind=kind)
    text = "\n".join(row[0] for row in runner.execute("EXPLAIN " + sql).rows)
    assert text.index("- SemiJoin") < text.index(f"- Join[{kind}")
    inner = runner.execute(sql.replace(f"{kind} JOIN", "JOIN")).rows
    assert runner.execute(sql).rows == inner  # every order has its customer: the count is the same
