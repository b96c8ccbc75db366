"""A join's ranks reach its expansion from the match's merged order in one of
two forms (`RanksWay`): `emitting` lists the probe rows that emit and sorts
them alone, `merged` sorts every merged row by the probe's row number. Both
give the page the match's own way back gives, bit for bit, inactive slots
included, and that page holds what a plain numpy join holds; the executor
makes one `sync:join_capacity` read a join whichever form it takes."""

from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest
from jax.tree_util import tree_leaves

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.ops import kernels as K
from trino_tpu.ops.compiler import CVal
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime import executor as E
from trino_tpu.runtime.metrics import REGISTRY
from trino_tpu.runtime.tracing import TRACER
from trino_tpu.spi.page import Column, Page
from trino_tpu.spi.types import BIGINT

# n + m past 16 x 1,024 slots: the emitting form walks the mask where a thousand
# rows emit or fewer and sorts the positions where more do
N, M = 20_000, 512
BITS, BASE = 12, 100   # the narrowed key: values in [100, 4196) on the build side
KINDS = ("inner", "left", "left_residual", "full")
SYMBOLS = ("pk", "pid", "bk", "bid")


def _residual(env):
    """An ON residual over both sides: pid + bid not a multiple of three."""
    pid, bid = env["pid"], env["bid"]
    return CVal((pid.data + bid.data) % 3 != 0, pid.valid & bid.valid)


def _case(case: str, rng):
    """(build keys, valid, active, probe keys, valid, active) of one case."""
    if case in ("unique", "e_all"):
        bkey = BASE + rng.permutation(M).astype(np.int64) * 3
    else:  # each key on eight build rows (two where every probe row emits)
        bkey = BASE + (np.arange(M) % (M // (2 if case == "e_all_general" else 8))) * 3
    bvalid = rng.random(M) < 0.95
    bactive = rng.random(M) < 0.9
    live_keys = bkey[bvalid & bactive]
    pick = rng.choice(live_keys, N)
    draw = rng.random(N)
    # a live key, one no build holds, a key outside the narrowed range, a null
    pkey = np.where(draw < 0.5, pick, np.where(draw < 0.7, pick + 1, np.where(draw < 0.85, 7, BASE + (1 << BITS) + 5)))
    pvalid = rng.random(N) >= 0.1
    pactive = rng.random(N) < 0.06
    if case in ("e_all", "e_all_general"):
        pkey, pvalid, pactive = pick, np.ones(N, bool), np.ones(N, bool)
    elif case == "e_zero":
        pactive[:] = False
    elif case == "e_one":
        pactive[:] = False
        pactive[N // 2], pkey[N // 2], pvalid[N // 2] = True, live_keys[0], True
    elif case == "general":  # the probe's last row emits: the slots past the rows emitted run on from it
        pactive[-1], pkey[-1], pvalid[-1] = True, live_keys[-1], True
    else:  # the probe's last row is not live
        pactive[-1] = False
    return bkey, bvalid, bactive, pkey.astype(np.int64), pvalid, pactive


def _pages(bkey, bvalid, bactive, pkey, pvalid, pactive):
    ones_p, ones_b = jnp.ones(N, bool), jnp.ones(M, bool)
    probe = Page((Column(BIGINT, jnp.asarray(pkey), jnp.asarray(pvalid)),
                  Column(BIGINT, jnp.arange(N, dtype=jnp.int64), ones_p)), jnp.asarray(pactive))
    build = Page((Column(BIGINT, jnp.asarray(bkey), jnp.asarray(bvalid)),
                  Column(BIGINT, jnp.arange(M, dtype=jnp.int64), ones_b)), jnp.asarray(bactive))
    return probe, build


def _expand(kind, out_capacity, unique, match, probe, build, *ranks):
    emit, count, lo, perm_b = match
    if kind == "left_residual":
        return E._jit_left_join_residual(
            _residual, SYMBOLS, out_capacity, unique, emit, count, lo, perm_b, probe, build, *ranks
        )
    page = E._jit_join_expand(out_capacity, unique, emit, count, lo, perm_b, probe, build, *ranks)
    if kind == "full":
        keys = ((probe.columns[0].data, probe.columns[0].valid),), ((build.columns[0].data, build.columns[0].valid),)
        page = E._concat_pages([page, E._jit_full_join_tail(*keys, (None,), probe, build)])
    return page


def _reference(kind, bkey, bvalid, bactive, pkey, pvalid, pactive):
    """(pid, bid or None) in the page's order: probe-major, a probe row's
    matches by build row; a LEFT join's unmatched rows null-padded in place,
    its residual's survivors first and the rows left without one after; a
    FULL join's unmatched builds last."""
    inside = (pkey >= BASE) & (pkey < BASE + (1 << BITS))
    matches = {i: [j for j in np.flatnonzero(bactive & bvalid & (bkey == pkey[i]))]
               for i in np.flatnonzero(pactive & pvalid & inside)}
    rows, tail = [], []
    for i in np.flatnonzero(pactive):
        found = matches.get(i, [])
        if kind == "left_residual":
            kept = [j for j in found if (i + j) % 3]
            rows += [(i, j) for j in kept]
            tail += [] if kept else [(i, None)]
        else:
            rows += [(i, j) for j in found] or ([(i, None)] if kind != "inner" else [])
    if kind == "full":
        hit = {j for found in matches.values() for j in found}
        tail = [(None, j) for j in np.flatnonzero(bactive) if j not in hit]
    return rows + tail


def _rows(page):
    (pid, pid_ok), (bid, bid_ok) = ((np.asarray(c.data), np.asarray(c.valid)) for c in page.columns[1::2])
    return [(int(pid[s]) if pid_ok[s] else None, int(bid[s]) if bid_ok[s] else None)
            for s in np.flatnonzero(np.asarray(page.active))]


def _same_page(a: Page, b: Page) -> bool:
    la, lb = tree_leaves(a), tree_leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


CASES = ("unique", "general", "e_zero", "e_one", "e_all", "e_all_general")


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", KINDS)
def test_both_forms_give_the_match_s_own_page_bit_for_bit(kind, case):
    rng = np.random.default_rng(CASES.index(case) * 7 + KINDS.index(kind))
    data = _case(case, rng)
    probe, build = _pages(*data)
    left_outer = kind != "inner"
    args = (left_outer, ((probe.columns[0].data, probe.columns[0].valid),),
            ((build.columns[0].data, build.columns[0].valid),), (None,), probe.active, build.active,
            (BITS,), (np.int64(BASE),))
    *own, totals = E._jit_join_match(*args)
    read = [int(v) for v in np.asarray(totals)]
    out_capacity = E._round_capacity(max(read[0], 1))
    unique = read[2] <= 1
    want = _expand(kind, out_capacity, unique, own, probe, build)
    assert _rows(want) == _reference(kind, *data)

    *merged, totals4, qid = E._jit_join_match(*args, True)
    read4 = [int(v) for v in np.asarray(totals4)]
    # the same read, one number more: the probe rows that emit
    assert read4[:3] == read and read4[3] == int(np.sum(np.asarray(own[0]) > 0))
    assert np.array_equal(np.asarray(merged[3]), np.asarray(own[3]))   # perm_b
    emitting = read4[3]
    assert emitting == {"e_zero": 0, "e_one": 1}.get(case, emitting)
    if case.startswith("e_all"):
        assert emitting == N
    for form in ("emitting", "merged"):
        way = E.RanksWay(form, left_outer, E._round_capacity(emitting + 1) if form == "emitting" else 0)
        got = _expand(kind, out_capacity, unique, merged[:4], probe, build, qid, way)
        assert _same_page(got, want), form


def test_the_forms_list_every_probe_row_once():
    """`K.emitting_ranks` and `K.merged_ranks` against the match's own way
    back: the emitting rows' (lo, count) in probe order, the last row listed
    whether it emits or not, and nothing else."""
    rng = np.random.default_rng(11)
    data = _case("general", rng)
    probe, build = _pages(*data)
    keys = (((probe.columns[0].data, probe.columns[0].valid),), ((build.columns[0].data, build.columns[0].valid),))
    emit, count, lo, _, _ = E._jit_join_match(False, *keys, (None,), probe.active, build.active)
    m_emit, m_count, m_lo, _, totals, qid = E._jit_join_match(False, *keys, (None,), probe.active, build.active, None, None, True)
    e_qid, e_lo, e_count = (np.asarray(a) for a in K.emitting_ranks(qid, m_lo, m_count, m_emit, M, 1024))
    listed = np.flatnonzero((np.asarray(emit) > 0) | (np.arange(N) == N - 1))
    assert np.array_equal(e_qid[: len(listed)], listed) and (e_qid[len(listed):] == N).all()
    assert np.array_equal(e_lo[: len(listed)], np.asarray(lo)[listed])
    assert np.array_equal(e_count[: len(listed)], np.asarray(count)[listed])
    back_lo, back_count = K.merged_ranks(qid, m_lo, m_count, M)
    assert np.array_equal(np.asarray(back_lo), np.asarray(lo)) and np.array_equal(np.asarray(back_count), np.asarray(count))


@pytest.mark.parametrize(
    "probe,build,emitting,unique,form",
    [
        # Q3: `lineitem` x `orders`, 93,314 probe rows emit of 16,777,216
        (16_777_216, 524_288, 93_314, True, "emitting"),
        # Q8: `lineitem` at its stored capacity x the parts of one type, about one line in 150
        (18_874_368, 4_096, 119_960, True, "emitting"),
        # Q7 and Q12: `orders` x `lineitem`, one-to-many: 430,000 rows out of about 150,000 orders
        (5_242_880, 16_777_216, 150_000, False, "emitting"),
        # Q13: `customer` LEFT JOIN `orders`, every customer emits: 290 ms listed against 363
        # merged for the whole expansion on a v5e (tools/ranks_probe.py)
        (524_288, 5_242_880, 450_000, False, "emitting"),
        # Q5: `lineitem` x `supplier`, 3.6M probe rows emit: 218 ms listed against 197 merged
        (18_874_368, 8_192, 3_600_000, True, "merged"),
        # a LEFT join over a dense probe: every line of `lineitem` emits against `orders`
        (18_874_368, 5_242_880, 17_993_932, True, "merged"),
    ],
)
def test_the_rule_lists_the_emitting_rows_where_they_are_few(probe, build, emitting, unique, form):
    slots = E._round_capacity(emitting + 1)
    assert K.ranks_form(probe + build, probe, slots, K.rank_words(build), unique) == form
    way = E._ranks_way(False, probe, build, unique, [emitting, emitting, 1, emitting])
    assert way.form == form and way.slots == (slots if form == "emitting" else 0)


# ------------------------------------------------------ the executor, served


@pytest.fixture(scope="module")
def runner():
    """Suppliers keyed 0..4 with every ninth key null, a dimension keyed 0..4
    once each, one keyed 0..4 five times each and one holding three keys."""
    r = LocalQueryRunner.tpch(scale=0.01)
    r.register_catalog("memory", MemoryConnector())
    schema = r.session.schema
    for sql in (
        f"CREATE TABLE memory.default.fact AS SELECT s_suppkey AS id, "
        f"CASE WHEN s_suppkey % 9 = 0 THEN NULL ELSE s_nationkey % 5 END AS k FROM tpch.{schema}.supplier",
        f"CREATE TABLE memory.default.dim_pk AS SELECT r_regionkey AS k, r_name AS name FROM tpch.{schema}.region",
        f"CREATE TABLE memory.default.dim_dup AS SELECT n_regionkey AS k, n_nationkey AS name FROM tpch.{schema}.nation",
        f"CREATE TABLE memory.default.dim_part AS SELECT r_regionkey AS k, r_name AS name "
        f"FROM tpch.{schema}.region WHERE r_regionkey < 3",
    ):
        r.execute(sql)
    return r


def _expected(fact, dim, kind: str, residual: bool):
    by_key = {}
    for k, name in dim:
        by_key.setdefault(k, []).append(name)
    out, hit = [], set()
    for fid, k in fact:
        names = [n for n in by_key.get(k, []) if not residual or fid % 3 != k] if k is not None else []
        hit.update((k, n) for n in names)
        out += [(fid, k, n) for n in names] or ([(fid, k, None)] if kind != "JOIN" else [])
    if kind == "FULL JOIN":
        out += [(None, None, n) for k, n in dim if (k, n) not in hit]
    return Counter(out)


@pytest.mark.parametrize("dim", ["dim_pk", "dim_dup", "dim_part"])
@pytest.mark.parametrize(
    "kind,residual", [("JOIN", False), ("LEFT JOIN", False), ("LEFT JOIN", True), ("FULL JOIN", False)]
)
def test_a_served_join_answers_alike_in_both_forms(runner, monkeypatch, dim, kind, residual):
    pages, reads, answers = {}, {}, {}
    real = {f: getattr(E, f)._jit for f in ("_jit_join_expand", "_jit_left_join_residual")}
    for form in ("emitting", "merged"):
        made = []
        monkeypatch.setattr(K, "ranks_form", lambda *a, form=form: form)
        for f, jitted in real.items():

            def spy(*args, _real=jitted, **kwargs):
                made.append(_real(*args, **kwargs))
                return made[-1]

            monkeypatch.setattr(getattr(E, f), "_jit", spy)
        on = "f.k = d.k" + (" AND f.id % 3 <> d.k" if residual else "")
        before = {f: REGISTRY.counter(E.JOIN_RANK_FORMS_COUNTER, {"form": f}).value for f in ("emitting", "merged")}
        res = runner.execute(f"SELECT f.id, f.k, d.name FROM memory.default.fact f {kind} memory.default.{dim} d ON {on}")
        spans = TRACER.spans(res.trace_id)
        (join,) = [s.attributes for s in spans if s.name == "op:JoinNode"]
        assert join["ranks"] == form and join["emitting_rows"] >= 1
        for f, value in before.items():
            assert REGISTRY.counter(E.JOIN_RANK_FORMS_COUNTER, {"form": f}).value - value == (f == form)
        reads[form] = [s.attributes["value"] for s in spans if s.name == "sync:join_capacity"]
        pages[form], answers[form] = made, Counter(res.rows)
    monkeypatch.undo()
    assert len(reads["emitting"]) == 1 and reads["emitting"] == reads["merged"]
    # the expansion, and a LEFT join's residual over it
    assert len(pages["emitting"]) == len(pages["merged"]) == 1 + residual
    assert all(_same_page(a, b) for a, b in zip(pages["emitting"], pages["merged"]))
    fact = runner.execute("SELECT * FROM memory.default.fact").rows
    table = runner.execute(f"SELECT * FROM memory.default.{dim}").rows
    assert answers["emitting"] == answers["merged"] == _expected(fact, table, kind, residual)
