"""The join expansion's unique form: where no probe row emits more than one
row, the output slots are the emitting rows in row order (`K.live_indices`),
with no scatter over the probe rows. The kernel against the general form on
every active slot, and the executor's choice between the forms, made on the
one `sync:join_capacity` read, against a plain reference."""

import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.ops import kernels as K
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime import executor as E
from trino_tpu.runtime.metrics import REGISTRY
from trino_tpu.runtime.tracing import TRACER

N, M = 4096, 512
# the walk of `live_indices` where its slots are few among the probe rows, its sort where they are not
PATHS = {"walk": N // 32, "sort": N}


def _sides(data: str, path: str, rng):
    """Build keys unique among the active builds (so no probe row matches twice),
    probe keys and the probe's active rows for one case."""
    build = rng.permutation(M).astype(np.int64) * 3
    ba = np.ones(M, bool)
    if path == "walk":
        pa = np.zeros(N, bool)
        pa[rng.choice(N, PATHS["walk"], replace=False)] = True
    else:
        pa = np.ones(N, bool)
    if data == "inactive_interleaved":
        pa[1::2] = False
    if data == "no_match":
        probe = build[rng.integers(0, M, N)] + 1
    elif data == "every_row_matched":
        probe = build[rng.integers(0, M, N)]
    else:  # about half the probe rows find their key
        probe = build[rng.integers(0, M, N)] + (rng.random(N) < 0.5)
    if data == "empty_build":
        ba[:] = False
    return build, ba, probe, pa


@jax.jit
def _match(build, ba, probe, pa):
    return K.join_match(build, ba, probe, pa)


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("data", ["no_match", "every_row_matched", "inactive_interleaved", "empty_build"])
@pytest.mark.parametrize("kind", ["inner", "left"])
def test_the_unique_form_gives_every_active_slot_what_the_general_form_gives(kind, data, path):
    rng = np.random.default_rng(sorted(PATHS).index(path) * 10 + len(data))
    build, ba, probe, pa = _sides(data, path, rng)
    perm_b, lo, _, count = _match(jnp.asarray(build), jnp.asarray(ba), jnp.asarray(probe), jnp.asarray(pa))
    emit = jnp.where(jnp.asarray(pa), jnp.maximum(count, 1), 0) if kind == "left" else count
    assert int(jnp.max(emit)) <= 1
    cap = PATHS[path]
    assert (cap * K.LIVE_INDEX_SHARE > N) == (path == "sort")
    expand = jax.jit(K.expand_matches, static_argnames=("out_capacity", "unique"))
    general = [np.asarray(a) for a in expand(emit, count, lo, perm_b, out_capacity=cap)]
    unique = [np.asarray(a) for a in expand(emit, count, lo, perm_b, out_capacity=cap, unique=True)]
    # probe_idx, build_pos, matched, out_active, total
    assert np.array_equal(unique[3], general[3]) and int(unique[4]) == int(general[4])
    live = general[3]
    emitting = np.flatnonzero(np.asarray(emit) > 0)
    assert live.sum() == len(emitting) <= cap
    for got, want in zip(unique[:3], general[:3]):
        assert np.array_equal(got[live], want[live])
    assert np.array_equal(unique[0][live], emitting)  # probe-major: the emitting rows in order
    matched = unique[2][live]
    assert matched.sum() == int(jnp.sum(count))
    if data in ("no_match", "empty_build"):
        assert not matched.any()
    elif data == "every_row_matched":
        assert matched.all()
    # a matched slot's build row holds its probe row's key
    assert np.array_equal(build[unique[1][live][matched]], probe[unique[0][live][matched]])


# ------------------------------------------------------ the executor's choice


@pytest.fixture(scope="module")
def runner():
    """A fact table of suppliers keyed 0..4, a dimension keyed 0..4 once each
    (`region`), one keyed 0..4 five times each (`nation` by its region) and one
    holding three of the five keys."""
    r = LocalQueryRunner.tpch(scale=0.01)
    r.register_catalog("memory", MemoryConnector())
    schema = r.session.schema
    for sql in (
        f"CREATE TABLE memory.default.fact AS SELECT s_suppkey AS id, s_nationkey % 5 AS k FROM tpch.{schema}.supplier",
        f"CREATE TABLE memory.default.dim_pk AS SELECT r_regionkey AS k, r_name AS name FROM tpch.{schema}.region",
        f"CREATE TABLE memory.default.dim_dup AS SELECT n_regionkey AS k, n_name AS name FROM tpch.{schema}.nation",
        f"CREATE TABLE memory.default.dim_part AS SELECT r_regionkey AS k, r_name AS name "
        f"FROM tpch.{schema}.region WHERE r_regionkey < 3",
    ):
        r.execute(sql)
    return r


def _rows(runner, table):
    return runner.execute(f"SELECT * FROM memory.default.{table}").rows


def _expected(fact, dim, left: bool, residual: bool):
    by_key = {}
    for k, name in dim:
        by_key.setdefault(k, []).append(name)
    out = []
    for fid, k in fact:
        names = by_key.get(k, []) if not residual or fid % 3 != k else []
        out += [(fid, k, name) for name in names] or ([(fid, k, None)] if left else [])
    return Counter(out)


@pytest.mark.parametrize(
    "dim,kind,residual,form",
    [
        ("dim_pk", "JOIN", False, "unique"),
        ("dim_dup", "JOIN", False, "general"),
        ("dim_part", "LEFT JOIN", False, "unique"),
        ("dim_dup", "LEFT JOIN", False, "general"),
        # an ON residual over both sides of a LEFT join (`_jit_left_join_residual`)
        ("dim_part", "LEFT JOIN", True, "unique"),
        ("dim_dup", "LEFT JOIN", True, "general"),
    ],
)
def test_a_unique_build_key_takes_the_unique_form_and_answers_alike(runner, dim, kind, residual, form):
    before = {f: REGISTRY.counter(E.JOIN_EXPANSIONS_COUNTER, {"form": f}).value for f in ("unique", "general")}
    on = "f.k = d.k" + (" AND f.id % 3 <> d.k" if residual else "")
    res = runner.execute(f"SELECT f.id, f.k, d.name FROM memory.default.fact f {kind} memory.default.{dim} d ON {on}")
    assert Counter(res.rows) == _expected(_rows(runner, "fact"), _rows(runner, dim), kind == "LEFT JOIN", residual)
    spans = TRACER.spans(res.trace_id)
    (join,) = [s.attributes for s in spans if s.name == "op:JoinNode"]
    assert join["expand"] == form and join["build_capacity"] < join["probe_capacity"]
    # before an ON residual drops rows, the expansion emits them
    assert join["rows_out"] <= join["capacity_out"] and (residual or join["rows_out"] == len(res.rows))
    for f, value in before.items():
        assert REGISTRY.counter(E.JOIN_EXPANSIONS_COUNTER, {"form": f}).value - value == (f == form)
    # the form rides the read that sizes the output: one read a join, nothing added
    reads = [s for s in spans if s.name == "sync:join_capacity"]
    assert len(reads) == 1 and reads[0].attributes["value"] == join["rows_out"]


def test_both_forms_make_the_same_reads(runner):
    """The same statement over a unique and a duplicated build key: the same
    reads, in number (the statement's `host_syncs`) and by site."""
    reads, syncs = {}, {}
    for dim in ("dim_pk", "dim_dup"):
        res = runner.execute(
            f"SELECT f.id, d.name FROM memory.default.fact f JOIN memory.default.{dim} d ON f.k = d.k"
        )
        spans = TRACER.spans(res.trace_id)
        reads[dim] = sorted(s.name for s in spans if s.name.startswith("sync:"))
        syncs[dim] = spans[0].attributes["host_syncs"]
    assert reads["dim_pk"] == reads["dim_dup"] and reads["dim_pk"].count("sync:join_capacity") == 1
    assert syncs["dim_pk"] == syncs["dim_dup"] == len(reads["dim_pk"])


# ------------------------------------------ how the ranks came back, served


@pytest.fixture(scope="module")
def client(runner):
    from trino_tpu.client.client import StatementClient
    from trino_tpu.server import CoordinatorServer

    server = CoordinatorServer(runner).start()
    yield StatementClient(f"http://{server.address}")
    server.stop()


def _finished(query_id, timeout=5.0):
    """The statement's spans once its root has closed (just after the last page)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        tree = TRACER.spans(query_id)
        if tree and tree[0].end_ns is not None:
            return tree
        time.sleep(0.005)
    raise AssertionError(f"the root of {query_id} never closed")


@pytest.mark.parametrize("dim,kind", [("dim_pk", "JOIN"), ("dim_dup", "JOIN"), ("dim_part", "LEFT JOIN")])
def test_a_served_join_states_how_its_ranks_came_back(runner, client, dim, kind):
    forms = ("emitting", "merged")
    before = {f: REGISTRY.counter(E.JOIN_RANK_FORMS_COUNTER, {"form": f}).value for f in forms}
    res = client.execute(f"SELECT f.id, d.name FROM memory.default.fact f {kind} memory.default.{dim} d ON f.k = d.k")
    (join,) = [s.attributes for s in _finished(res.query_id) if s.name == "op:JoinNode"]
    # E, the probe rows that emit: each emits one row in the unique form, a LEFT join's live rows all emit
    emitting = join["emitting_rows"]
    assert 1 <= emitting <= join["rows_out"] and (join["expand"] == "general" or emitting == join["rows_out"])
    if kind == "LEFT JOIN":
        assert emitting == len(_rows(runner, "fact"))
    way = E._ranks_way(kind == "LEFT JOIN", join["probe_capacity"], join["build_capacity"],
                       join["expand"] == "unique", [join["rows_out"], 0, 0, emitting])
    assert join["ranks"] == way.form
    for f, value in before.items():
        assert REGISTRY.counter(E.JOIN_RANK_FORMS_COUNTER, {"form": f}).value - value == (f == way.form)


def test_the_mesh_tier_s_join_programs_keep_the_match_s_own_way_back(monkeypatch):
    """The traced executors read nothing: the mesh tier's programs call
    `_jit_join_match` and `_jit_join_expand` as they did before the match
    could stop at the merge (no `merged`, no `RanksWay`), so their text is
    unchanged, and none of the merged order's kernels is traced."""
    from trino_tpu.parallel.mesh_runner import MeshQueryRunner

    calls = {}
    for name in ("_jit_join_match", "_jit_join_expand"):
        jitted = getattr(E, name)

        def spy(*args, _real=jitted._jit, _name=name, **kwargs):
            calls.setdefault(_name, []).append(len(args) + len(kwargs))
            return _real(*args, **kwargs)

        monkeypatch.setattr(jitted, "_jit", spy)
    for kernel in ("join_merge", "emitting_ranks", "merged_ranks", "expand_listed"):
        monkeypatch.setattr(K, kernel, lambda *a, _k=kernel, **k: pytest.fail(f"K.{_k} traced"))
    mesh = MeshQueryRunner.tpch(scale=0.001, n_devices=4)
    subplan = mesh.plan_distributed(
        "SELECT sum(l_extendedprice), count(*) FROM lineitem JOIN part ON l_partkey = p_partkey "
        "WHERE p_type LIKE 'PROMO%'"
    )
    specs, counts = mesh._shard_scans(subplan)
    program = mesh._build_program(subplan, counts, [None] * len(mesh._points(subplan)), 1.0)
    program.fn.lower(*[s.page for s in specs])
    # the match with its eight arguments, the expansion with its eight: the parent's calls
    assert calls["_jit_join_match"] and set(calls["_jit_join_match"]) == {8}
    assert calls["_jit_join_expand"] and set(calls["_jit_join_expand"]) == {8}
