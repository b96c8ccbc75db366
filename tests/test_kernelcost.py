"""Kernel cost observability plane (runtime/kernelcost.py — ISSUE 17).

What this suite pins down:

- roofline math: peaks from $TRINO_TPU_ROOFLINE_PEAKS vs built-in defaults,
  memory- vs compute-bound classification at the ridge point, and the
  EXPLAIN one-liner format;
- the CostJit wrapper: transparent pass-through with no scope installed,
  attribution (sink + ledger + record fields) under a scope, the tracer
  guard (an enclosing program owns the cost), and every degrade path —
  lower-refused (the CPU-interpret / shard_map shape), cost-model-silent
  compiled objects, and the missing-store-key path — each ticking
  ``trino_tpu_kernel_cost_unavailable_total`` instead of raising;
- persistence: the ``$TRINO_TPU_CAP_STORE`` sibling file round-trips
  records so a warm process (XLA compile cache hit — jit dispatch never
  lowers) still attributes from the store (cache-hit-no-lowering path);
- acceptance: EXPLAIN ANALYZE VERBOSE on TPC-H Q3 AND a vector top-k
  query renders per-operator FLOPs/HBM/roofline lines, while the
  ``kernel_cost``-off path stays byte-identical.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.runtime import kernelcost
from trino_tpu.runtime.local import LocalQueryRunner
from trino_tpu.runtime.metrics import REGISTRY

SCALE = 0.001

def _unavailable(reason: str) -> float:
    # read via collect() — counter() would REGISTER the series (with empty
    # HELP, tripping the registry help lint other suites assert on)
    for series in REGISTRY.collect():
        if (
            series["name"] == "trino_tpu_kernel_cost_unavailable_total"
            and series["labels"].get("reason") == reason
        ):
            return series["value"]
    return 0.0


@pytest.fixture
def clean(monkeypatch):
    """Isolated plane: no persisted store, empty ledger + record cache."""
    monkeypatch.delenv("TRINO_TPU_CAP_STORE", raising=False)
    monkeypatch.delenv(kernelcost.ENV_PEAKS, raising=False)
    kernelcost.clear_memory()
    kernelcost.clear_ledger()
    yield monkeypatch
    kernelcost.clear_memory()
    kernelcost.clear_ledger()


class TestRooflineMath:
    def test_table_peaks_labeled_as_table(self, clean):
        pf, pb, prov = kernelcost.roofline_peaks("cpu")
        assert (pf, pb) == kernelcost.PEAKS["cpu"]
        assert prov == "table"
        # keyed by the device_kind jax reports, not by platform name
        assert kernelcost.roofline_peaks("TPU v5 lite")[:2] == (1.97e14, 8.19e11)

    def test_env_peaks_override_and_provenance(self, clean):
        clean.setenv(
            kernelcost.ENV_PEAKS, "TPU v5 lite=1e14:1e12, cpu=4e10:1e10"
        )
        pf, pb, prov = kernelcost.roofline_peaks("cpu")
        assert (pf, pb, prov) == (4e10, 1e10, "env")
        assert kernelcost.roofline_peaks("tpu v5 lite") == (1e14, 1e12, "env")

    def test_unknown_device_kind_is_an_error(self, clean):
        with pytest.raises(LookupError, match="no peaks for device kind 'tpu'"):
            kernelcost.roofline_peaks("tpu")
        with pytest.raises(LookupError, match="no peaks for"):
            kernelcost.classify(1e6, 1e6, device_kind="TPU v9")

    def test_garbage_env_degrades_to_table(self, clean):
        clean.setenv(kernelcost.ENV_PEAKS, "cpu=fast:wide,,tpu")
        pf, pb, prov = kernelcost.roofline_peaks("cpu")
        assert (pf, pb) == kernelcost.PEAKS["cpu"]
        assert prov == "table"

    def test_classify_ridge_point_split(self, clean):
        clean.setenv(kernelcost.ENV_PEAKS, "cpu=1e10:1e9")  # ridge = 10 flop/B
        lo = kernelcost.classify(flops=1e6, bytes_accessed=1e6, device_kind="cpu")
        hi = kernelcost.classify(flops=1e8, bytes_accessed=1e6, device_kind="cpu")
        assert lo["classification"] == "memory-bound"
        assert hi["classification"] == "compute-bound"
        assert lo["arithmetic_intensity"] == pytest.approx(1.0)
        assert kernelcost.classify(None, None) is None
        assert kernelcost.classify(0, 0) is None

    def test_roofline_pct_needs_measured_seconds(self, clean):
        clean.setenv(kernelcost.ENV_PEAKS, "cpu=1e10:1e9")
        unmeasured = kernelcost.classify(1e6, 1e6, device_kind="cpu")
        assert unmeasured["roofline_pct"] is None
        # AI=1 → attainable = 1e9 flop/s; 1e6 flops in 0.01s = 1e8 → 10%
        measured = kernelcost.classify(
            1e6, 1e6, device_secs=0.01, device_kind="cpu"
        )
        assert measured["roofline_pct"] == pytest.approx(0.1)
        # achieved can never render above the roof
        capped = kernelcost.classify(
            1e12, 1e6, device_secs=1e-9, device_kind="cpu"
        )
        assert capped["roofline_pct"] == 1.0

    def test_render_roofline_line_shape(self, clean):
        clean.setenv(kernelcost.ENV_PEAKS, "cpu=1e10:1e9")
        line = kernelcost.render_roofline(
            1.2e9, 890 * (1 << 20), peak_hbm_bytes=98304,
            device_secs=0.5, device_kind="cpu",
        )
        assert line.startswith("flops 1.2G · hbm 890MB · peak 96KB · arith ")
        assert "flop/B → " in line and line.endswith(" @ cpu")
        assert "-bound" in line and "% of roofline" in line
        assert kernelcost.render_roofline(None, None) is None


class TestCostJit:
    def test_pass_through_without_scope(self, clean):
        calls = []

        def f(x):
            calls.append(1)
            return x * 2.0

        jf = kernelcost.jit(f)
        x = jnp.arange(8, dtype=jnp.float32)
        expect = jax.jit(f)(x)  # lint: disable=jit-without-cost-hook -- test oracle for the wrapper itself
        got = jf(x)
        assert np.array_equal(np.asarray(got), np.asarray(expect))
        assert kernelcost.ledger_rows() == []
        # jit-object surface proxies through (traced.py relies on these)
        assert jf.__wrapped__ is f
        assert callable(jf.lower)

    def test_attribution_records_cost_and_ledger(self, clean):
        jf = kernelcost.jit(lambda x: (x * x).sum(), label="sq_sum")
        x = jnp.arange(1024, dtype=jnp.float32)
        seen = []
        with kernelcost.attributing(
            "plan:0:test_node", "test_node", sink=seen.append, query_id="q_1"
        ):
            jf(x)
            jf(x)  # same program key: sink fires again, ledger dedups
        assert len(seen) == 2
        rec = seen[0]
        assert rec["status"] == "ok" and rec["label"] == "sq_sum"
        assert rec["flops"] and rec["flops"] > 0
        assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0
        assert rec["peak_hbm_bytes"] and rec["peak_hbm_bytes"] > 0
        rows = kernelcost.ledger_rows()
        assert len(rows) == 1
        row = rows[0]
        assert row["plan_node"] == "test_node" and row["query_id"] == "q_1"
        assert row["classification"] in ("memory-bound", "compute-bound")
        assert row["platform"] == jax.default_backend()

    def test_innermost_scope_wins(self, clean):
        jf = kernelcost.jit(lambda x: x + 1.0, label="inc")
        outer, inner = [], []
        with kernelcost.attributing("p:0:outer", "outer", outer.append):
            with kernelcost.attributing("p:1:inner", "inner", inner.append):
                jf(jnp.ones(4))
        assert not outer and len(inner) == 1
        assert [r["plan_node"] for r in kernelcost.ledger_rows()] == ["inner"]

    def test_tracer_guard_skips_enclosing_trace(self, clean):
        """A jit launched while TRACING an enclosing program must not
        attribute — the enclosing program owns the launch cost."""
        inner = kernelcost.jit(lambda x: x * 3.0, label="inner_prog")
        sunk = []

        def outer(x):
            return inner(x) + 1.0

        jouter = kernelcost.jit(outer, label="outer_prog")
        with kernelcost.attributing("p:0:n", "n", sunk.append):
            jouter(jnp.ones(8))
        labels = {r["label"] for r in sunk}
        assert labels == {"outer_prog"}, labels

    def test_static_argnums_forms(self, clean):
        from functools import partial

        @partial(kernelcost.jit, static_argnums=(0,))
        def scale(k, x):
            return x * k

        sunk = []
        with kernelcost.attributing("p:0:s", "s", sunk.append):
            out = scale(3.0, jnp.ones(4))
        assert np.allclose(np.asarray(out), 3.0)
        assert len(sunk) == 1 and sunk[0]["status"] == "ok"


class TestDegradePaths:
    def test_lower_refused_degrades_to_cost_unavailable(self, clean):
        """The CPU-interpret / shard_map shape: a program that refuses to
        lower standalone records cost_unavailable and ticks the counter —
        the call itself still returns the right answer."""
        jf = kernelcost.jit(lambda x: x + 1.0, label="no_lower")

        class _RefusesLower:
            def __init__(self, jitted):
                self._jitted = jitted

            def __call__(self, *a, **k):
                return self._jitted(*a, **k)

            def lower(self, *a, **k):
                raise RuntimeError("interpret-mode program: no standalone lowering")

        jf._jit = _RefusesLower(jf._jit)
        before = _unavailable("lower_failed")
        sunk = []
        with kernelcost.attributing("p:0:d", "d", sunk.append):
            out = jf(jnp.zeros(4))
        assert np.allclose(np.asarray(out), 1.0)
        assert len(sunk) == 1
        assert sunk[0]["status"] == "cost_unavailable"
        assert sunk[0]["reason"].startswith("lower_failed:")
        assert _unavailable("lower_failed") == before + 1
        assert kernelcost.ledger_rows()[0]["status"] == "cost_unavailable"

    def test_cost_model_silent_compiled(self, clean):
        """Backend exposes neither cost_analysis nor memory_analysis
        (Pallas interpret-mode): degrade, count, don't raise."""
        jf = kernelcost.jit(lambda x: x, label="silent")

        class _Silent:
            def cost_analysis(self):
                raise NotImplementedError

            def memory_analysis(self):
                raise NotImplementedError

        class _Lowers:
            def __init__(self, jitted):
                self._jitted = jitted

            def __call__(self, *a, **k):
                return self._jitted(*a, **k)

            def lower(self, *a, **k):
                class _L:
                    def compile(self):
                        return _Silent()

                return _L()

        jf._jit = _Lowers(jf._jit)
        before = _unavailable("cost_analysis_unavailable")
        sunk = []
        with kernelcost.attributing("p:0:d", "d", sunk.append):
            jf(jnp.zeros(2))
        assert sunk[0]["status"] == "cost_unavailable"
        assert sunk[0]["reason"] == "cost_analysis_unavailable"
        assert _unavailable("cost_analysis_unavailable") == before + 1

    def test_sink_exception_counts_hook_error(self, clean):
        jf = kernelcost.jit(lambda x: x * 2.0, label="boom_sink")
        before = _unavailable("hook_error")

        def bad_sink(record):
            raise ValueError("sink bug must not fail the query")

        with kernelcost.attributing("p:0:b", "b", bad_sink):
            out = jf(jnp.ones(4))
        assert np.allclose(np.asarray(out), 2.0)
        assert _unavailable("hook_error") == before + 1

    def test_missing_store_key_computes_fresh(self, clean, tmp_path):
        """A persisted store that does NOT hold this program's key must not
        satisfy the read — the record is computed and then persisted."""
        store = tmp_path / "caps.json"
        clean.setenv("TRINO_TPU_CAP_STORE", str(store))
        side = str(store) + ".kernelcost"
        with open(side, "w") as f:
            json.dump({"deadbeefdeadbeefdeadbeef": {"status": "ok"}}, f)
        sunk = []
        jf = kernelcost.jit(lambda x: x - 1.0, label="fresh")
        with kernelcost.attributing("p:0:m", "m", sunk.append):
            jf(jnp.ones(4))
        assert sunk[0]["source"] == "computed"
        with open(side) as f:
            data = json.load(f)
        assert len(data) == 2  # stranger key untouched, fresh key added


class TestPersistence:
    def test_store_round_trip_warm_process(self, clean, tmp_path):
        """Cache-hit-no-lowering: a warm process whose jit dispatch hits the
        XLA compile cache never lowers — it must attribute from the
        persisted sibling file instead of re-tracing."""
        store = tmp_path / "caps.json"
        clean.setenv("TRINO_TPU_CAP_STORE", str(store))
        jf = kernelcost.jit(lambda x: (x * x).sum(), label="persisted")
        x = jnp.arange(256, dtype=jnp.float32)
        first = []
        with kernelcost.attributing("p:0:w", "w", first.append):
            jf(x)
        assert first[0]["source"] == "computed"
        side = str(store) + ".kernelcost"
        assert os.path.exists(side)
        with open(side) as f:
            persisted = json.load(f)
        assert first[0]["key"] in persisted
        assert persisted[first[0]["key"]]["status"] == "ok"

        # simulate the warm process: in-memory caches gone, and lowering
        # would blow up if attempted — the store must satisfy the read
        kernelcost.clear_memory()

        class _MustNotLower:
            def __init__(self, jitted):
                self._jitted = jitted

            def __call__(self, *a, **k):
                return self._jitted(*a, **k)

            def lower(self, *a, **k):
                raise AssertionError("warm path must not re-lower")

        jf._jit = _MustNotLower(jf._jit)
        warm = []
        with kernelcost.attributing("p:0:w", "w", warm.append):
            jf(x)
        assert warm[0]["source"] == "store"
        assert warm[0]["status"] == "ok"
        assert warm[0]["flops"] == first[0]["flops"]
        assert warm[0]["peak_hbm_bytes"] == first[0]["peak_hbm_bytes"]

    def test_no_store_configured_still_attributes(self, clean):
        assert kernelcost.store_path() is None
        sunk = []
        jf = kernelcost.jit(lambda x: x + 2.0, label="storeless")
        with kernelcost.attributing("p:0:n", "n", sunk.append):
            jf(jnp.ones(4))
        assert sunk[0]["status"] == "ok"

    def test_degraded_records_not_persisted(self, clean, tmp_path):
        """Only ok records persist: a transient lower failure must not
        poison the store for future (healthy) processes."""
        store = tmp_path / "caps.json"
        clean.setenv("TRINO_TPU_CAP_STORE", str(store))
        jf = kernelcost.jit(lambda x: x, label="transient")

        class _Refuses:
            def __init__(self, jitted):
                self._jitted = jitted

            def __call__(self, *a, **k):
                return self._jitted(*a, **k)

            def lower(self, *a, **k):
                raise RuntimeError("transient")

        jf._jit = _Refuses(jf._jit)
        with kernelcost.attributing("p:0:t", "t"):
            jf(jnp.ones(2))
        assert not os.path.exists(str(store) + ".kernelcost")


class TestFederation:
    def test_announcement_ingest_ttl_and_system_table(self, clean):
        jf = kernelcost.jit(lambda x: (x * x).sum(), label="fed")
        with kernelcost.attributing("p:0:agg", "agg", query_id="q_fed"):
            jf(jnp.arange(64, dtype=jnp.float32))
        rows = kernelcost.announcement_rows()
        assert rows and rows[0]["plan_node"] == "agg"
        assert kernelcost.ingest_federated("worker-a", rows) == len(rows)
        fed = kernelcost.federated_rows()
        assert ("worker-a" in {n for n, _ in fed}) and len(fed) == len(rows)
        # junk announcements fold to nothing, bad rows filtered
        assert kernelcost.ingest_federated("worker-b", "junk") == 0
        assert kernelcost.ingest_federated("worker-c", [1, {"ok": 1}]) == 1

    def test_system_runtime_kernel_costs_table(self, clean):
        runner = LocalQueryRunner.tpch(scale=SCALE)
        jf = kernelcost.jit(lambda x: (x + x).sum(), label="tbl")
        with kernelcost.attributing("p:0:scan", "scan", query_id="q_tbl"):
            jf(jnp.arange(32, dtype=jnp.float32))
        kernelcost.ingest_federated("worker-z", kernelcost.announcement_rows())
        res = runner.execute(
            "SELECT node, plan_node, label, platform, classification, status "
            "FROM system.runtime.kernel_costs"
        )
        rows = res.rows
        # local rows carry node='' ; federated rows carry the node id
        assert any(r[0] == "" and r[2] == "tbl" for r in rows)
        assert any(r[0] == "worker-z" and r[2] == "tbl" for r in rows)
        assert all(r[5] in ("ok", "cost_unavailable") for r in rows)


class TestExplainVerboseAcceptance:
    def test_q3_roofline_lines_and_off_path_identical(self, clean):
        runner = LocalQueryRunner.tpch(scale=SCALE)
        q3 = """
        SELECT o_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue
        FROM customer JOIN orders ON c_custkey = o_custkey
        JOIN lineitem ON l_orderkey = o_orderkey
        WHERE c_mktsegment = 'BUILDING'
        GROUP BY o_orderkey ORDER BY revenue DESC LIMIT 10
        """
        baseline = runner.execute(q3).rows
        # off path: no scope installs, ledger stays empty, bytes identical
        off = runner.execute(q3).rows
        assert off == baseline
        assert kernelcost.ledger_rows() == []
        verbose = "\n".join(
            r[0] for r in runner.execute(
                "EXPLAIN ANALYZE VERBOSE " + q3
            ).rows
        )
        assert "[kernel:" in verbose
        kernel_lines = [
            ln for ln in verbose.splitlines() if "[kernel:" in ln
        ]
        # at least one operator classified, with the roofline grammar
        classified = [ln for ln in kernel_lines if "-bound" in ln]
        assert classified, kernel_lines
        assert any("flops" in ln and "arith" in ln for ln in classified)
        assert any("% of roofline @" in ln for ln in classified)
        # attribution under EXPLAIN must not perturb the answer
        assert runner.execute(q3).rows == baseline
        # plain EXPLAIN ANALYZE (not VERBOSE) stays kernel-free
        plain = "\n".join(
            r[0] for r in runner.execute("EXPLAIN ANALYZE " + q3).rows
        )
        assert "[kernel:" not in plain

    def test_vector_topk_roofline_lines(self, clean):
        from trino_tpu.connectors.memory import MemoryConnector

        runner = LocalQueryRunner.tpch(scale=SCALE)
        runner.register_catalog("memory", MemoryConnector())
        dim, rows = 8, 64
        rng = np.random.RandomState(7)
        data = np.round(rng.uniform(-1, 1, size=(rows, dim)), 6)
        runner.execute(
            f"CREATE TABLE memory.default.emb (id bigint, v vector({dim}))"
        )
        vals = ", ".join(
            "({}, ARRAY[{}])".format(
                i, ", ".join(f"CAST({x} AS double)" for x in data[i])
            )
            for i in range(rows)
        )
        runner.execute(f"INSERT INTO memory.default.emb VALUES {vals}")
        qv = ", ".join(f"CAST({x} AS double)" for x in np.round(
            rng.uniform(-1, 1, size=dim), 6))
        sql = (
            "SELECT id FROM memory.default.emb "
            f"ORDER BY cosine_similarity(v, ARRAY[{qv}]) DESC, id LIMIT 5"
        )
        baseline = runner.execute(sql).rows
        verbose = "\n".join(
            r[0] for r in runner.execute(
                "EXPLAIN ANALYZE VERBOSE " + sql
            ).rows
        )
        assert "[kernel:" in verbose
        assert any(
            "-bound" in ln for ln in verbose.splitlines() if "[kernel:" in ln
        )
        assert runner.execute(sql).rows == baseline

    def test_session_property_gates_executor_scopes(self, clean):
        runner = LocalQueryRunner.tpch(scale=SCALE)
        sql = "SELECT count(*), sum(l_quantity) FROM lineitem"
        runner.execute(sql)
        assert kernelcost.ledger_rows() == []
        runner.session.set("kernel_cost", True)
        on_rows = runner.execute(sql).rows
        assert kernelcost.ledger_rows(), "kernel_cost=true must attribute"
        runner.session.properties.pop("kernel_cost", None)
        kernelcost.clear_ledger()
        off_rows = runner.execute(sql).rows
        assert off_rows == on_rows
        assert kernelcost.ledger_rows() == []

