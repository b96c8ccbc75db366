"""Static-analysis plane: plan sanity checkers + the engine lint suite.

Three layers of coverage:

1. Checker mutation suite — every checker in planner/sanity.py is killed by
   at least one seeded plan corruption (dangling symbol, duplicate node id,
   dropped partition key, nondet-below-exchange, ...), and each corruption is
   caught by EXACTLY the checker that owns it (disjoint ownership is what
   makes a PlanSanityError actionable).
2. Whole-corpus validation — all 22 TPC-H queries (tests/tpch_corpus.py) and
   the TPC-DS conformance corpus (when the reference checkout is present)
   optimize + add_exchanges cleanly with validate_plan=true, i.e. the
   intermediate checks run after EVERY optimizer rule; repeated with
   history_based_stats=true over warm history (the stats overlay must keep
   estimates finite/non-negative).
3. Engine lint tier-1 gate — python -m tools.lint over trino_tpu/ reports
   zero non-baselined findings, and each lint rule is itself mutation-tested
   against a seeded bad snippet.
"""

import os

import pytest

from trino_tpu.metadata import Session
from trino_tpu.planner.plan import (
    Aggregation,
    AggregationNode,
    ExchangeNode,
    ExchangeScope,
    ExchangeType,
    FilterNode,
    LimitNode,
    LogicalPlan,
    Ordering,
    OutputNode,
    ProjectNode,
    SemiJoinNode,
    UnionNode,
    ValuesNode,
    VectorTopNNode,
    WindowFunction,
    WindowNode,
)
from trino_tpu.planner.sanity import (
    CHECKERS,
    PlanSanityError,
    SanityContext,
    checker_ids,
    run_checkers,
    validate_final,
    validate_intermediate,
)
from trino_tpu.planner.stats import PlanStats
from trino_tpu.spi.types import BIGINT, BOOLEAN, DOUBLE
from trino_tpu.sql.ir import Call, Constant, Reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _leaf(symbols=("a", "b")):
    return ValuesNode(symbols=tuple(symbols), rows=((1, 2),))


def _types(**extra):
    out = {"a": BIGINT, "b": BIGINT}
    out.update(extra)
    return out


def _fired(root, types=None, session=None, estimator=None):
    ctx = SanityContext(types if types is not None else _types(),
                        session=session, estimator=estimator)
    return {v.checker for v in run_checkers(root, ctx)}


class TestCheckerMutations:
    """Each seeded corruption is caught by exactly the checker that owns it."""

    def test_checker_count(self):
        # the plane's floor: >= 8 composable checkers
        assert len(CHECKERS) >= 8
        assert len(set(checker_ids())) == len(CHECKERS)

    def test_valid_plan_is_clean(self):
        v = _leaf()
        root = OutputNode(
            source=ProjectNode(
                source=FilterNode(
                    source=v,
                    predicate=Call("$eq", (Reference("a", BIGINT),
                                           Constant(BIGINT, 1)), BOOLEAN),
                ),
                assignments=(("p", Reference("a", BIGINT)),),
            ),
            column_names=("p",), symbols=("p",),
        )
        assert _fired(root, _types(p=BIGINT)) == set()

    def test_dangling_symbol(self):
        root = ProjectNode(
            source=_leaf(), assignments=(("p", Reference("zz", BIGINT)),)
        )
        assert _fired(root, _types(p=BIGINT)) == {"symbol-dependencies"}

    def test_semijoin_key_dangling(self):
        root = SemiJoinNode(
            source=_leaf(), filtering_source=ValuesNode(symbols=("c",), rows=()),
            source_key="zz", filtering_key="c", output="m",
        )
        assert _fired(root, _types(c=BIGINT, m=BOOLEAN)) == {"symbol-dependencies"}

    def test_duplicate_node_id(self):
        v = _leaf(("a",))
        root = UnionNode(
            inputs=(v, v), symbols=("u",), symbol_mapping=(("a",), ("a",))
        )
        assert _fired(root, {"a": BIGINT, "u": BIGINT}) == {"no-duplicate-plan-node-ids"}

    def test_duplicate_output_symbols(self):
        root = ProjectNode(
            source=_leaf(),
            assignments=(("d", Reference("a", BIGINT)),
                         ("d", Reference("b", BIGINT))),
        )
        assert _fired(root, _types(d=BIGINT)) == {"unique-output-symbols"}

    def test_missing_symbol_type(self):
        root = ProjectNode(
            source=_leaf(), assignments=(("untyped", Reference("a", BIGINT)),)
        )
        assert _fired(root, _types()) == {"type-consistency"}

    def test_non_boolean_filter_predicate(self):
        root = FilterNode(source=_leaf(), predicate=Reference("a", BIGINT))
        assert _fired(root, _types()) == {"type-consistency"}

    def test_aggregation_arg_dangling(self):
        root = AggregationNode(
            source=_leaf(), group_keys=("a",),
            aggregations=(("s", Aggregation("sum", ("zz",), output_type=BIGINT)),),
        )
        assert _fired(root, _types(s=BIGINT)) == {"aggregation-validity"}

    def test_window_arg_dangling(self):
        root = WindowNode(
            source=_leaf(),
            functions=(("w", WindowFunction("sum", ("zz",), output_type=BIGINT)),),
        )
        assert _fired(root, _types(w=BIGINT)) == {"window-validity"}

    def test_dropped_partition_key(self):
        root = ExchangeNode(
            source=_leaf(), exchange_type=ExchangeType.REPARTITION,
            scope=ExchangeScope.REMOTE, partition_keys=("zz",),
        )
        assert _fired(root, _types()) == {"exchange-partitioning"}

    def test_repartition_without_keys(self):
        root = ExchangeNode(
            source=_leaf(), exchange_type=ExchangeType.REPARTITION,
            scope=ExchangeScope.REMOTE, partition_keys=(),
        )
        assert _fired(root, _types()) == {"exchange-partitioning"}

    def test_nondeterministic_below_retryable_exchange(self):
        root = ExchangeNode(
            source=ProjectNode(
                source=_leaf(),
                assignments=(("r", Call("random", (), DOUBLE)),),
            ),
            exchange_type=ExchangeType.GATHER, scope=ExchangeScope.REMOTE,
        )
        fte = Session(properties={"retry_policy": "TASK"})
        assert _fired(root, _types(r=DOUBLE), session=fte) == {"fte-determinism"}
        # without TASK retries the same plan is legal
        assert _fired(root, _types(r=DOUBLE), session=Session()) == set()

    def test_union_mapping_arity(self):
        root = UnionNode(
            inputs=(_leaf(("a",)), ValuesNode(symbols=("c",), rows=())),
            symbols=("u",), symbol_mapping=(("a",),),
        )
        assert _fired(root, {"a": BIGINT, "c": BIGINT, "u": BIGINT}) == {
            "union-consistency"
        }

    def test_negative_limit(self):
        root = LimitNode(source=_leaf(), count=-1)
        assert _fired(root, _types()) == {"limit-sanity"}

    def test_output_arity(self):
        root = OutputNode(source=_leaf(("a",)), column_names=("x", "y"),
                          symbols=("a",))
        assert _fired(root, {"a": BIGINT}) == {"output-arity"}

    def test_nan_estimate(self):
        class NanEstimator:
            def stats(self, node):
                return PlanStats(float("nan"), {})

        root = _leaf()
        assert _fired(root, _types(), estimator=NanEstimator()) == {
            "estimate-sanity"
        }

    def test_vector_dimension_mismatch(self):
        """Tensor plane: dot_product over mismatched VECTOR dimensions must
        fail plan validation naming type-consistency, never inside a
        kernel."""
        from trino_tpu.spi.types import vector_type

        expr = Call(
            "dot_product",
            (Reference("a", vector_type(3)), Reference("b", vector_type(4))),
            DOUBLE,
        )
        root = ProjectNode(source=_leaf(), assignments=(("p", expr),))
        assert _fired(root, _types(p=DOUBLE)) == {"type-consistency"}

    def test_vector_arg_not_a_vector(self):
        from trino_tpu.spi.types import vector_type

        expr = Call(
            "cosine_similarity",
            (Reference("a", vector_type(3)), Reference("b", BIGINT)),
            DOUBLE,
        )
        root = FilterNode(
            source=_leaf(),
            predicate=Call("$gt", (expr, Constant(DOUBLE, 0.5)), BOOLEAN),
        )
        assert _fired(root, _types()) == {"type-consistency"}

    def test_linear_model_arity_mismatch(self):
        from trino_tpu.spi.types import UNKNOWN

        spec = ((1.0, 2.0, 3.0), 0.0)  # 3 weights...
        expr = Call(
            "$linear_model",
            (Constant(UNKNOWN, spec), Reference("a", DOUBLE)),  # ...1 feature
            DOUBLE,
        )
        root = ProjectNode(source=_leaf(), assignments=(("p", expr),))
        assert _fired(root, _types(p=DOUBLE)) == {"type-consistency"}

    def test_gbdt_model_arity_mismatch(self):
        from trino_tpu.spi.types import UNKNOWN

        # one depth-1 tree splitting on feature index 2...
        spec = (0.0, (((2,), (0.5,), (-1.0, 1.0)),))
        expr = Call(
            "$gbdt_model",
            (Constant(UNKNOWN, spec), Reference("a", DOUBLE)),  # ...1 feature
            DOUBLE,
        )
        root = ProjectNode(source=_leaf(), assignments=(("p", expr),))
        assert _fired(root, _types(p=DOUBLE)) == {"type-consistency"}

    def test_fused_topn_unprojected_sort_key(self):
        root = VectorTopNNode(
            source=_leaf(),
            assignments=(("p", Reference("a", BIGINT)),),
            count=5,
            orderings=(Ordering("zz"),),
        )
        assert _fired(root, _types(p=BIGINT)) == {"symbol-dependencies"}

    def test_fused_topn_negative_count(self):
        root = VectorTopNNode(
            source=_leaf(),
            assignments=(("p", Reference("a", BIGINT)),),
            count=-2,
            orderings=(Ordering("p"),),
        )
        assert _fired(root, _types(p=BIGINT)) == {"limit-sanity"}

    def test_every_checker_killed(self):
        """The mutation suite above covers the full checker set."""
        killed = {
            "symbol-dependencies", "no-duplicate-plan-node-ids",
            "unique-output-symbols", "type-consistency",
            "aggregation-validity", "window-validity",
            "exchange-partitioning", "union-consistency", "limit-sanity",
            "output-arity", "fte-determinism", "estimate-sanity",
        }
        assert killed == set(checker_ids())


class TestSanityErrorReporting:
    def test_error_names_checker_path_and_rule(self):
        root = ProjectNode(
            source=_leaf(), assignments=(("p", Reference("zz", BIGINT)),)
        )
        with pytest.raises(PlanSanityError) as ei:
            validate_intermediate(root, _types(p=BIGINT), rule="bogus_rule")
        err = ei.value
        assert err.checker == "symbol-dependencies"
        assert err.rule == "bogus_rule"
        assert "Project" in err.node_path
        assert "zz" in str(err)

    def test_validate_final_raises_on_corrupt_plan(self):
        plan = LogicalPlan(LimitNode(source=_leaf(), count=-3), _types())
        with pytest.raises(PlanSanityError) as ei:
            validate_final(plan, stage="add_exchanges")
        assert ei.value.rule == "add_exchanges"

    def test_optimizer_reports_offending_rule(self, monkeypatch):
        """An optimizer rule that corrupts the plan is named by the error."""
        from trino_tpu.planner import optimizer as opt
        from trino_tpu.runtime.local import LocalQueryRunner

        runner = LocalQueryRunner.tpch(scale=0.0005)
        runner.session.set("validate_plan", True)

        real = opt.optimizer_passes

        def sabotaged(metadata, types, session):
            passes = real(metadata, types, session)

            def corrupt(root):
                return LimitNode(source=root, count=-1)

            return passes[:3] + [("evil_rule", corrupt)] + passes[3:]

        monkeypatch.setattr(opt, "optimizer_passes", sabotaged)
        with pytest.raises(PlanSanityError) as ei:
            runner.plan_sql("SELECT count(*) FROM nation")
        assert ei.value.rule == "evil_rule"
        assert ei.value.checker == "limit-sanity"


SCALE = 0.0005


@pytest.fixture(scope="module")
def runner():
    from trino_tpu.runtime import LocalQueryRunner

    return LocalQueryRunner.tpch(scale=SCALE)


class TestTpchCorpusValidates:
    """Final + intermediate plan sanity across the full TPC-H corpus with
    validate_plan=true (the knob also defaults on under pytest, so every
    OTHER test in the suite exercises the checkers over its own queries —
    this class makes the 22-query contract explicit and adds the
    warm-history overlay)."""

    @pytest.mark.parametrize("name", sorted(__import__(
        "tests.tpch_corpus", fromlist=["TPCH_QUERIES"]).TPCH_QUERIES))
    def test_query_validates_through_exchanges(self, runner, name):
        from tests.tpch_corpus import TPCH_QUERIES
        from trino_tpu.planner.fragmenter import add_exchanges, create_fragments

        runner.session.set("validate_plan", True)
        plan = runner.plan_sql(TPCH_QUERIES[name])  # intermediate + final
        distributed = add_exchanges(plan, runner.metadata, runner.session)
        create_fragments(distributed)

    def test_corpus_validates_with_warm_history(self, runner):
        """history_based_stats=true over recorded actuals: the overlay
        changes estimates (possibly plans) but must keep every estimate
        finite/non-negative through every rule."""
        from tests.tpch_corpus import TPCH_QUERIES
        from trino_tpu.planner.fragmenter import add_exchanges

        runner.session.set("validate_plan", True)
        # warm the statistics-feedback history with real executions
        for name in ("q03", "q05", "q06"):
            runner.execute(TPCH_QUERIES[name])
        runner.session.set("history_based_stats", True)
        try:
            for name, sql in sorted(TPCH_QUERIES.items()):
                plan = runner.plan_sql(sql)
                add_exchanges(plan, runner.metadata, runner.session)
        finally:
            runner.session.properties.pop("history_based_stats", None)

    def test_fte_execution_validates(self):
        """The FTE tier (durable exchanges, retries, the adaptive join-mode
        flip below the plan layer) plans through the same validated
        optimize + add_exchanges path; the distributed smoke shape must
        stay bit-correct with validation explicitly on."""
        from trino_tpu.parallel.runner import DistributedQueryRunner

        r = DistributedQueryRunner.tpch(scale=0.001, n_workers=2)
        r.session.set("retry_policy", "TASK")
        r.session.set("validate_plan", True)
        r.session.set("join_distribution_type", "PARTITIONED")
        r.session.set("target_partition_rows", 500)
        rows = r.execute(
            "SELECT count(*) FROM lineitem JOIN orders ON l_orderkey = o_orderkey"
        ).rows
        assert rows and rows[0][0] > 0


TPCDS_CANON = (
    "/root/reference/testing/trino-benchmark-queries/src/main/resources/sql/trino/tpcds"
)


@pytest.mark.skipif(not os.path.isdir(TPCDS_CANON),
                    reason="reference checkout not available")
class TestTpcdsCorpusValidates:
    """Every canonical TPC-DS query optimizes + places exchanges cleanly
    under intermediate + final sanity checks."""

    @pytest.fixture(scope="class")
    def ds_runner(self):
        from trino_tpu.connectors import tpcds as ds
        from trino_tpu.runtime import LocalQueryRunner

        r = LocalQueryRunner(Session(catalog="tpcds", schema="sf0_001"))
        r.register_catalog("tpcds", ds.TpcdsConnector(scale=0.001))
        r.session.set("validate_plan", True)
        return r

    def test_corpus_validates(self, ds_runner):
        import glob
        import sys

        from trino_tpu.planner.fragmenter import add_exchanges

        sys.setrecursionlimit(20000)  # q08-class IN-lists recurse in the parser
        failures = []
        for path in sorted(glob.glob(os.path.join(TPCDS_CANON, "q*.sql"))):
            sql = open(path).read().strip().rstrip(";")
            sql = sql.replace('"${database}"."${schema}".', "")
            sql = sql.replace("${database}.${schema}.", "")
            try:
                plan = ds_runner.plan_sql(sql)
                add_exchanges(plan, ds_runner.metadata, ds_runner.session)
            except PlanSanityError as e:
                failures.append((os.path.basename(path), str(e)[:120]))
            except Exception:
                # parse/plan gaps are the conformance suite's concern, not
                # the sanity plane's
                continue
        assert not failures, failures


class TestEngineLint:
    """Tier-1 gate: the lint suite over trino_tpu/ has zero non-baselined
    findings, and each rule is killed by a seeded bad snippet."""

    def test_lint_trino_tpu_clean(self):
        from tools.lint import run_lint

        result = run_lint()
        new = [f"{f.file}:{f.line} [{f.rule}] {f.message}"
               for f in result.findings]
        assert not new, new

    def test_rule_count(self):
        from tools.lint.rules import ALL_RULES

        assert len(ALL_RULES) >= 5
        assert len({r.id for r in ALL_RULES}) == len(ALL_RULES)

    # ---------------------------------------------------------- rule kills

    def _lint_snippet(self, tmp_path, relpath, source, rules=None):
        from tools.lint.engine import LintEngine
        from tools.lint.rules import ALL_RULES

        full = tmp_path / relpath
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(source)
        engine = LintEngine(list(rules or ALL_RULES), root=str(tmp_path))
        return engine.lint_file(str(full))

    def test_kill_blocking_call_under_lock(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "import time\n"
            "def f(self):\n"
            "    with self._lock:\n"
            "        time.sleep(1)\n"
        ))
        assert [f.rule for f in findings] == ["blocking-call-under-lock"]

    def test_kill_nested_acquire_and_foreign_wait(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "def f(self):\n"
            "    with self._lock:\n"
            "        other.acquire()\n"
            "        self._other_cond.wait()\n"
            "    with self._cond:\n"
            "        self._cond.wait()\n"  # waiting on the held cond is fine
        ))
        assert [f.rule for f in findings] == ["blocking-call-under-lock"] * 2

    def test_io_lock_exemption(self, tmp_path):
        # the sanctioned dedicated-I/O-serialization-lock pattern
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "def f(self):\n"
            "    with self._io_lock:\n"
            "        with open('x', 'a') as fh:\n"
            "            fh.write('y')\n"
        ))
        assert findings == []

    def test_kill_unpaired_flight_span(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "def f():\n"
            "    sp = RECORDER.span('a', 'b')\n"
            "    with RECORDER.span('c', 'd'):\n"
            "        pass\n"
        ))
        assert [f.rule for f in findings] == ["unpaired-flight-span"]
        assert findings[0].line == 2

    def test_kill_metric_help_missing(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "REGISTRY.counter('x_total')\n"
            "REGISTRY.counter('y_total', help='')\n"
            "REGISTRY.counter('z_total', help='a real description')\n"
            "REGISTRY.counter('p_total', {'l': 'v'}, 'positional')\n"
            "REGISTRY.counter('q_total', {'l': 'v'}, '')\n"
        ))
        assert [f.rule for f in findings] == ["metric-help-missing"] * 3
        assert {f.line for f in findings} == {1, 2, 5}

    def test_kill_metric_name_conformance(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "REGISTRY.counter('trino_tpu_things', help='h')\n"        # bad
            "REGISTRY.counter('trino_tpu_things_total', help='h')\n"  # ok
            "_counter('trino_tpu_helper_things', 'h')\n"              # bad
            "_counter('trino_tpu_helper_things_total', 'h')\n"        # ok
            "REGISTRY.histogram('trino_tpu_lat_secs', help='h')\n"    # bad
            "REGISTRY.histogram('trino_tpu_lat_secs', help='h', "
            "buckets=[1, 2])\n"                                       # ok
        ))
        assert [f.rule for f in findings] == ["metric-name-conformance"] * 3
        assert {f.line for f in findings} == {1, 3, 5}

    def test_metric_name_rule_ignores_foreign_counters(self, tmp_path):
        # a non-registry call named counter() with a non-metric literal is
        # not a metric registration; gauges carry no _total requirement
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "collections.Counter('abc')\n"
            "words.counter('not_a_metric')\n"
            "REGISTRY.gauge('trino_tpu_queries_running', help='h')\n"
        ))
        assert findings == []

    def test_kill_env_read_outside_knobs(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "import os\n"
            "E = 'TRINO_TPU_SOMETHING'\n"
            "a = os.environ.get('TRINO_TPU_FOO')\n"
            "b = os.environ['TRINO_TPU_BAR']\n"
            "c = os.environ.get(E)\n"
            "d = os.environ.get('NOT_OURS')\n"
            "e = os.environ[E]\n"
        ))
        assert [f.rule for f in findings] == ["env-read-outside-knobs"] * 4

    def test_env_rule_skips_knobs_module(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "trino_tpu/knobs.py", (
            "import os\n"
            "a = os.environ.get('TRINO_TPU_FOO')\n"
        ))
        assert findings == []

    def test_kill_bare_except_swallow(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "runtime/executor.py", (
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except:\n"
            "        pass\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        pass\n"
        ))
        assert [f.rule for f in findings] == ["bare-except-swallow"] * 2

    def test_swallow_ok_outside_critical_paths(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "connectors/x.py", (
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except ValueError:\n"
            "        pass\n"
        ))
        assert findings == []

    def test_kill_undeclared_session_property(self, tmp_path):
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "def f(session):\n"
            "    session.get('definitely_not_a_knob')\n"
            "    session.get('validate_plan')\n"
        ))
        assert [f.rule for f in findings] == ["undeclared-session-property"]

    def test_kill_unnamed_thread(self, tmp_path):
        # thread names are the host-profile/cluster-trace lane identity:
        # every Thread construction spelling must pass name=
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "import threading\n"
            "import threading as _th\n"
            "from threading import Thread\n"
            "a = threading.Thread(target=f)\n"
            "b = _th.Thread(target=f, daemon=True)\n"
            "c = Thread(target=f, args=(1,))\n"
        ))
        assert [f.rule for f in findings] == ["unnamed-thread"] * 3
        assert {f.line for f in findings} == {4, 5, 6}

    def test_unnamed_thread_ok_paths(self, tmp_path):
        # named construction, kwargs forwarding, and non-Thread callables
        ok = self._lint_snippet(tmp_path, "runtime/x.py", (
            "import threading\n"
            "a = threading.Thread(target=f, name='worker-http-8080')\n"
            "b = threading.Thread(**kwargs)\n"
            "c = threading.Timer(1.0, f)\n"
        ))
        assert ok == []

    def test_unnamed_thread_baseline_empty(self):
        # the engine migration is total: no file carries a baselined
        # unnamed-thread finding
        import json

        from tools.lint.engine import BASELINE_PATH

        with open(BASELINE_PATH) as f:
            baseline = json.load(f)
        assert not [e for e in baseline if "unnamed-thread" in str(e)]

    def test_kill_jit_without_cost_hook(self, tmp_path):
        # every form a raw jax.jit takes in the engine: decorator,
        # partial-wrapped decorator, and plain call
        findings = self._lint_snippet(tmp_path, "runtime/x.py", (
            "import jax\n"
            "from functools import partial\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x\n"
            "g = partial(jax.jit, static_argnums=(0,))(f)\n"
            "h = jax.jit(f, static_argnums=(0,))\n"
        ))
        assert [f.rule for f in findings] == ["jit-without-cost-hook"] * 3
        assert {f.line for f in findings} == {3, 6, 7}

    def test_jit_rule_ok_paths(self, tmp_path):
        # the wrapper itself and non-jit jax attributes stay clean
        ok = self._lint_snippet(tmp_path, "runtime/x.py", (
            "import jax\n"
            "from . import kernelcost\n"
            "@kernelcost.jit\n"
            "def f(x):\n"
            "    return jax.vmap(f)(x)\n"
        ))
        assert ok == []
        suppressed = self._lint_snippet(tmp_path, "runtime/y.py", (
            "import jax\n"
            "j = jax.jit(abs)  # lint: disable=jit-without-cost-hook -- tested reason\n"
        ))
        assert suppressed == []

    def test_jit_rule_baseline_empty(self):
        # the migration is total: no engine file carries a baselined raw
        # jax.jit (the one sanctioned site suppresses inline with a reason)
        import json

        from tools.lint.engine import BASELINE_PATH

        with open(BASELINE_PATH) as f:
            baseline = json.load(f)
        assert not [
            e for e in baseline if "jit-without-cost-hook" in str(e)
        ]

    def test_suppression_requires_reason(self, tmp_path):
        with_reason = self._lint_snippet(tmp_path, "runtime/executor.py", (
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:  # lint: disable=bare-except-swallow -- tested reason\n"
            "        pass\n"
        ))
        assert with_reason == []
        without = self._lint_snippet(tmp_path, "runtime/fte_scheduler.py", (
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:  # lint: disable=bare-except-swallow\n"
            "        pass\n"
        ))
        assert len(without) == 1 and "without a reason" in without[0].message

    def test_json_entry_point(self):
        import json
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable, "-m", "tools.lint", "--format", "json"],
            cwd=REPO, capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["new"] == []

    def test_shared_help_rule_runtime_half(self):
        from tools.lint.rules import registry_help_problems

        class FakeRegistry:
            def collect(self):
                return [
                    {"name": "good_total", "help": "fine"},
                    {"name": "bad_total", "help": ""},
                ]

        problems = registry_help_problems(FakeRegistry(), required=("missing_x",))
        assert any("bad_total" in p for p in problems)
        assert any("missing_x" in p for p in problems)


class TestKnobRegistry:
    """The central knob registry (satellite): every TRINO_TPU_* env var is
    declared, accessors enforce declaration, and the generated doc table in
    ARCHITECTURE.md matches the generator (no drift)."""

    def test_undeclared_env_knob_rejected(self):
        from trino_tpu import knobs

        with pytest.raises(KeyError):
            knobs.env_str("TRINO_TPU_NOT_DECLARED")

    def test_every_source_env_var_is_declared(self):
        """Grep the tree for TRINO_TPU_* literals; each must be a declared
        knob (docstrings and the knobs module itself included — an
        undeclared name anywhere is either a typo or undeclared config)."""
        import re

        from trino_tpu import knobs

        declared = {k.name for k in knobs.ENV_KNOBS}
        pat = re.compile(r"TRINO_TPU_[A-Z_]+")
        undeclared = {}
        root = os.path.join(REPO, "trino_tpu")
        for dirpath, _dirs, files in os.walk(root):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                for name in pat.findall(open(path).read()):
                    if name not in declared:
                        undeclared.setdefault(name, path)
        assert not undeclared, undeclared

    def test_session_defaults_built_from_registry(self):
        from trino_tpu import knobs

        assert set(Session.DEFAULTS) == set(knobs.session_property_names())
        # every declared property carries a non-empty description
        assert all(p.description for p in knobs.SESSION_PROPERTIES)

    def test_validate_plan_defaults_on_under_pytest(self):
        # PYTEST_CURRENT_TEST is set while this test runs
        assert Session().get("validate_plan") is True

    def test_validate_plan_env_override(self, monkeypatch):
        monkeypatch.setenv("TRINO_TPU_VALIDATE_PLAN", "0")
        assert Session().get("validate_plan") is False
        monkeypatch.setenv("TRINO_TPU_VALIDATE_PLAN", "1")
        assert Session().get("validate_plan") is True

    def test_architecture_knob_table_not_drifted(self):
        from trino_tpu import knobs

        doc = open(os.path.join(REPO, "ARCHITECTURE.md")).read()
        assert knobs.knob_table_markdown() in doc, (
            "ARCHITECTURE.md knob table drifted: run "
            "`python -m trino_tpu.knobs --write`"
        )
