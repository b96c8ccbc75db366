"""The comparison that decides `correct`: the reference against the engine at
SF0.01 for several seeds' literals; a whole run of the harness without the look
for a chip; the float32 control; and the timed path broken underneath."""

import importlib
import io
import json
import time
from decimal import Decimal

import pytest

from benchmark import control, harness
from benchmark import reference as ref
from benchmark.tests.conftest import SCALE
from benchmark.traffic import Traffic, load_mix

BENCH = harness.manifest()
CELLS = [w["name"] for w in BENCH["workloads"]]
ONE_CLIENT = [w["name"] for w in BENCH["workloads"] if load_mix(w["traffic"])["clients"] == 1]
CONCURRENT = next(c for c in CELLS if c not in ONE_CLIENT)
MESH = [w["name"] for w in BENCH["workloads"] if w["chips"] > 1]
SMALL = {"scale_factor": SCALE}


def drive(cell, seed, **kwargs):
    out = io.StringIO()
    rc = harness.run(cell, seed, 2.0, False, time.perf_counter(), need_chips=False,
                     config_overrides=SMALL, out=out, **kwargs)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module", params=[c["name"] for c in BENCH["configs"]])
def served(request):
    config = json.loads((harness.ROOT / "configs" / f"{request.param}.json").read_text())
    served = harness.Served({**config, **SMALL})
    served.load()
    yield served
    served.stop()


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 4_000_000_000])
def test_reference_equals_the_engine(served, seed):
    client = served.client()
    for mix in (w["traffic"] for w in BENCH["workloads"] if w["config"] == served.config["name"]):
        traffic = Traffic(load_mix(mix), seed, served.config["schema"])
        assert set(traffic.templates) <= set(served.config["query_set"])
        rows = [("test", harness.send(served, client, s, annotate=False)) for s in traffic.statements]
        comparison, right = harness.judge(rows, traffic, served.config)
        assert comparison.correct, comparison.report()
        assert len(right) == len(rows) == comparison.compared
        assert comparison.values["double_rel_gap"] < 1e-12


@pytest.mark.parametrize("cell", CELLS)
def test_a_whole_run_is_correct_and_prints_the_contract_line(cell):
    line = drive(cell, 2**31 + 11)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    wanted = {m["name"] for m in harness.metrics_of(cell, "end_to_end")} - {"peak_hbm_bytes"}
    assert set(line["metrics"]) == wanted  # the CPU reports no device memory
    assert all(v["value"] > 0 for v in line["metrics"].values())
    held = {k: v for k, v in line["compared"].items() if "limit" in v}
    assert all(v["value"] <= v["limit"] for v in held.values())
    assert ("off_tier" in held) == (cell in MESH)  # the mesh runner's own number, by statement


@pytest.mark.parametrize("cell", CELLS)
def test_the_float32_control_fails(cell, capsys):
    assert control.main(["--workload", cell, "--seeds", "5", "6", "7", "--scale", str(SCALE)]) == 0
    for text in capsys.readouterr().out.strip().splitlines():
        seen = json.loads(text)
        assert seen["correct"] is False
        assert seen["compared"]["exact_cells_wrong"]["value"] > 0  # q06's and q01's sums
        if any(t.startswith("q14") for t in harness.find_cell(cell)[1]["query_set"]):  # a double
            assert seen["compared"]["double_rel_gap"]["value"] > 3 * ref.DOUBLE_REL_LIMIT


def _alter(value):
    if isinstance(value, Decimal):
        return value + Decimal(1).scaleb(value.as_tuple().exponent)  # one unit in the last place
    if isinstance(value, float):
        return value * (1 + 1e-6)
    return value + 1 if isinstance(value, int) else value


@pytest.fixture
def broken(monkeypatch):
    """Breaks the timed path underneath the server: `after` SELECTs the runner
    answers as it should, from then on one value of each answer is altered
    where it is produced; with `half`, every other order's lines are left out
    of lineitem when it is loaded."""
    from trino_tpu.parallel.runner import DistributedQueryRunner
    from trino_tpu.runtime import LocalQueryRunner

    def install(after=None, half=False):
        seen = [0]

        def breaking(real):
            def execute(self, sql, *args, **kwargs):
                if half and sql.startswith("CREATE TABLE memory.default.lineitem "):
                    sql += " WHERE l_orderkey % 2 = 0"
                result = real(self, sql, *args, **kwargs)
                if after is not None and sql.lstrip().startswith("SELECT"):
                    seen[0] += 1
                    if seen[0] > after and result.rows:
                        first = list(result.rows[0])
                        at = next(i for i, v in enumerate(first) if not isinstance(v, str))
                        first[at] = _alter(first[at])
                        result.rows[0] = type(result.rows[0])(first)
                return result

            return execute

        for runner in (LocalQueryRunner, DistributedQueryRunner):  # whichever the cell serves
            monkeypatch.setattr(runner, "execute", breaking(runner.execute))

    return install


@pytest.mark.parametrize("cell", ONE_CLIENT)
def test_an_answer_altered_in_the_window_is_not_correct(broken, cell):
    cell_, config = harness.find_cell(cell)
    warm = len(Traffic(load_mix(cell_["traffic"]), 2**31 + 12, config["schema"]).statements)
    broken(after=warm + 2)  # the warm-up's statements and the window's first 2 stay right
    line = drive(cell, 2**31 + 12)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] > 2
    wrong = line["compared"]
    assert wrong["exact_cells_wrong"]["value"] > 0 or wrong["double_rel_gap"]["value"] > 1e-9
    assert "window" in wrong["first_wrong"]
    # a wrong answer is not a completed statement
    assert sum(t["n"] for t in line["by_template"].values()) == 2


@pytest.mark.parametrize("cell", [CONCURRENT] + MESH)
def test_half_of_the_rows_left_out_is_not_correct(broken, cell):
    broken(half=True)
    line = drive(cell, 2**31 + 13)
    assert line["correct"] is False
    assert line["compared"]["exact_cells_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", MESH)
def test_a_statement_off_the_mesh_tier_is_not_correct(monkeypatch, cell):
    """With `use_ici_exchange` off the staged tier answers, and answers right:
    only the runner's own number, `off_tier`, refuses the run."""
    module = importlib.import_module(f"benchmark.runners.{harness.find_cell(cell)[1]['runner']}")
    real = module.start

    def start(config):
        runner = real(config)
        runner.session.set("use_ici_exchange", False)
        return runner

    monkeypatch.setattr(module, "start", start)
    line = drive(cell, 2**31 + 14)
    assert line["correct"] is False and line["failed"] == 0
    wrong = line["compared"]
    assert wrong["off_tier"]["value"] == wrong["statements_compared"]["value"] > 0
    assert wrong["exact_cells_wrong"]["value"] == 0 and wrong["unanswered"]["value"] == 0
    assert "not on tier ici" in wrong["first_wrong"]
