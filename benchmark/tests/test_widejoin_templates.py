"""The wide-join templates (q04, q07, q08, q12, q19): the domains are the
specification's and each tuple makes a statement of its own, no tuple the
tests' seeds draw has rows that tie on the specification's ORDER BY
(`assumed.order_of_equal_rows` of configs/tpch_widejoin_1chip.json), and the
reader `join_rows_per_query` on trees written out by hand and on the ring
recorded on the chip."""

import itertools
import json
import math
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import harness, population
from benchmark.layer_metrics import join_rows_per_query
from benchmark.tests.conftest import SCALE
from benchmark.traffic import Traffic, load_mix, load_template

TEMPLATES = ["q04", "q07", "q08", "q12", "q19"]
SEEDS = [1, 5, 6, 7, 2**31 + 7, 2**31 + 11, 2**31 + 12, 4_000_000_000]   # those of test_correct.py
# cl. 2.4.4.3, 2.4.7.3, 2.4.8.3, 2.4.12.3, 2.4.19.3
SIZES = {"q04": 58, "q07": 600, "q08": 3750, "q12": 210, "q19": 10 * 11 * 11 * 25 ** 3}


@pytest.fixture(scope="module")
def host():
    _, config = harness.find_cell("resident_widejoin_stream")
    traffic = Traffic(load_mix("widejoin_stream"), 1, config["schema"])
    return harness.host_for(traffic, {**config, "scale_factor": SCALE})


def tuples(module):
    names = list(module.DOMAIN)
    for combo in itertools.product(*(module.DOMAIN[n] for n in names)):
        yield dict(zip(names, combo))


@pytest.mark.parametrize("name", TEMPLATES)
def test_each_tuple_of_the_domain_makes_a_statement_of_its_own(name):
    module = load_template(name)
    assert math.prod(len(v) for v in module.DOMAIN.values()) == SIZES[name]
    some = tuples(module) if SIZES[name] < 10_000 else itertools.islice(tuples(module), 0, None, 997)
    made = [module.SQL.format(schema="memory.default", **module.literals(p)) for p in some]
    assert len(set(made)) == len(made) and not any("{" in s or "}" in s for s in made)
    assert not hasattr(module, "SCANS")


def test_the_domains_are_the_specifications():
    q04, q07, q08, q12, q19 = (load_template(n) for n in TEMPLATES)
    assert q04.DOMAIN["month"][0] == "1993-01" and q04.DOMAIN["month"][-1] == "1997-10"
    pairs = q07.DOMAIN["nations"]
    assert pairs[0] == ["FRANCE", "GERMANY"] and len({tuple(p) for p in pairs}) == 600
    assert all(a != b and {a, b} <= {n for n, _ in population.NATIONS} for a, b in pairs)
    assert q08.DOMAIN["nation"][0] == "BRAZIL" and q08.DOMAIN["type"][0] == "ECONOMY ANODIZED STEEL"
    assert sorted(q08.DOMAIN["type"]) == population.PART_TYPES
    assert {q08.literals({"nation": n, "type": "x"})["region"] for n in ("FRANCE", "JAPAN")} == {"EUROPE", "ASIA"}
    modes = q12.DOMAIN["modes"]
    assert modes[0] == ["MAIL", "SHIP"] and len({tuple(m) for m in modes}) == 42
    assert all(a != b for a, b in modes) and q12.DOMAIN["year"] == [1993, 1994, 1995, 1996, 1997]
    assert [q19.DOMAIN[f"quantity{i}"][0] for i in (1, 2, 3)] == [1, 10, 20]
    assert [q19.DOMAIN[f"brand{i}"][0] for i in (1, 2, 3)] == ["Brand#12", "Brand#23", "Brand#34"]
    assert all(sorted(q19.DOMAIN[f"brand{i}"]) == population.BRANDS for i in (1, 2, 3))
    assert "AIR REG" not in population.SHIP_MODES and "REG AIR" in population.SHIP_MODES


@pytest.mark.parametrize("seed", SEEDS)
def test_no_drawn_tuple_ties_on_the_specifications_order(host, seed):
    traffic = Traffic(load_mix("widejoin_stream"), seed, "memory.default")
    assert len(traffic.statements) == 10
    for statement in traffic.statements:
        module = traffic.templates[statement.template]
        assert not module.ties(host, statement.params), statement.label


def _span(name, span_id, parent, start, end, **attributes):
    return {"name": name, "spanId": span_id, "parentSpanId": parent, "startNs": start, "endNs": end,
            "attributes": attributes}


def test_the_reader_by_hand():
    first = [
        _span("statement", 1, None, 0, 1000),
        _span("optimizer", 2, 1, 10, 20, derived_predicates=2),
        _span("execution", 3, 1, 20, 900),
        _span("op:JoinNode", 4, 3, 30, 400, kind="INNER", probe_rows=1000, build_rows=20),
        _span("op:JoinNode", 5, 4, 40, 300, kind="INNER", probe_rows=300, build_rows=2),
        _span("op:AggregationNode", 6, 3, 400, 800, rows_in=40),
    ]
    second = [
        _span("statement", 7, None, 2000, 2500),
        _span("optimizer", 8, 7, 2010, 2020, derived_predicates=0),
        _span("op:SemiJoinNode", 9, 7, 2100, 2400, probe_rows=50, build_rows=600),
    ]
    assert join_rows_per_query.rows(first) == 1322 and join_rows_per_query.rows(second) == 650
    assert join_rows_per_query.of([first, second]) == pytest.approx((1322 + 650) / 2)
    assert join_rows_per_query.derived([first, second]) == 2
    records = [SimpleNamespace(start=2.0, statement=SimpleNamespace(template="q04")),
               SimpleNamespace(start=0.0, statement=SimpleNamespace(template="q07"))]
    assert join_rows_per_query.by_template([second, first], records) == {"q04": 650.0, "q07": 1322.0}
    run = SimpleNamespace(_statement_trees=[first, second], records=records, notes={})
    assert join_rows_per_query.read(run) == pytest.approx(986.0)
    assert run.notes == {"join_rows_by_template": {"q04": 650.0, "q07": 1322.0}, "derived_predicates": 2}
    # a program whose optimizer states no `derived_predicates`: the mean all the same, the note left out
    for tree in (first, second):
        tree[1]["attributes"].pop("derived_predicates")
    run = SimpleNamespace(_statement_trees=[first, second], records=records, notes={})
    assert join_rows_per_query.read(run) == pytest.approx(986.0) and "derived_predicates" not in run.notes
    # nothing to read: None, never 0
    assert join_rows_per_query.read(SimpleNamespace(_statement_trees=None, notes={})) is None


def test_the_reader_on_the_ring_recorded_on_the_chip():
    """The ring of the stream cell recorded on the chip: its one join states
    no `probe_rows` (it was recorded before joins stated them), so there is
    nothing to read."""
    recorded = json.loads((Path(__file__).parent / "recorded_statements.json").read_text())
    trees = recorded["trees"]
    assert any(s["name"] == "op:JoinNode" for t in trees for s in t)
    assert join_rows_per_query.of(trees) is None and join_rows_per_query.derived(trees) is None
