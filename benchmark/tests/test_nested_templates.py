"""The nested templates (q02, q11, q15, q16): every tuple of their domains
makes a statement, the domains are the specification's, and no tuple the
tests' seeds draw has rows that tie on the specification's ORDER BY
(`assumed.order_of_equal_rows` of configs/tpch_nested_1chip.json: evaluated
at SF3 over every tuple, Q11's three tied nations left out of its domain)."""

import itertools

import numpy as np
import pytest

from benchmark import harness, population
from benchmark.tests.conftest import SCALE
from benchmark.traffic import Traffic, load_mix, load_template

TEMPLATES = ["q02", "q11", "q15", "q16"]
SEEDS = [1, 5, 6, 7, 2**31 + 7, 2**31 + 11, 2**31 + 12, 4_000_000_000]   # those of test_correct.py


@pytest.fixture(scope="module")
def host():
    _, config = harness.find_cell("resident_nested_stream")
    traffic = Traffic(load_mix("nested_stream"), 1, config["schema"])
    return harness.host_for(traffic, {**config, "scale_factor": SCALE})


def tuples(module):
    names = list(module.DOMAIN)
    for combo in itertools.product(*(module.DOMAIN[n] for n in names)):
        yield dict(zip(names, combo))


@pytest.mark.parametrize("name", TEMPLATES)
def test_every_tuple_of_the_domain_makes_a_statement(name):
    module = load_template(name)
    statements = {module.SQL.format(schema="memory.default", **module.literals(p)) for p in tuples(module)}
    sizes = {"q02": 1250, "q11": 22, "q15": 58, "q16": 18000}   # cl. 2.4.2.3, 2.4.11.3, 2.4.15.3, 2.4.16.3
    assert len(statements) == sizes[name]        # each tuple a statement of its own
    assert not any("{" in s or "}" in s for s in statements)
    assert "ORDER BY" in module.SQL and not hasattr(module, "SCANS")


def test_the_domains_are_the_specifications():
    q11, q15, q16 = (load_template(n) for n in ("q11", "q15", "q16"))
    assert set(q11.DOMAIN["nation"]) | set(q11.TIED_AT_SF3) == {n for n, _ in population.NATIONS}
    assert len(q15.DOMAIN["month"]) == 58 and q15.DOMAIN["month"][-1] == "1997-10"
    assert q16.literals(next(tuples(q16)))["type"] == "ECONOMY ANODIZED"   # two syllables of a type
    sizes = q16.DOMAIN["sizes"]
    assert sizes[0] == [49, 14, 23, 45, 19, 3, 36, 9]       # the validation tuple, cl. 2.4.16.4
    assert all(len(s) == len(set(s)) == 8 and 1 <= min(s) and max(s) <= 50 for s in sizes)
    assert len({tuple(s) for s in sizes}) == len(sizes) == 24


@pytest.mark.parametrize("seed", SEEDS)
def test_no_drawn_tuple_ties_on_the_specifications_order(host, seed):
    traffic = Traffic(load_mix("nested_stream"), seed, "memory.default")
    assert len(traffic.statements) == 8
    for statement in traffic.statements:
        module = traffic.templates[statement.template]
        assert not module.ties(host, statement.params), statement.label


def test_q11s_ties_are_seen(host):
    """`ties` sees a tie where one is: two parts of equal value, made here."""
    q11 = load_template("q11")
    ps = host["partsupp"]
    tied = {**host, "partsupp": {k: v.copy() for k, v in ps.items()}}
    value = np.asarray(tied["partsupp"]["ps_supplycost"])
    value[:] = 100          # every partsupp row worth its availqty: many parts of equal value
    assert q11.ties(tied, {"nation": "GERMANY"})
