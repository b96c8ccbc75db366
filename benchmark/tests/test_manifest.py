"""BENCHMARK.json and every file it leads to load, and name only things that exist."""

import importlib
import json
import re
from pathlib import Path

import pytest

from benchmark import harness
from benchmark.traffic import Traffic, load_mix

REPO = Path(__file__).resolve().parents[2]
BENCH = harness.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_keys_and_names():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert BENCH["command"] == ["python3", "benchmark/run.py"] and BENCH["paths"] == ["benchmark"]
    names = [e["name"] for group in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[group]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
        assert m["name"] in harness.END_TO_END
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(config):
    held = json.loads((REPO / config["file"]).read_text())
    assert held["name"] == config["name"] and held["source"] == config["source"]
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert set(config["reduced"]) == set(held["reduced"])  # every cut is a key of the file
    assert all(key in held for key in config["reduced"])
    assert held["chips"] in (1, 4)
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    runner = importlib.import_module(f"benchmark.runners.{held['runner']}")  # found by name
    assert callable(runner.start) and callable(runner.load)
    assert callable(getattr(runner, "check", len))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files(cell):
    _, config = harness.find_cell(cell["name"])
    assert cell["chips"] == config["chips"] and len(cell["why"]) <= 200
    traffic = Traffic(load_mix(cell["traffic"]), 3_000_000_123, config["schema"])
    assert set(traffic.templates) <= set(config["query_set"])
    for statement in traffic.statements:
        assert "{" not in statement.sql  # every parameter was given a literal
    assert len(traffic.streams) == traffic.clients
    mine = [{s.index for v in stream.by_template.values() for s in v} for stream in traffic.streams]
    assert sorted(i for m in mine for i in m) == list(range(len(traffic.statements)))  # its own each
    for c, stream in enumerate(traffic.streams):
        # a seeded cycle holds every statement of the stream once, whatever each template's k;
        # k passes in a fixed order hold them all too
        k = len(mine[c]) // len(traffic.templates)
        sent = [traffic.next(c) for _ in range(len(stream.order) * k if stream.order else len(mine[c]))]
        assert {s.index for s in sent} == mine[c]
        if stream.order:
            assert [s.template for s in sent] == stream.order * k
    # the cell reports setup_s, another end-to-end metric and a per-layer metric
    e2e = {m["name"] for m in harness.metrics_of(cell["name"], "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(cell["name"], "per_layer")


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric['name'].split('.')[0]}")
    assert callable(reader.read)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
    for cell in metric.get("workloads", []):  # each cell reports the metric it moves
        assert "workloads" not in moved or cell in moved["workloads"]
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_same_seed_same_traffic_and_seeds_differ():
    mix = load_mix("analytic_stream")
    a, b = Traffic(mix, 2**31 + 5, "s"), Traffic(mix, 2**31 + 5, "s")
    assert [s.sql for s in a.statements] == [s.sql for s in b.statements]
    assert [a.next(0).index for _ in range(40)] == [b.next(0).index for _ in range(40)]
    c = Traffic(mix, 2**31 + 6, "s")
    assert [s.sql for s in a.statements] != [s.sql for s in c.statements]
    # the same amount of work whatever the seed: each template as often
    assert sorted(s.template for s in a.statements) == sorted(s.template for s in c.statements)


def test_stream_orders_are_the_sources_ordered_sets_cut_to_the_query_set():
    for cell in BENCH["workloads"]:
        mix = load_mix(cell["traffic"])
        if "stream_orders" not in mix:
            continue
        kept = {int(t["name"][1:]): t["name"] for t in mix["templates"]}
        rows = [mix["source_orders"][str(s + 1)] for s in range(mix["clients"])]
        assert all(sorted(row) == list(range(1, 23)) for row in rows)
        assert mix["stream_orders"] == [[kept[q] for q in row if q in kept] for row in rows]


def test_peaks_table_knows_the_chip_and_refuses_others():
    peaks, widths = harness.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 8.19e11 and widths["decimal"] == 8
    with pytest.raises(LookupError):
        harness.peaks_for("cpu")
