"""The benchmark's own tests run on the CPU at SF0.01; nothing here describes
or touches a TPU. The four-chip configuration runs on four host devices,
which have to be asked for before JAX loads.

`held_out.json` holds the entries of a cell that is proven here and not
admitted to BENCHMARK.json (PERF.md section 7). The tests see the manifest with
those entries laid over it, so each test of a cell, a configuration or a
metric covers them as it covers the others."""

import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
).strip()
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness  # noqa: E402

SCALE = 0.01
HELD_OUT = json.loads((Path(__file__).parent / "held_out.json").read_text())
_admitted = harness.manifest


def manifest() -> dict:
    bench = _admitted()
    for group in ("configs", "workloads", "per_layer"):
        have = {e["name"] for e in bench[group]}
        bench[group] = bench[group] + [e for e in HELD_OUT[group] if e["name"] not in have]
    for metric in bench["end_to_end"]:
        for cell in HELD_OUT["end_to_end_workloads"].get(metric["name"], []):
            if "workloads" in metric and cell not in metric["workloads"]:
                metric["workloads"] = metric["workloads"] + [cell]
    return bench


harness.manifest = manifest
