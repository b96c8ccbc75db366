"""One statement at a time on the chip: each template of a benchmark cell cold
(compiling), warm and traced alone, through the cell's own runner and the
served path, reduced by `benchmark/trace.py`.

    chiprun --timeout 1500 -- python3 tools/statement_profile.py chiprun_out/<dir>/profile.json \
        --workload resident_subquery_stream [--seed 1] [q21 q17 ...]

Run it from the root of the tree to be profiled (a copy under `chip_stage/`
for the parent). Per template it writes the cold and warm seconds with their
compile counts, the device's busy seconds and launches of the traced run,
the device seconds by program and by operation, the statement's spans with
their attributes, and the peak bytes. `JAX_LOG_COMPILES=1` lists what each
program took to compile on stderr. PERF.md section 5 is written from it
(PR 34, PR 36). A time from a CPU run of it is not a device number: without a
TPU it runs cold and warm only."""

import argparse
import glob
import json
import os
import random
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("out")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0, help="draws each template's parameters")
    parser.add_argument("--scale", type=float, help="for a rehearsal on the CPU; default the configuration's")
    parser.add_argument("templates", nargs="*")
    args = parser.parse_args(argv)

    import jax

    from benchmark import harness
    from benchmark import trace as tracing
    from benchmark.traffic import Statement, draw_params, load_mix, load_template
    from trino_tpu.runtime.tracing import TRACER

    cell, config = harness.find_cell(args.workload)
    if args.scale is not None:
        config = {**config, "scale_factor": args.scale}
    names = args.templates or [t["name"] for t in load_mix(cell["traffic"])["templates"]]
    started = time.time()
    compilations = harness.Compilations()
    served = harness.Served(config)
    served.load()
    result = {"load_s": time.time() - started, "after_load": harness.device_facts()}
    print("loaded", result["load_s"], result["after_load"], flush=True)
    client = served.client()
    for i, name in enumerate(names):
        module = load_template(name)
        params = draw_params(module.DOMAIN, random.Random(f"{args.seed}:params:{name}"), 1)[0]
        sql = module.SQL.format(schema=config["schema"], **module.literals(params))
        statement = Statement(i, name, params, sql)
        entry = result[name] = {"params": params}
        for phase in ("cold", "warm"):
            before = compilations.read()
            record = harness.send(served, client, statement, annotate=False)
            entry[phase + "_s"] = record.latency
            entry[phase + "_compiles"] = harness.Compilations.between(before, compilations.read())
            entry["error"], entry["rows"] = record.error, (record.rows or [])[:3]
            print(name, phase, record.latency, entry[phase + "_compiles"], record.error, flush=True)
            if record.error:
                break
        entry["peak_after"] = harness.device_facts()["memory_peak_bytes"]
        if not entry["error"] and jax.default_backend() == "tpu":
            entry.update(traced(jax, tracing, harness, TRACER, served, client, statement))
            print(name, "traced", entry["traced_s"], "busy", entry["busy_s"], "launches", entry["launches"],
                  entry["by_program"][:6], flush=True)
        with open(args.out, "w") as out:
            json.dump(result, out)
    served.stop()
    return 0


def traced(jax, tracing, harness, tracer, served, client, statement) -> dict:
    """One more run of `statement` under the profiler, reduced."""
    where = str(harness.REPO / "benchmark_out" / "profile" / statement.template)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.host_tracer_level = 0, 2
    jax.profiler.start_trace(where, profiler_options=options)
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        record = harness.send(served, client, statement, annotate=True)
    jax.profiler.stop_trace()
    files = sorted(glob.glob(where + "/plugins/profile/*/*.xplane.pb"))
    device = tracing.reduce(tracing.load(files[-1])).fullest
    shutil.rmtree(where, ignore_errors=True)
    by_program: dict = {}
    for key, seconds in device.op_seconds.items():
        program, _, operation = key.partition(" ")
        if not operation.startswith(("%while", "%conditional", "%call")):  # their bodies are counted
            by_program[program] = by_program.get(program, 0.0) + seconds
    tree = [span.to_dict() for span in tracer.finished("statement")[-1]]
    spans = [
        [s["name"], round((s["startNs"] - tree[0]["startNs"]) / 1e6, 1), round((s["endNs"] - s["startNs"]) / 1e6, 1),
         {k: v for k, v in s["attributes"].items() if not k.endswith("_types")}]
        for s in tree if s["endNs"] is not None
    ]
    return {
        "traced_s": record.latency, "busy_s": device.busy_s, "launches": device.launches,
        "by_program": sorted(by_program.items(), key=lambda kv: -kv[1]),
        "top_ops": sorted(device.op_seconds.items(), key=lambda kv: -kv[1])[:40], "spans": spans,
    }


if __name__ == "__main__":
    sys.exit(main())
