"""The control of "How `correct` is decided": the reference evaluated in
float32, put in the program's place and compared with the exact reference at
the cell's own scale, for several seeds. It has to come out as not correct.

    python3 benchmark/control.py --workload resident_analytic_stream --seeds 1 2 3

Needs no chip (both sides are host evaluations); prints one JSON line per seed
with every number compared beside its limit. PERF.md holds the readings."""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--scale", type=float, help="for the tests; default the configuration's")
    args = parser.parse_args(argv)
    from benchmark import harness
    from benchmark.traffic import Traffic, load_mix

    cell, config = harness.find_cell(args.workload)
    if args.scale is not None:
        config = {**config, "scale_factor": args.scale}
    host = None
    all_failed = True
    for seed in args.seeds:
        traffic = Traffic(load_mix(cell["traffic"]), seed, config["schema"])
        host = host or harness.host_for(traffic, config)
        stand_ins = [("control", harness.Record(s, 0.0, 0.0)) for s in traffic.statements]
        comparison, _ = harness.judge(stand_ins, traffic, config, control=True, host=host)
        all_failed &= not comparison.correct
        print(json.dumps({"seed": seed, "arith": "float32", "correct": comparison.correct,
                          "least_double_gap": min(comparison.double_gaps, default=None),
                          "compared": comparison.report()}), flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
