"""`python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`

One run of one cell of BENCHMARK.json on the chips of this machine; the last
line of standard output is the result (README.md)."""

import time

T0 = time.perf_counter()  # set-up is counted from the start of the process

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    from benchmark import harness

    return harness.run(args.workload, args.seed, args.seconds, bool(args.trace), T0)


if __name__ == "__main__":
    sys.exit(main())
