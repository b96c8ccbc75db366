"""Runner `local_memory_wide`: `local_memory` (one `LocalQueryRunner`, the
tables resident on the one device through the memory catalog, every session
property at its default), for the wide-join deployment
(`configs/tpch_widejoin_1chip.json`).

It differs in one thing: it refuses, at once and before any table is made, a
program that cannot sort a page holding a double on this device. Q8 answers
(o_year, mkt_share) ORDER BY o_year, and mkt_share is a double; a program
that packs the page's columns into 32-bit words to gather them in the
sort's order bitcasts the double, which the TPU's compiler refuses
(UNIMPLEMENTED: "While rewriting computation to not contain X64 element
types"), so every Q8 fails there while the CPU answers it. Ending with code 4
lets a caller tell "cannot run this deployment" from a run that failed. The
test is for the feature, not for a version: the probe is a statement of that
shape, run where the cell runs."""

from benchmark.runners import local_memory

REFUSED = 4
PROBE = "SELECT k, x FROM (VALUES (2, CAST(0.5 AS double)), (1, CAST(0.25 AS double)), (3, CAST(0.125 AS double))) AS t (k, x) ORDER BY k"
ANSWER = [(1, 0.25), (2, 0.5), (3, 0.125)]


def start(config: dict):
    runner = local_memory.start(config)
    try:
        answer = [tuple(r) for r in runner.execute(PROBE).rows]
    except Exception as e:  # the device's compiler refuses the program
        answer = f"{type(e).__name__}: {str(e)[:300]}"
    if answer != ANSWER:
        print(
            f"benchmark: this program cannot sort a page holding a double here ({answer}); "
            f"Q8 of {config['name']} answers so, and the cell is not run",
            flush=True,
        )
        raise SystemExit(REFUSED)
    return runner


load = local_memory.load
