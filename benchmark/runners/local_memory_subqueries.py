"""Runner `local_memory_subqueries`: `local_memory` (one `LocalQueryRunner`,
the tables resident on the one device through the memory catalog, every
session property at its default), for the deployment whose statements are an
outer join, decorrelated aggregates and anti-joins
(`configs/tpch_subqueries_1chip.json`).

It differs in one thing: it refuses, at once and before any table is made, a
program whose grouped `min` and `max` are scatters. Such a program answers
these statements (the parent of PR 36 was `correct` on the chip), but one Q21
takes it 11.2 s, two scatters of 2.07 s for each of its two aggregations of
`lineitem`, so a 51 s window holds 13 to 16 statements: under the two whole
cycles of eight that a run of `resident_subquery_stream` has to hold, and
`queries_per_s` is then decided by where in a cycle the window ends (the
driver's six runs of that program spread 5.9% where the bound is 6%; a
simulation of the generator gives 8%). That is no measurement the cell could
be compared against. Ending with code 4 lets a caller tell "cannot run this
deployment" from a run that failed. The test is for the kernel the scatters
were replaced with (`K.segment_running`, PR 36), not for a version."""

from benchmark.runners import local_memory

REFUSED = 4


def start(config: dict):
    from trino_tpu.ops import kernels

    if not hasattr(kernels, "segment_running"):
        print(
            "benchmark: this program's grouped min and max are scatters (no ops.kernels.segment_running); "
            f"a window of {config['name']} would hold under two cycles of its statements and is not run",
            flush=True,
        )
        raise SystemExit(REFUSED)
    return local_memory.start(config)


load = local_memory.load
