"""Runner `local_memory_joins`: `local_memory` (one `LocalQueryRunner`, the
tables resident on the one device through the memory catalog, every session
property at its default), for the deployment whose statements are multi-way
joins and grouping by sort (`configs/tpch_joins_1chip.json`).

It differs in one thing: it refuses, at once and before any table is made, a
program whose sort family carries a page's columns inside its sorts. Such a
program answers these statements, but compiles them for hours on a TPU (Q3
alone took 783 s at SF1, CHANGES.md PR 21; Q10's group sort is 15 sorts of about
30 operands, none of which compiled inside 150 s against a described v5e,
ISSUE 34), far outside what one run of the benchmark is allowed. Ending with
code 4 lets a caller tell "cannot run this deployment" from a run that hangs.
The test is for the kernel the compile wall was cured with (`K.sort_perm`,
PR 34), not for a version."""

from benchmark.runners import local_memory

REFUSED = 4


def start(config: dict):
    from trino_tpu.ops import kernels

    if not hasattr(kernels, "sort_perm"):
        print(
            "benchmark: this program's sorts carry a page's columns (no ops.kernels.sort_perm); "
            f"{config['name']} would compile for hours on a TPU and is not run",
            flush=True,
        )
        raise SystemExit(REFUSED)
    return local_memory.start(config)


load = local_memory.load
