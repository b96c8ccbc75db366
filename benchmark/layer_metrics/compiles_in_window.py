"""Layer: executor / XLA. Compilations inside the window, from the program's
counters (`trino_tpu_xla_compiles_total` minus the persistent cache's hits).
Warm-up has run every statement of the seed, so this should read 0."""


def read(run):
    return run.compiles["compiled"] + run.compiles["from_cache"]
