"""Runner `mesh_memory`: `DistributedQueryRunner` over the `chips` devices of
one host, its `ici` tier (one shard_map program per statement, the exchanges
as collectives) over tables that a `LocalQueryRunner` loads by CREATE TABLE AS
into the one `MemoryConnector` both have registered. Every session property at
its default (`use_ici_exchange` is true by default); the server gets the
runner as it is, nothing wrapped.

`off_tier` is this runner's number under `compared`: the statements of
warm-up and window that the tier `ici` did not answer, whether it refused to
lower them, its capacity retries ran out, or it was switched off. It is read
statement by statement from the program's own spans (`TRACER`'s ring, one tree
per statement): only the mesh tier opens `mesh:program` (each attempt of the
one program) and `mesh:gather` (the answer's rows taken from it), so a tree
without both was answered elsewhere. A statement whose tree the ring no longer
holds counts as off the tier too."""

from benchmark.layer_metrics import _statements as st
from benchmark.runners import local_memory

ON_TIER = {"mesh:program", "mesh:gather"}


def start(config: dict):
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.parallel.runner import DistributedQueryRunner

    runner = DistributedQueryRunner.tpch(config["scale_factor"], n_workers=config["chips"])
    runner.catalogs.register("memory", MemoryConnector())
    return runner


def load(served) -> None:
    from trino_tpu.runtime import LocalQueryRunner

    loader = LocalQueryRunner.tpch(scale=served.config["scale_factor"])
    loader.register_catalog("memory", served.runner.catalogs.get("memory"))
    local_memory.create_tables(loader, served)


def off_tier(trees: list, statements: int) -> tuple:
    """(the statements of `statements` without a tree that shows the mesh
    tier's spans, what the first tree without them shows)."""
    on, first = 0, None
    for tree in trees:
        names = {s["name"] for s in tree if s["endNs"] is not None}
        if ON_TIER <= names:
            on += 1
        elif first is None:
            shown = sorted(n for n in names if n != st.STATEMENT)
            first = f"statement {tree[0]['attributes'].get('query_id')}: not on tier ici, its spans: {shown}"
    off = statements - on
    return off, first or (f"{off} statements have no tree in the tracer's ring" if off else None)


def check(served, records: list) -> dict:
    """{name: (value, limit, what went wrong first)} for `compared`: every
    record of warm-up and window has to have been answered on the mesh tier."""
    first = min(r.start for r in records) * 1e9
    last = max(r.end for r in records) * 1e9
    trees = [t for t in st.ring() or [] if first <= t[0]["startNs"] <= last]
    off, what = off_tier(trees, len(records))
    return {"off_tier": (off, 0, what)}
