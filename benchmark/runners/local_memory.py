"""Runner `local_memory`: one `LocalQueryRunner` over the TPC-H generator with
the memory catalog registered; the configuration's tables are loaded by CREATE
TABLE AS and stay resident on the one device."""


def start(config: dict):
    """The object `CoordinatorServer` is built over."""
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.runtime import LocalQueryRunner

    runner = LocalQueryRunner.tpch(scale=config["scale_factor"])
    runner.register_catalog("memory", MemoryConnector())
    return runner


def create_tables(runner, served) -> None:
    """CREATE TABLE AS by `runner` into its memory catalog, the tables
    device-resident, and each described into `served.table_rows` and
    `served.column_types`."""
    source = runner.session.schema
    for table in served.config["tables"]:
        res = runner.execute(
            f"CREATE TABLE memory.default.{table} AS SELECT * FROM tpch.{source}.{table}"
        )
        served.table_rows[table] = int(res.rows[0][0])
        described = runner.execute(f"DESCRIBE memory.default.{table}").rows
        served.column_types[table] = {name: kind for name, kind in described}


def load(served) -> None:
    create_tables(served.runner, served)
