"""Layer: kernels. For the window's statements of the templates that scan one
table (they state `SCANS`): the bytes each has to read, rows of the table times
the width of the columns it names (types from DESCRIBE, widths from
benchmark/peaks.json, not from what the program lowered to), over the chip's
peak bytes/s, as a share of the device-busy time inside those statements'
spans. HBM-bound: the arithmetic is a few operations per byte."""

import re


def bytes_of(statement_scans: dict, run) -> int:
    widths = run.type_bytes
    total = 0
    for table, columns in statement_scans.items():
        for column in columns:
            kind = re.sub(r"\(.*\)", "", run.column_types[table][column])
            total += run.table_rows[table] * widths[kind]
    return total


def read(run):
    if run.trace is None:
        return None
    scanning = {n: m.SCANS for n, m in run.traffic.templates.items() if hasattr(m, "SCANS")}
    busy_s, _ = run.trace.busy_inside(set(scanning))
    sent = [name for name, _, _, whole in run.trace.spans if whole and name in scanning]
    if not sent or busy_s <= 0:
        return None
    least_s = sum(bytes_of(scanning[name], run) for name in sent) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s
