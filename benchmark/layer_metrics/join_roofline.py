"""Layer: kernels. The least bytes the window's joins and semi-joins have to
move (`_operators.join_bytes`, from the rows and SQL types their spans state
and the widths of benchmark/peaks.json) over the chip's peak bytes a second, as
a share of the device seconds of the join programs. HBM-bound: a join compares
and copies."""

from benchmark.layer_metrics import _operators as ops


def read(run):
    return ops.roofline(run, ops.JOIN_SPANS, ops.JOIN_PROGRAMS, ops.join_bytes)
