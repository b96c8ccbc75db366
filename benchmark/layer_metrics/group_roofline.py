"""Layer: kernels. The least bytes the window's aggregations and ORDER BYs
have to move (`_operators.group_bytes`: rows in times the width of keys and
aggregated columns plus groups out, from their spans and benchmark/peaks.json)
over the chip's peak bytes a second, as a share of the device seconds of the
grouping and ordering programs. HBM-bound."""

from benchmark.layer_metrics import _operators as ops


def read(run):
    return ops.roofline(run, ops.GROUP_SPANS, ops.GROUP_PROGRAMS, ops.group_bytes)
