"""Layer: kernels. Seconds of the grouping and ordering programs (group sort, aggregate, ORDER BY) among
the fullest device's operations in the traced window, as a share of its busy
seconds (`_operators.GROUP_PROGRAMS` lists the programs). None where the trace
shows none of them."""

from benchmark.layer_metrics import _operators as ops


def read(run):
    return ops.device_share(run, ops.GROUP_PROGRAMS)
