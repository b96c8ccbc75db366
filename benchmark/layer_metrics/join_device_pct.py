"""Layer: kernels. Seconds of the join programs (match, expand, semi-join) among
the fullest device's operations in the traced window, as a share of its busy
seconds (`_operators.JOIN_PROGRAMS` lists the programs). None where the trace
shows none of them."""

from benchmark.layer_metrics import _operators as ops


def read(run):
    return ops.device_share(run, ops.JOIN_PROGRAMS)
