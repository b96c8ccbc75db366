"""Layer: device. The share of the traced window in which the fullest device
was idle while the innermost span open on the host was one that waits for the
device: a device-to-host read (`sync:<site>`), the wait for the answer's last
program (`drain`), a transfer to the shards (`mesh:shard`). The host was not
working in those seconds: a launch's latency, a read's round trip, a copy.
`_idle.py` lays the ring over the trace; beside the number, under `notes`:
`idle_clock`, `idle_by_class`, `idle_by_span`, `idle_unspanned_between`,
`idle_longest`. None without a trace, a ring of the window's statements and a
clock that joins them."""

from benchmark.layer_metrics import _idle


def read(run):
    return _idle.share(run, "wait")
