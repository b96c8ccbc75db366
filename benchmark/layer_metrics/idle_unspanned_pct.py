"""Layer: device. The share of the traced window in which the fullest device
was idle and the statement in flight had its root span open and no other: the
program at work under no span, which no reader can name. A number to keep
small: `notes.idle_unspanned_between` says between which two spans of the root
the seconds lie, and that is where the next span goes (`_idle.py`). None
without a trace, a ring of the window's statements and a clock that joins
them."""

from benchmark.layer_metrics import _idle


def read(run):
    return _idle.share(run, "unspanned")
