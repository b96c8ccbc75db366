"""Layer: executor. Device program executions on the fullest device in the
traced window (events of the trace's `XLA Modules` line) per statement of the
window."""


def read(run):
    if run.trace is None or not run.records:
        return None
    return run.trace.fullest.launches / len(run.records)
