"""Layer: exchange. The seconds in which a collective ran (`all-to-all`,
`all-reduce`, `all-gather`, `reduce-scatter`, `collective-permute`; a `-start`
with its `-done` counts from the one to the other, once) as a share of the
same device's busy seconds in the traced window. Read on the device that
shows the most collective seconds: every device of a mesh program runs the
same collectives, but the profiler names the program's operations otherwise on
some (`region.<n>` on device 0 of the v5e host, PERF.md section 7), and a
device that shows none is not one that ran none. None where no device shows a
collective: one chip, or a tier without them."""


def of(devices):
    seen = max(devices, key=lambda d: d.collective_s)
    if seen.busy_s <= 0 or seen.collective_s <= 0:
        return None
    return 100.0 * seen.collective_s / seen.busy_s


def read(run):
    return None if run.trace is None else of(run.trace.devices)
