"""From the profiler's trace (`.xplane.pb`) to the numbers the per-layer
readers use. `load` is the only part that touches JAX; `reduce` works on plain
lists, so the tests drive it from a small recorded trace kept as JSON.

Layout of a trace taken on a TPU (looked at by hand, PERF.md): one plane per
chip, `/device:TPU:<n>`, whose line `XLA Modules` holds one event per program
execution and whose line `XLA Ops` holds one event per operation inside them
(`Async XLA Ops` holds what is in flight beside them, from a `-start` to its
`-done`: copies, slices, and a collective that the compiler made asynchronous;
of that line only the collectives are kept, and they count as no busy time);
host threads are lines of the plane `/host:CPU`, and the spans this benchmark
writes (`bench_window`, `stmt:<template>`) are events there, on the same clock,
beside the runtime's own (`np.asarray(jax.Array)` where the host waits for the
device, `PjitFunction(...)`, `ReadSyncFlag`, `tpu::System::Execute=>Done`, ...).
"""

from __future__ import annotations

import bisect
import math
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^%?(all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute|async-collective)"
    r"(-start|-done)?[.\d]*$"
)
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench_window"
STATEMENT_SPAN = "stmt:"
LONG_HOST_EVENT_NS = 20e6   # shorter host events are dropped when a trace is loaded


def load(path: str) -> list:
    """[(plane, line, event name, start ns, duration ns)] of the planes and
    lines `reduce` reads; everything else in the file is dropped here."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (MODULES_LINE, OPS_LINE, ASYNC_LINE):
                continue
            for e in line.events:
                if line.name == ASYNC_LINE and not COLLECTIVE.match(short(e.name)):
                    continue
                if (device or e.name == WINDOW_SPAN or e.name.startswith(STATEMENT_SPAN)
                        or e.duration_ns >= LONG_HOST_EVENT_NS):
                    events.append(
                        (plane.name, line.name, short(e.name), float(e.start_ns), float(e.duration_ns))
                    )
    return events


def short(name: str) -> str:
    """An operation is named by its whole HLO text, `%sort.82 = (s8[...` on;
    a program by `jit__jit_compact(<fingerprint>)`. Keep what comes first."""
    return re.split(r" = |\(", name, maxsplit=1)[0][:80]


def union(intervals: list) -> list:
    """Sorted, merged copies of (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def covered(merged: list, lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(merged, lo, hi))


@dataclass
class Device:
    name: str
    busy: list = field(default_factory=list)     # merged (start, end) of operations, in the window
    launches: int = 0                            # program executions begun in the window
    op_seconds: dict = field(default_factory=dict)   # "<program> <operation>" -> seconds in the window
    collective: list = field(default_factory=list)   # merged (start, end) of collectives, in the window

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy) / 1e9

    @property
    def collective_s(self) -> float:
        return sum(e - s for s, e in self.collective) / 1e9


@dataclass
class Reduced:
    window: tuple                                # (start, end) ns, the bench_window span
    devices: list
    spans: list                                  # (template, start, end, whole) of statements;
                                                 # clipped to the window, `whole` if none was cut
    host_events: list = field(default_factory=list)   # (name, start, end) of the host's other events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def fullest(self) -> Device:
        return max(self.devices, key=lambda d: d.busy_s)

    def busy_and_window(self) -> dict:
        """For the result line's `device`: busy seconds averaged over the chips."""
        return {
            "busy_s": sum(d.busy_s for d in self.devices) / len(self.devices),
            "window_s": self.window_s,
        }

    def busy_inside(self, templates) -> tuple:
        """(device-busy seconds on the fullest device inside the spans of
        `templates` that lie wholly in the window, the number of those spans)."""
        mine = [(s, e) for name, s, e, whole in self.spans if whole and name in templates]
        busy = sum(covered(self.fullest.busy, s, e) for s, e in union(mine))
        return busy / 1e9, len(mine)

    def idle_gaps(self) -> dict:
        """Idle seconds of the fullest device by what the host was doing: the
        template of the statement in flight at the gap's middle (the one that
        began first), or that none was."""
        out: dict = {}
        edges = [self.window[0]] + [t for iv in self.fullest.busy for t in iv] + [self.window[1]]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi <= lo:
                continue
            mid = (lo + hi) / 2
            inside = [(s, name) for name, s, e, _ in self.spans if s <= mid < e]
            label = f"in {min(inside)[1]}" if inside else "no statement in flight"
            out[label] = out.get(label, 0.0) + (hi - lo) / 1e9
        return out

    def longest_gap(self) -> tuple:
        """(seconds, what the host was doing) for the one longest idle gap of
        the fullest device: the statement in flight, and three host events
        with the seconds of the gap each covers. First those that cover half
        of the gap or more, the innermost (latest start) first: nested spans
        all cover it, and the innermost says most. Then the others, by the
        seconds they cover."""
        edges = [self.window[0]] + [t for iv in self.fullest.busy for t in iv] + [self.window[1]]
        lo, hi = max(zip(edges[0::2], edges[1::2]), key=lambda g: g[1] - g[0])
        inside = [(s, name) for name, s, e, _ in self.spans if s <= (lo + hi) / 2 < e]
        covers: dict = {}    # name -> (seconds of the gap covered, start) of its widest event
        for name, s, e in self.host_events:
            if min(e, hi) > max(s, lo):
                covers[name] = max(covers.get(name, (0.0, 0.0)), ((min(e, hi) - max(s, lo)) / 1e9, s))
        half = (hi - lo) / 2e9

        def rank(item):
            seconds, start = item[1]
            return (0, -start) if seconds >= half else (1, -seconds)

        ranked = sorted(covers.items(), key=rank)
        doing = ", ".join(f"{name[:40]} {seconds:.3f}" for name, (seconds, _) in ranked[:3])
        where = f"in {min(inside)[1]}" if inside else "no statement in flight"
        return (hi - lo) / 1e9, f"longest gap, {where}; host: {doing or 'no long event'}"

    def breakdown(self) -> dict:
        def top(table, n):
            return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]

        seconds, doing = self.longest_gap()
        return {
            "device_ops": top(self.fullest.op_seconds, 10),
            "idle_gaps": top(self.idle_gaps(), 9) + [[doing, seconds]],
        }


def reduce(events: list) -> Reduced:
    windows = [(s, s + d) for _, _, name, s, d in events if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} {WINDOW_SPAN!r} spans, not one")
    lo, hi = windows[0]
    spans = [
        (name[len(STATEMENT_SPAN):], max(s, lo), min(s + d, hi), lo <= s and s + d <= hi)
        for plane, _, name, s, d in events
        if name.startswith(STATEMENT_SPAN) and s < hi and s + d > lo
    ]
    devices, programs = {}, {}
    for plane, line, name, s, d in events:
        if DEVICE_PLANE.match(plane) and line == MODULES_LINE:
            devices.setdefault(plane, Device(plane)).launches += lo <= s < hi
            programs.setdefault(plane, []).append((s, s + d, name))
    for runs in programs.values():
        runs.sort()
    for plane, line, name, s, d in events:
        if not (DEVICE_PLANE.match(plane) and line in (OPS_LINE, ASYNC_LINE)):
            continue
        dev = devices.setdefault(plane, Device(plane))
        if COLLECTIVE.match(name):
            dev.collective += clip([(s, s + d)], lo, hi)
        if line == ASYNC_LINE:
            continue
        runs = programs.get(plane, [])
        at = bisect.bisect_right(runs, (s, math.inf, "")) - 1  # the program the operation began in
        if at >= 0 and s < runs[at][1]:
            name = f"{runs[at][2]} {name}"
        for a, b in clip([(s, s + d)], lo, hi):
            dev.busy.append((a, b))
            dev.op_seconds[name] = dev.op_seconds.get(name, 0.0) + (b - a) / 1e9
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane: nothing ran on the device")
    for dev in devices.values():
        dev.busy, dev.collective = union(dev.busy), union(dev.collective)
    host_events = [
        (name, s, s + d) for plane, _, name, s, d in events
        if plane == HOST_PLANE and name != WINDOW_SPAN and not name.startswith(STATEMENT_SPAN)
    ]
    return Reduced((lo, hi), sorted(devices.values(), key=lambda d: d.name), spans, host_events)
