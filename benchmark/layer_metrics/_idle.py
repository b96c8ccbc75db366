"""Shared by the readers of the device's idle seconds (`idle_host_wait_pct`,
`idle_unspanned_pct`): the program's own spans (runtime/tracing.py, `TRACER`'s
ring, on `perf_counter_ns`) laid over the idle gaps of the fullest device (the
profiler's trace, on the profiler's clock), so that every idle second has the
name of what the host was doing in it. Pure functions over plain lists, like
`trace.reduce`; only `of` touches a run.

The clock. The ring and the trace share no epoch. A first guess of (profiler
ns - `perf_counter_ns`) comes from the first `stmt:` span against the earliest
`Record.start`; under it each `trino:statement` event of the trace (the root
span's `TraceAnnotation`: the same span stamped on both clocks a few hundred
nanoseconds apart; `trace.load` keeps those of 20 ms and more) finds the ring's
`statement` root that began within 1 ms of it, and the offset is the median
over those pairs. Fewer than 8 pairs, or pairs whose interquartile distance is
over 100 us, and every reader of this file gives None.

The attribution. The gaps are those of `Reduced.idle_gaps`: the window less
the busy intervals of the fullest device. A gap is cut wherever the answer to
"which open span started last" changes, over the window's `statement` trees
and the `stats_feedback` trees; the bare root `statement` ranks below every
other span, the harness's `stmt:` span (the client's side of the statement)
below the root. A piece's class:

  wait       `sync:*`, `drain`, `mesh:shard`: the host blocked on the device
             (a device-to-host read, the answer's last program, a transfer)
  client     `client_turn`, or a `stmt:` span in flight with no root open
             (HTTP and the client library before `QueryManager.submit` and
             after the last page)
  unspanned  the bare root: the program was at work under no span
  none       nothing in flight
  work       every other name: the host at work, the device with nothing to do

so that wait + work + unspanned + client + none = the idle seconds of the run,
`device_idle_pct` x the window."""

import bisect
import heapq
import statistics
import sys
import time
import traceback

from benchmark.layer_metrics import _statements as st

STATS_FEEDBACK = "stats_feedback"
ROOT_EVENT = "trino:" + st.STATEMENT
WAIT_PREFIX = "sync:"
WAIT = ("drain", "mesh:shard")
CLIENT = "client_turn"
CLASSES = ("wait", "work", "unspanned", "client", "none")

MIN_PAIRS = 8
MAX_IQR_US = 100.0
PAIR_WITHIN_NS = 1e6

# what ranks an open span beside the time it began
_STMT, _BARE_ROOT, _SPAN = 0, 1, 2


def class_of(name, rank=_SPAN) -> str:
    if name is None:
        return "none"
    if rank == _STMT or name == CLIENT:
        return "client"
    if rank == _BARE_ROOT:
        return "unspanned"
    if name.startswith(WAIT_PREFIX) or name in WAIT:
        return "wait"
    return "work"


# --------------------------------------------------------------------- clock


def clock(stmt_starts, record_starts_ns, root_events, root_starts):
    """{`offset_ns` (profiler ns - perf_counter ns), `pairs`, `iqr_us`};
    `offset_ns` is None where the two clocks cannot be joined, and the whole
    is None where there is nothing to pair. `stmt_starts`: the `stmt:`
    spans' starts (profiler); `record_starts_ns`: the records' (perf_counter);
    `root_events`: the `trino:statement` events' starts (profiler);
    `root_starts`: the ring's `statement` roots' (perf_counter)."""
    if not stmt_starts or not record_starts_ns or not root_starts:
        return None
    guess = min(stmt_starts) - min(record_starts_ns)
    roots = sorted(root_starts)
    deltas = []
    for event in root_events:
        at = bisect.bisect_left(roots, event - guess)
        near = [roots[i] for i in (at - 1, at) if 0 <= i < len(roots)]
        root = min(near, key=lambda r: abs(event - guess - r))
        if abs(event - guess - root) <= PAIR_WITHIN_NS:
            deltas.append(event - root)
    found = {"pairs": len(deltas), "offset_ns": None, "iqr_us": None}
    if len(deltas) >= MIN_PAIRS:
        q1, _, q3 = statistics.quantiles(deltas, n=4)
        found["iqr_us"] = (q3 - q1) / 1e3
        if found["iqr_us"] <= MAX_IQR_US:
            found["offset_ns"] = statistics.median(deltas)
    return found


# --------------------------------------------------------------- attribution


def gaps_of(window, busy) -> list:
    """The idle (lo, hi) of a device inside the window: `Reduced.idle_gaps`'
    edges. `busy` is merged and clipped to the window."""
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    return [(lo, hi) for lo, hi in zip(edges[0::2], edges[1::2]) if hi > lo]


def lay(gaps, spans):
    """[(lo, hi, index into `spans` or None)]: each gap cut where the open
    span that ranks highest changes. `spans`: (start, end, rank, ...), any
    order; the higher rank wins, then the later start. `gaps`: sorted,
    disjoint."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    starts = [spans[i][0] for i in order]
    opened, heap, pieces = 0, [], []     # heap: (-rank, -start, index) of spans begun so far
    for lo, hi in gaps:
        at = lo
        while at < hi:
            while opened < len(order) and starts[opened] <= at:
                i = order[opened]
                heapq.heappush(heap, (-spans[i][2], -spans[i][0], i))
                opened += 1
            while heap and spans[heap[0][2]][1] <= at:
                heapq.heappop(heap)
            top = heap[0][2] if heap else None
            until = hi if top is None else min(hi, spans[top][1])
            if opened < len(order):
                until = min(until, starts[opened])
            if pieces and pieces[-1][2] == top and pieces[-1][1] == at:
                pieces[-1] = (pieces[-1][0], until, top)    # the same span went on: one piece
            else:
                pieces.append((at, until, top))
            at = until
    return pieces


def flatten(trees, offset_ns, stmt_spans) -> list:
    """The spans `lay` ranks, each (start, end, rank, name, tree or None, span
    or None): a tree's spans moved to the profiler's clock, the statement's
    root ranked below its descendants, and the harness's `stmt:` spans below
    every root."""
    spans = []
    for tree in trees:
        for s in tree:
            if s["endNs"] is None:
                continue
            bare = s["parentSpanId"] is None and s["name"] == st.STATEMENT
            spans.append((s["startNs"] + offset_ns, s["endNs"] + offset_ns,
                          _BARE_ROOT if bare else _SPAN, s["name"], tree, s))
    spans += [(start, end, _STMT, "stmt:" + template, None, None)
              for template, start, end, _ in stmt_spans]
    return spans


def chain(tree, span) -> list:
    """The names of `span`'s ancestors and its own, outermost first."""
    by_id = {s["spanId"]: s for s in tree}
    names = []
    while span is not None:
        names.append(span["name"])
        span = by_id.get(span["parentSpanId"])
    return names[::-1]


def between(tree, lo_ns, hi_ns) -> tuple:
    """For a piece under the bare root: (the root's child that ended last
    before it, the one that began first after it), `(start)` and `(end)` where
    there is none. On the tree's own clock."""
    kids = st.children(tree, tree[0])
    before = max((k for k in kids if k["endNs"] <= lo_ns), key=lambda k: k["endNs"], default=None)
    after = min((k for k in kids if k["startNs"] >= hi_ns), key=lambda k: k["startNs"], default=None)
    return (before["name"] if before else "(start)", after["name"] if after else "(end)")


NOTHING = (None, None, _SPAN, None, None, None)     # what a piece under no span is laid to


def summarise(pieces, spans, offset_ns, stmt_spans, window_lo=0.0, top=15, few=5) -> dict:
    """`idle_by_class`, `idle_by_span`, `idle_unspanned_between` and
    `idle_longest` of the pieces `lay` gave over `flatten`'s spans; seconds."""
    by_class = dict.fromkeys(CLASSES, 0.0)
    by_span, unspanned = {}, {}
    for lo, hi, i in pieces:
        _, _, rank, name, tree, _ = spans[i] if i is not None else NOTHING
        kind = class_of(name, rank)
        seconds = (hi - lo) / 1e9
        by_class[kind] += seconds
        label = "(nothing in flight)" if name is None else name
        entry = by_span.setdefault(label, [label, kind, 0.0, 0])
        entry[2] += seconds
        entry[3] += 1
        if kind == "unspanned":
            # a slack of 1 us: a piece ends where the next span's annotation
            # begins, a few hundred nanoseconds before the span's own stamp
            pair = between(tree, lo - offset_ns + 1e3, hi - offset_ns - 1e3)
            entry = unspanned.setdefault(pair, [pair[0], pair[1], 0.0, 0])
            entry[2] += seconds
            entry[3] += 1

    def in_flight(at):
        inside = [(s, template) for template, s, e, _ in stmt_spans if s <= at < e]
        return min(inside)[1] if inside else None

    longest = []
    for lo, hi, i in heapq.nlargest(few, pieces, key=lambda p: p[1] - p[0]):
        _, _, _, name, tree, span = spans[i] if i is not None else NOTHING
        longest.append({
            "seconds": (hi - lo) / 1e9,
            "at_s": (lo - window_lo) / 1e9,       # since the window began
            "template": in_flight((lo + hi) / 2),
            "query_id": tree[0]["attributes"].get("query_id") if tree else None,
            "spans": chain(tree, span) if tree else ([name] if name else []),
        })

    def most(table, n):
        return sorted(table.values(), key=lambda e: -e[2])[:n]

    return {"idle_by_class": by_class, "idle_by_span": most(by_span, top),
            "idle_unspanned_between": most(unspanned, few), "idle_longest": longest}


# ----------------------------------------------------------------------- run


def feedback_trees() -> list:
    """The ring's `stats_feedback` trees (a served statement's statistics
    feedback runs under a root of its own, after the statement's has closed);
    none where the program keeps none."""
    try:
        from trino_tpu.runtime.tracing import TRACER

        return [[span.to_dict() for span in tree] for tree in TRACER.finished(STATS_FEEDBACK)]
    except (ImportError, AttributeError):  # a program without such a ring has nothing to lay
        return []


def of(run):
    """{`window_s`, `idle_by_class`, ...} for the run, once; None where there
    is no trace, no ring or no clock to join them. Puts the notes beside the
    two metrics under the result line's `notes`. A reader never fails a run:
    what goes wrong in here is printed and reads as None."""
    if not hasattr(run, "_idle"):
        run._idle = None
        try:
            run._idle = _lay_run(run)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
    return run._idle


def _lay_run(run):
    began = time.perf_counter()
    trees = st.window_trees(run) if run.trace is not None else None
    if not trees:
        return None
    reduced = run.trace
    found = clock(
        [s for _, s, _, _ in reduced.spans],
        [r.start * 1e9 for r in run.records],
        [s for name, s, _ in reduced.host_events if name == ROOT_EVENT],
        [t[0]["startNs"] for t in trees],
    )
    run.notes["idle_clock"] = found
    if found is None or found["offset_ns"] is None:
        return None
    offset = found["offset_ns"]
    lo, hi = reduced.window
    beside = [t for t in feedback_trees()
              if t and t[0]["endNs"] + offset > lo and t[0]["startNs"] + offset < hi]
    spans = flatten(trees + beside, offset, reduced.spans)
    pieces = lay(gaps_of(reduced.window, reduced.fullest.busy), spans)
    summary = summarise(pieces, spans, offset, reduced.spans, lo)
    run.notes.update(summary)
    run.notes["idle_reader"] = {"spans": len(spans), "pieces": len(pieces),
                                "seconds": time.perf_counter() - began}
    print(f"idle timeline: {run.notes['idle_reader']}", file=sys.stderr)
    return {"window_s": reduced.window_s, **summary}


def share(run, kind):
    """100 x the idle seconds of class `kind` over the traced window."""
    laid = of(run)
    if laid is None or laid["window_s"] <= 0:
        return None
    return 100.0 * laid["idle_by_class"][kind] / laid["window_s"]
