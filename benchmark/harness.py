"""One run of one cell: set-up, the measured window, the comparison with the
reference, the result line. Everything that belongs to one cell, configuration,
traffic mix, template or per-layer metric is found by its name in
BENCHMARK.json and read from a file of its own (README.md); nothing here
names one.

One process owns the chips: the coordinator (`CoordinatorServer`), the
closed-loop clients (`StatementClient`, one per thread) and, once the window
has closed, the reference on the host.
"""

from __future__ import annotations

import contextlib
import glob
import importlib
import json
import math
import os
import shutil
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

from benchmark import reference as ref
from benchmark.traffic import Statement, Traffic, load_mix

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parent


# ------------------------------------------------------------------ manifest


def manifest() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def find_cell(name: str) -> tuple:
    """(cell, configuration file's content) for the cell `name`."""
    bench = manifest()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (has {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, json.loads((REPO / entry["file"]).read_text())


def metrics_of(cell_name: str, group: str) -> list:
    """The `group` ("end_to_end" or "per_layer") metrics this cell reports. A
    per-layer metric without a `workloads` key goes wherever the end-to-end
    metric it moves is reported."""
    bench = manifest()
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def listed(m):
        return "workloads" not in m or cell_name in m["workloads"]

    if group == "end_to_end":
        return [m for m in bench["end_to_end"] if listed(m)]
    return [
        m for m in bench["per_layer"]
        if (cell_name in m["workloads"] if "workloads" in m else listed(e2e[m["moves"]]))
    ]


# -------------------------------------------------------------------- device


def require_chips(chips: int) -> None:
    """Exit 2 unless JAX runs on a TPU with at least `chips` devices."""
    import jax

    found = jax.devices() if jax.default_backend() == "tpu" else []
    if len(found) < chips:
        print(
            f"benchmark: the cell needs {chips} TPU chip(s); JAX's backend is "
            f"{jax.default_backend()!r} with {len(found)}",
            file=sys.stderr,
        )
        raise SystemExit(2)


def device_facts() -> dict:
    import jax

    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max((p for p in peaks if p is not None), default=None),
        "memory_limit_bytes": stats[0].get("bytes_limit"),
    }


def peaks_for(kind: str) -> tuple:
    """(the peaks of device kind `kind`, the stored width of each SQL type)."""
    table = json.loads((ROOT / "peaks.json").read_text())
    if kind not in table["device_kinds"]:
        raise LookupError(f"no peaks for device kind {kind!r} in benchmark/peaks.json")
    return table["device_kinds"][kind], table["type_bytes"]


class Compilations:
    """XLA compilations of this process, from the counters the engine's own
    jax.monitoring listener keeps. A request the persistent cache answered is
    not a compilation."""

    def __init__(self):
        from trino_tpu.runtime import observability as obs
        from trino_tpu.runtime.metrics import REGISTRY

        with obs.compile_window():  # registers the listener
            pass
        self._requests = REGISTRY.counter("trino_tpu_xla_compiles_total")
        self._hits = REGISTRY.counter("trino_tpu_xla_persistent_cache_hits_total")

    def read(self) -> tuple:
        return int(self._requests.value), int(self._hits.value)

    @staticmethod
    def between(a: tuple, b: tuple) -> dict:
        requests, hits = b[0] - a[0], b[1] - a[1]
        return {"compiled": requests - hits, "from_cache": hits}


# ------------------------------------------------------------------- serving


class Served:
    """The coordinator in this process, over the configuration's runner:
    `runners/<config["runner"]>.py` gives `start(config)`, the object the
    server is built over, and `load(served)`, which fills `table_rows` and
    `column_types`; it may give `check(served, records)`, the numbers of its own
    that `compared` holds beside their limits: {name: (value, limit, what)}."""

    def __init__(self, config: dict):
        from trino_tpu import native
        from trino_tpu.server import CoordinatorServer

        if not native.native_available():
            raise RuntimeError(f"native page codec unavailable: {native.load_error()}")
        self.config = config
        os.environ.update(config.get("environment", {}))  # the deployment's own settings
        self.module = importlib.import_module(f"benchmark.runners.{config['runner']}")
        self.runner = self.module.start(config)
        self.server = CoordinatorServer(self.runner).start()
        self.url = f"http://{self.server.address}"
        self.table_rows: dict = {}
        self.column_types: dict = {}

    def client(self):
        from trino_tpu.client import StatementClient

        # one timeout bounds each request and the whole statement
        return StatementClient(self.url, timeout=1000.0)

    def load(self) -> None:
        self.module.load(self)

    def stop(self) -> None:
        self.server.stop()


# -------------------------------------------------------------------- window


@dataclass
class Record:
    statement: Statement
    start: float
    end: float
    rows: list = None
    error: str = None
    server_ms: float = None

    @property
    def latency(self) -> float:
        return self.end - self.start


def send(served: Served, client, statement: Statement, annotate: bool) -> Record:
    import jax

    span = (
        jax.profiler.TraceAnnotation(f"stmt:{statement.template}")
        if annotate else contextlib.nullcontext()
    )
    start = time.perf_counter()
    record = Record(statement, start, start)
    try:
        with span:
            res = client.execute(statement.sql)
        record.rows = res.rows
        record.server_ms = res.stats.get("elapsedTimeMillis")
    except Exception as e:  # a failed statement is counted, never retried
        record.error = f"{type(e).__name__}: {e}"
    record.end = time.perf_counter()
    return record


def run_window(served: Served, traffic: Traffic, seconds: float, annotate: bool) -> tuple:
    """Closed loop: every client sends its next statement when the last has
    returned with all rows. No statement is sent after `seconds`; the window
    closes when the last one in flight has returned, so it holds whole
    statements only: all of their work and all of the time it took. Returns
    (records, the window's start, the window's seconds)."""
    import jax

    records: list = []
    lock = threading.Lock()
    clients = [served.client() for _ in range(traffic.clients)]
    go = threading.Event()
    deadline = [math.inf]

    def loop(index, client):
        go.wait()
        while time.perf_counter() < deadline[0]:
            record = send(served, client, traffic.next(index), annotate)
            with lock:
                records.append(record)

    threads = [
        threading.Thread(target=loop, args=(i, c), daemon=True) for i, c in enumerate(clients)
    ]
    for t in threads:
        t.start()
    with jax.profiler.TraceAnnotation("bench_window") if annotate else contextlib.nullcontext():
        start = time.perf_counter()
        deadline[0] = start + seconds
        go.set()
        for t in threads:
            t.join(timeout=seconds + 120.0)
        if any(t.is_alive() for t in threads):
            raise RuntimeError("a client did not return within 120 s of the last send")
    return records, start, max(r.end for r in records) - start


_TIMER = """
import json, select, sys, time
longest, at, last = 0.0, None, time.time()
while not select.select([sys.stdin], [], [], 0.010)[0]:
    now = time.time()
    if now - last - 0.010 > longest:
        longest, at = now - last - 0.010, last
    last = now
print(json.dumps([longest, at]))
"""


class HostWatch:
    """What the host did to the window, for a statement that stalls. A thread
    of this process asks to wake every 10 ms and notes its longest oversleep
    and when: that long the process, or the interpreter's lock, was kept from
    it. A child process that touches nothing but its own clock keeps the same
    timer: a gap that both see is the machine's, one that only the thread sees
    is a call of this process holding the interpreter's lock. Costs 100
    wake-ups a second on each side."""

    def __init__(self):
        self._child = subprocess.Popen(
            [sys.executable, "-S", "-c", _TIMER], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True,
        )
        self._stop = threading.Event()
        self.longest, self.at = 0.0, None
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def _watch(self) -> None:
        last = time.time()
        while not self._stop.wait(0.010):
            now = time.time()
            if now - last - 0.010 > self.longest:
                self.longest, self.at = now - last - 0.010, last
            last = now

    def stop(self) -> None:
        """Ends the thread and the child, and waits for both."""
        self._stop.set()
        self._thread.join()
        try:
            self.beside, self.beside_at = json.loads(self._child.communicate(timeout=10)[0])
        except Exception:  # the child's reading is a help, never a reason to fail a run
            self._child.kill()
            self._child.wait()
            self.beside, self.beside_at = None, None

    def report(self, window_start_wall: float) -> dict:
        def since_start(t):
            return None if t is None else t - window_start_wall

        return {
            "longest_gap_s": self.longest, "longest_gap_at_s": since_start(self.at),
            "child_longest_gap_s": self.beside, "child_longest_gap_at_s": since_start(self.beside_at),
        }


# ------------------------------------------------------------------- metrics


def _geomean_s(run) -> float:
    by_template = run.latencies_by_template()
    if not all(by_template.values()):
        return None
    means = [sum(v) / len(v) for v in by_template.values()]
    return math.exp(sum(math.log(m) for m in means) / len(means))


END_TO_END = {
    "setup_s": lambda run: run.setup_s,
    "query_geomean_s": _geomean_s,
    "queries_per_s": lambda run: len(run.completed) / run.window_s,
    "peak_hbm_bytes": lambda run: run.device["memory_peak_bytes"],
}


@dataclass
class Run:
    """What a per-layer reader (layer_metrics/<quantity>.py: read(run)) sees."""

    cell: dict
    config: dict
    traffic: Traffic
    device: dict
    setup_s: float
    window_s: float
    records: list            # every statement of the window
    completed: list          # of those, the ones answered and equal to the reference
    compiles: dict           # {"compiled": n, "from_cache": m} inside the window
    table_rows: dict
    column_types: dict
    peaks: dict = None       # of this device kind, from peaks.json
    type_bytes: dict = None
    trace: object = None     # benchmark.trace.Reduced, in a traced run
    notes: dict = field(default_factory=dict)   # what a reader prints beside its metric, under `notes`

    def latencies_by_template(self) -> dict:
        """{template: latencies of its completed statements}, every template of the mix."""
        out = {name: [] for name in self.traffic.templates}
        for r in self.completed:
            out[r.statement.template].append(r.latency)
        return out


def report(run: Run, group: str) -> dict:
    """The cell's metrics of `group`; a reader that finds nothing to read returns
    None and its metric is left out."""
    out = {}
    for m in metrics_of(run.cell["name"], group):
        if group == "end_to_end":
            read = END_TO_END[m["name"]]
        else:  # layer_metrics/<quantity>.py serves <quantity> and <quantity>.<suffix>
            quantity = m["name"].split(".")[0]
            read = importlib.import_module(f"benchmark.layer_metrics.{quantity}").read
        value = read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ----------------------------------------------------------------- reference


def host_for(traffic: Traffic, config: dict) -> dict:
    """The population's columns that the traffic's templates read."""
    wanted: dict = {}
    for module in traffic.templates.values():
        for table, columns in module.COLUMNS.items():
            wanted.setdefault(table, [])
            wanted[table] += [c for c in columns if c not in wanted[table]]
    return ref.host_columns(config["scale_factor"], wanted)


def judge(served: list, traffic: Traffic, config: dict, control: bool = False,
          host: dict = None) -> tuple:
    """Compare every (label, Record) in `served` with the reference. As the
    `control`, the reference evaluated in float32 stands in the program's
    place. Returns (Comparison, the positions in `served` that are right)."""
    host = host or host_for(traffic, config)
    comparison = ref.Comparison()
    right = set()
    expected: dict = {}
    for i, (label, record) in enumerate(served):
        statement, rows, error = record.statement, record.rows, record.error
        module = traffic.templates[statement.template]
        if statement.index not in expected:
            want = module.expect(host, statement.params, ref.EXACT)
            expected[statement.index] = want, ref.as_client(want)
        if control:
            rows = ref.as_client(module.expect(host, statement.params, ref.FLOAT32))
            error = None
        label = f"{label} {statement.label}"
        if error is not None or rows is None:
            comparison.unanswered(f"{label}: {error}")
            continue
        if comparison.rows(label, rows, *expected[statement.index]):
            right.add(i)
    return comparison, right


# ----------------------------------------------------------------------- run


def run(workload: str, seed: int, seconds: float, trace: bool, t0: float,
        need_chips: bool = True, config_overrides: dict = None, out=sys.stdout) -> int:
    """One run; prints the result line last on `out`. `need_chips=False`,
    `config_overrides` and `out` are for the tests (SF0.01 on the CPU); the
    command line has none of them."""
    cell, config = find_cell(workload)
    config = {**config, **(config_overrides or {})}
    if need_chips:
        require_chips(cell["chips"])
    import jax

    import trino_tpu  # noqa: F401  (x64, and the one compile cache directory)

    mix = load_mix(cell["traffic"])
    traffic = Traffic(mix, seed, config["schema"])
    compilations = Compilations()
    served = Served(config)
    trace_dir = REPO / "benchmark_out" / "trace" / f"{workload}-{seed}"
    try:
        served.load()
        warm = []
        client = served.client()
        before_warm = compilations.read()
        for statement in traffic.statements:  # every shape the window will use
            warm.append(send(served, client, statement, annotate=False))
        warm_compiles = Compilations.between(before_warm, compilations.read())
        setup_s = time.perf_counter() - t0

        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        before = compilations.read()
        watch = HostWatch()
        wall_minus_perf = time.time() - time.perf_counter()
        try:
            records, window_start, window_s = run_window(served, traffic, seconds, annotate=trace)
        finally:
            watch.stop()
            if trace:
                jax.profiler.stop_trace()
        host_watch = watch.report(window_start + wall_minus_perf)
        compiles = Compilations.between(before, compilations.read())
        device = device_facts()
    finally:
        served.stop()

    # the window has closed and the peak is read: now the reference, on the host
    comparison, right = judge(
        [("warm-up", r) for r in warm] + [("window", r) for r in records], traffic, config
    )
    check = getattr(served.module, "check", None)  # what the runner itself guarantees
    for name, (value, limit, what) in (check(served, warm + records) if check else {}).items():
        comparison.hold(name, value, limit, what)
    completed = [r for i, r in enumerate(records, start=len(warm)) if i in right]
    result = Run(
        cell=cell, config=config, traffic=traffic, device=device, setup_s=setup_s,
        window_s=window_s, records=records, completed=completed, compiles=compiles,
        table_rows=served.table_rows, column_types=served.column_types,
    )
    line = {
        "correct": comparison.correct,
        "attempted": len(records),
        "failed": sum(1 for r in records if r.error is not None),
        "metrics": None,
        "device": device,
        "table_rows": served.table_rows,
        "warm_up": {"statements": len(warm), **warm_compiles},
        "window_s": window_s,
        "window_compiles": compiles,
        "slowest": max(
            ({"template": r.statement.template, "latency_s": r.latency, "server_ms": r.server_ms,
              "sent_at_s": r.start - window_start} for r in records),
            key=lambda d: d["latency_s"]),
        "host": host_watch,
        "by_template": {
            name: {"n": len(v), "mean_s": sum(v) / len(v), "max_s": max(v)}
            for name, v in result.latencies_by_template().items() if v
        },
    }
    if trace:
        from benchmark import trace as tracing

        files = sorted(glob.glob(str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb")))
        result.trace = tracing.reduce(tracing.load(files[-1]))
        shutil.rmtree(trace_dir, ignore_errors=True)  # a trace is tens of MB; keep none
        result.peaks, result.type_bytes = peaks_for(device["kind"])
        line["metrics"] = report(result, "per_layer")
        line["device"] = {**device, **result.trace.busy_and_window()}
        line["breakdown"] = result.trace.breakdown()
    else:
        line["metrics"] = report(result, "end_to_end")
    if result.notes:
        line["notes"] = result.notes
    line["compared"] = comparison.report()  # each number beside its limit, last
    for name, entry in line["compared"].items():
        print(f"compared: {name} {json.dumps(entry)}", file=sys.stderr)
    print(json.dumps(line), file=out, flush=True)
    return 0
