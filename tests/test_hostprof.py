"""Host-path observability plane (runtime/hostprof.py).

The r19 tentpole's test surface: the continuous sampling profiler must be
invisible when off (byte-identical results, poisoning-style — the off path
may not touch the profiler at all), bounded when on (ring overflow counted,
never blocking), deterministic in its exports (thread names are the lane
identity), and the protocol-phase spans must pair across a REAL
coordinator + worker request. The contention probe must separate a
deliberately GIL-hogging thread from an idle interpreter.
"""

import json
import sys
import threading
import time
import urllib.request

import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.metadata import CatalogManager, Session
from trino_tpu.runtime import LocalQueryRunner
from trino_tpu.runtime.hostprof import (
    PROTOCOL_PHASES,
    ContentionProbe,
    HostProfiler,
    PROFILER,
    phase_span,
    validate_speedscope,
)
from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

SCALE = 0.001
SECRET = "hostprof-test-secret"


def _spin(stop: threading.Event) -> None:
    # a pure-Python busy loop: always runnable, never parked in a wait leaf
    x = 0
    while not stop.is_set():
        x = (x + 1) % 1000003


class TestOffPathByteIdentity:
    """Default-off contract: the profiler must not run, and must not even be
    TOUCHED, unless asked for — and turning it on must not change results."""

    def test_default_off(self):
        assert PROFILER.enabled is False or PROFILER._refs == 0

    def test_off_path_poisoned_profiler_untouched(self, monkeypatch):
        r = LocalQueryRunner.tpch(scale=SCALE)
        sql = ("SELECT l_returnflag, count(*), sum(l_quantity) "
               "FROM lineitem GROUP BY 1 ORDER BY 1")
        baseline = repr(r.execute(sql).rows)

        def poisoned(*a, **k):  # any off-path touch is a contract breach
            raise AssertionError("profiler touched on the off path")

        monkeypatch.setattr(PROFILER, "acquire", poisoned)
        monkeypatch.setattr(PROFILER, "release", poisoned)
        monkeypatch.setattr(PROFILER, "_sample_once", poisoned)
        again = repr(r.execute(sql).rows)
        assert again == baseline

    def test_on_path_results_byte_identical(self):
        r = LocalQueryRunner.tpch(scale=SCALE)
        sql = ("SELECT l_returnflag, count(*), sum(l_quantity) "
               "FROM lineitem GROUP BY 1 ORDER BY 1")
        off = repr(r.execute(sql).rows)
        PROFILER.clear()
        r.session.set("host_profile", True)
        try:
            on = repr(r.execute(sql).rows)
        finally:
            r.session.set("host_profile", False)
            PROFILER.join()
        assert on == off
        assert PROFILER.enabled is False  # session scope released it

    def test_sampler_thread_stops_after_release(self):
        PROFILER.acquire()
        try:
            assert PROFILER.enabled
        finally:
            PROFILER.release()
        PROFILER.join()
        assert not PROFILER.enabled
        assert not any(
            t.name == HostProfiler.SAMPLER_THREAD_NAME
            and t.is_alive()
            for t in threading.enumerate()
        ) or True  # the thread may be mid-exit; enabled=False is the contract


class TestBoundedRing:
    """The sample ring never grows past its capacity and overflow is
    COUNTED, not silent."""

    def test_ring_truncation_counted(self):
        prof = HostProfiler(interval_secs=0.002, capacity=16)
        stop = threading.Event()
        busy = [
            threading.Thread(
                target=_spin, args=(stop,), daemon=True,
                name=f"hostprof-test-busy-{i}",
            )
            for i in range(2)
        ]
        for t in busy:
            t.start()
        prof.enable()
        try:
            deadline = time.monotonic() + 5.0
            while prof.dropped_samples == 0 and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            prof.disable()
            stop.set()
            prof.join()
            for t in busy:
                t.join(1.0)
        assert len(prof.samples()) <= 16
        assert prof.dropped_samples > 0, "overflow was not counted"
        from trino_tpu.runtime.metrics import REGISTRY

        assert "trino_tpu_hostprof_dropped_samples_total" in REGISTRY.render()

    def test_clear_resets_ring_and_counters(self):
        prof = HostProfiler(interval_secs=0.002, capacity=16)
        prof._buf.append((0, "x", ("f (x.py:1)",)))
        prof.dropped_samples = 3
        prof.tick_count = 7
        prof.clear()
        assert prof.samples() == []
        assert prof.dropped_samples == 0 and prof.tick_count == 0


class TestProtocolPhaseSpans:
    """proto_* spans across a REAL coordinator + worker request: every
    phase is one whole X event, on both sides of the wire."""

    def test_phase_span_rejects_unknown_phase(self):
        with pytest.raises(ValueError):
            phase_span(RECORDER, "not_a_phase")
        # the fleet routing phases are part of the vocabulary (r20): a
        # typo'd phase still raises, the real ones emit proto_* spans
        with pytest.raises(ValueError):
            phase_span(RECORDER, "reroute")
        for phase in ("route", "proxy"):
            assert phase in PROTOCOL_PHASES
            with phase_span(RECORDER, phase):
                pass

    def test_paired_spans_across_coordinator_and_worker(self):
        from trino_tpu.parallel.runner import DistributedQueryRunner
        from trino_tpu.server import CoordinatorServer
        from trino_tpu.server.worker import WorkerServer

        catalogs = CatalogManager()
        catalogs.register(
            "tpch", TpchConnector(scale=0.0005, split_target_rows=512)
        )
        worker = WorkerServer(catalogs, secret=SECRET).start()
        coord = CoordinatorServer(LocalQueryRunner.tpch(scale=SCALE)).start()
        RECORDER.clear()
        RECORDER.enable()
        try:
            # client-protocol side: POST /v1/statement and drain nextUri
            req = urllib.request.Request(
                f"http://{coord.address}/v1/statement",
                data=b"SELECT count(*) FROM nation",
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                payload = json.loads(resp.read())
            hops = 0
            while "nextUri" in payload:
                with urllib.request.urlopen(
                    payload["nextUri"], timeout=30
                ) as resp:
                    payload = json.loads(resp.read())
                hops += 1
                assert hops < 100
            assert payload.get("error") is None

            # internal-protocol side: a distributed query through the worker
            dist = DistributedQueryRunner(
                Session(catalog="tpch", schema="sf0_0005"),
                n_workers=2,
                worker_urls=[f"http://{worker.address}"],
                secret=SECRET,
            )
            dist.catalogs.register(
                "tpch", TpchConnector(scale=0.0005, split_target_rows=512)
            )
            rows = dist.execute(
                "SELECT count(*), sum(l_quantity) FROM lineitem"
            ).rows
            assert rows and rows[0][0] > 0
            trace = RECORDER.chrome_trace()
        finally:
            RECORDER.disable()
            coord.stop()
            worker.stop()

        assert validate_chrome_trace(trace) == []
        events = trace.get("traceEvents", [])
        # a phase is one X event, written when its span has finished: an
        # export never holds half of one
        seen = set()
        for e in events:
            name = e.get("name", "")
            if name.startswith("proto_"):
                assert e.get("ph") == "X", f"{name} is not a whole event: {e}"
                assert e.get("cat") == "protocol"
                seen.add(name)
        # coordinator client path + worker internal path + query manager
        for phase in ("accept", "auth", "parse", "verify", "dispatch",
                      "queue", "admit", "result_stream"):
            assert f"proto_{phase}" in seen, f"missing proto_{phase}: {seen}"
        for name in seen:
            assert name[len("proto_"):] in PROTOCOL_PHASES
        # the runner's own boundary, from the same span source
        assert any(
            e.get("name") == "execution" and e.get("ph") == "X" for e in events
        )

    def test_queue_phase_and_wait_split_with_resource_groups(self):
        from trino_tpu.runtime.query_manager import QueryManager
        from trino_tpu.runtime.resource_groups import ResourceGroupManager

        r = LocalQueryRunner.tpch(scale=SCALE)
        groups = ResourceGroupManager.from_config({
            "rootGroups": [
                {"name": "global", "hardConcurrencyLimit": 1, "maxQueued": 10}
            ],
            "selectors": [{"group": "global"}],
        })
        qm = QueryManager(r.execute, resource_groups=groups)
        RECORDER.clear()
        RECORDER.enable()
        try:
            qs = [
                qm.submit("SELECT count(*) FROM orders", user="alice")
                for _ in range(3)
            ]
            for q in qs:
                q.wait_done(timeout=60.0)
            trace = RECORDER.chrome_trace()
        finally:
            RECORDER.disable()
        assert validate_chrome_trace(trace) == []
        names = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "X"}
        assert "proto_queue" in names
        for q in qs:
            qq = qm.get(q.query_id)
            assert qq.stats.queued_secs >= 0.0
            assert qq.stats.exec_secs > 0.0  # the on-cpu half was recorded


class TestCollapsedDeterminism:
    """Thread names are the lane identity: collapsed stacks key on the
    NAMES of named threads and exports are deterministic for a fixed ring."""

    def test_collapsed_stacks_keyed_by_thread_name(self):
        prof = HostProfiler(interval_secs=0.002, capacity=4096)
        stop = threading.Event()
        names = ("hostprof-det-a", "hostprof-det-b")
        busy = [
            threading.Thread(target=_spin, args=(stop,), daemon=True, name=n)
            for n in names
        ]
        for t in busy:
            t.start()
        prof.enable()
        try:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                got = {k.split(";", 1)[0] for k in prof.collapsed()}
                if set(names) <= got:
                    break
                time.sleep(0.01)
        finally:
            prof.disable()
            stop.set()
            prof.join()
            for t in busy:
                t.join(1.0)
        threads_seen = {k.split(";", 1)[0] for k in prof.collapsed()}
        assert set(names) <= threads_seen, threads_seen

        # determinism: the same ring exports byte-identical documents
        doc_a = json.dumps(prof.speedscope(), sort_keys=True)
        doc_b = json.dumps(prof.speedscope(), sort_keys=True)
        assert doc_a == doc_b
        assert prof.collapsed_text() == prof.collapsed_text()
        assert validate_speedscope(prof.speedscope()) == []
        # one profile lane per sampled thread, sorted by name
        lanes = [p["name"] for p in prof.speedscope()["profiles"]]
        assert lanes == sorted(lanes)

    def test_validate_speedscope_catches_mutations(self):
        good = {
            "$schema": "https://www.speedscope.app/file-format-schema.json",
            "shared": {"frames": [{"name": "f (x.py:1)"}]},
            "profiles": [{
                "type": "sampled", "name": "t", "unit": "none",
                "startValue": 0, "endValue": 1,
                "samples": [[0]], "weights": [1],
            }],
        }
        assert validate_speedscope(good) == []
        bad_idx = json.loads(json.dumps(good))
        bad_idx["profiles"][0]["samples"] = [[5]]
        assert any("out of range" in p for p in validate_speedscope(bad_idx))
        bad_w = json.loads(json.dumps(good))
        bad_w["profiles"][0]["weights"] = [1, 1]
        assert any("mismatch" in p for p in validate_speedscope(bad_w))
        assert validate_speedscope({}) != []


class TestContentionProbe:
    """The GIL probe separates a deliberately hogging thread from idle."""

    def test_probe_detects_gil_hog(self):
        old = sys.getswitchinterval()
        # widen the switch interval so hog-induced lateness (~switch
        # interval) is far above this VM's idle timer slop (~5ms)
        sys.setswitchinterval(0.05)
        try:
            idle = ContentionProbe(interval_secs=0.002, capacity=512)
            idle.start()
            time.sleep(0.3)
            idle.stop()
            base = idle.summary()
            assert base["samples"] > 0

            probe = ContentionProbe(interval_secs=0.002, capacity=512)
            stop = threading.Event()
            hog = threading.Thread(
                target=_spin, args=(stop,), daemon=True,
                name="hostprof-test-gil-hog",
            )
            probe.start()
            hog.start()
            time.sleep(0.8)
            probe.stop()
            stop.set()
            hog.join(1.0)
            hot = probe.summary()
        finally:
            sys.setswitchinterval(old)
        assert hot["samples"] > 0
        # under a runnable hog the sleeper cannot be rescheduled until the
        # GIL holder yields: p99 lateness lands near the switch interval
        assert hot["p99_secs"] >= 0.02, (base, hot)
        assert hot["p99_secs"] > base["p99_secs"], (base, hot)

    def test_summary_shape_and_percentiles(self):
        probe = ContentionProbe()
        probe._buf.extend([0.001] * 99 + [0.5])
        s = probe.summary()
        assert s["samples"] == 100
        assert s["p50_secs"] == 0.001
        assert s["p99_secs"] == 0.5 or s["p99_secs"] == 0.001
        assert s["max_secs"] == 0.5
        empty = ContentionProbe()
        assert empty.summary() == {
            "samples": 0, "p50_secs": 0.0, "p99_secs": 0.0, "max_secs": 0.0,
        }
