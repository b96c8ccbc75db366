"""Metrics, tracing spans, and the spool SPI.

Model: the reference's spi/metrics + JMX exposure, its OpenTelemetry span
instrumentation (TracingMetadata planning spans), and spi/spool
SpoolingManager + the spooled client protocol (protocol/spooling).
"""

import json
import urllib.request

import pytest


@pytest.fixture(scope="module")
def server():
    from trino_tpu.runtime import LocalQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer

    r = LocalQueryRunner.tpch(scale=0.001)
    srv = CoordinatorServer(r)
    srv.start()
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def client(server):
    from trino_tpu.client.client import StatementClient

    return StatementClient(f"http://{server.address}")


class TestMetrics:
    def test_prometheus_rendering(self):
        from trino_tpu.runtime.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.counter("test_total", help="a test counter").inc(3)
        reg.gauge("test_gauge", {"pool": "a"}).set(7)
        text = reg.render()
        assert "# TYPE test_total counter" in text
        assert "test_total 3" in text
        assert 'test_gauge{pool="a"} 7' in text

    def test_endpoint_counts_queries(self, server, client):
        client.execute("SELECT 1")
        text = (
            urllib.request.urlopen(f"http://{server.address}/v1/metrics")
            .read()
            .decode()
        )
        assert "trino_tpu_queries_submitted_total" in text
        assert "trino_tpu_queries_finished_total" in text


class TestTracing:
    def test_span_tree(self):
        from trino_tpu.runtime.tracing import Tracer

        tr = Tracer()
        with tr.span("root") as root:
            with tr.span("child"):
                pass
        spans = tr.trace(root.trace_id)
        assert [s["name"] for s in spans] == ["root", "child"]
        child = spans[1]
        assert child["parentSpanId"] == spans[0]["spanId"]
        assert child["durationMs"] is not None

    def test_error_recorded(self):
        from trino_tpu.runtime.tracing import Tracer

        tr = Tracer()
        with pytest.raises(ValueError):
            with tr.span("boom") as s:
                raise ValueError("nope")
        assert "ValueError" in s.attributes["error"]

    def test_query_trace_endpoint(self, server, client):
        res = client.execute("SELECT count(*) FROM nation")
        info = json.loads(
            urllib.request.urlopen(
                f"http://{server.address}/v1/query/{res.query_id}/trace"
            ).read()
        )
        names = [s["name"] for s in info["spans"]]
        # the trace id is the query id; the root opens at submit
        assert info["traceId"] == res.query_id
        assert names[0] == "statement"
        in_root = [
            s["name"] for s in info["spans"]
            if s["parentSpanId"] == info["spans"][0]["spanId"]
            and s["name"] not in ("result_stream", "client_turn")
        ]
        assert in_root == [
            "queue", "admit", "parse", "planner", "optimizer", "execution",
            "drain", "encode",
        ]


class TestSpool:
    def test_manager_roundtrip(self, tmp_path):
        from trino_tpu.runtime.spool import FileSystemSpoolingManager

        m = FileSystemSpoolingManager(str(tmp_path))
        h = m.create_segment(b"payload", rows=3)
        assert m.get_segment(h.segment_id) == b"payload"
        m.delete_segment(h.segment_id)
        assert m.get_segment(h.segment_id) is None

    def test_ttl_eviction(self, tmp_path):
        from trino_tpu.runtime.spool import FileSystemSpoolingManager

        m = FileSystemSpoolingManager(str(tmp_path), ttl_secs=0.0)
        h1 = m.create_segment(b"a", rows=1)
        m.create_segment(b"b", rows=1)  # triggers eviction of h1
        assert h1.segment_id not in m.list_segments()

    def test_spooled_protocol_matches_inline(self, client):
        inline = client.execute(
            "SELECT n_nationkey, n_name FROM nation ORDER BY n_nationkey"
        )
        spooled = client.execute(
            "SELECT n_nationkey, n_name FROM nation ORDER BY n_nationkey",
            data_encoding="json",
        )
        assert spooled.rows == inline.rows

    def test_spooled_lz4(self, client):
        from trino_tpu.native import native_available

        if not native_available():
            pytest.skip("native lz4 unavailable")
        spooled = client.execute(
            "SELECT n_nationkey FROM nation ORDER BY n_nationkey",
            data_encoding="json+lz4",
        )
        assert len(spooled.rows) == 25

    def test_segments_acked_and_freed(self, server, client):
        client.execute("SELECT n_name FROM nation", data_encoding="json")
        # the client acks (DELETEs) every segment it fetched
        assert server.spooling.list_segments() == []


class TestMetricsPrecision:
    def test_large_counter_full_precision(self):
        from trino_tpu.runtime.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.counter("big_total").inc(12_345_678)
        assert "big_total 12345678" in reg.render()


class TestPrometheusConformance:
    """Text exposition format conformance (the scrape contract)."""

    def test_help_and_type_lines_once_per_name(self):
        from trino_tpu.runtime.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.counter("multi_total", {"shard": "a"}, help="a multi counter").inc()
        reg.counter("multi_total", {"shard": "b"}).inc(2)
        text = reg.render()
        assert text.count("# HELP multi_total a multi counter") == 1
        assert text.count("# TYPE multi_total counter") == 1
        assert '# HELP' not in text.split("# TYPE multi_total counter")[1]

    def test_label_escaping(self):
        from trino_tpu.runtime.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.gauge("esc_gauge", {"q": 'a"b\\c\nd'}).set(1)
        text = reg.render()
        assert 'q="a\\"b\\\\c\\nd"' in text

    def test_counter_monotonic_across_scrapes(self):
        from trino_tpu.runtime.metrics import MetricsRegistry

        reg = MetricsRegistry()
        c = reg.counter("mono_total")
        values = []
        for _ in range(5):
            c.inc(3)
            line = [
                l for l in reg.render().splitlines()
                if l.startswith("mono_total ")
            ][0]
            values.append(float(line.split()[1]))
        assert values == sorted(values)
        with pytest.raises(ValueError):
            c.inc(-1)  # counters never go down

    def test_metrics_endpoint_content_type(self, server):
        resp = urllib.request.urlopen(f"http://{server.address}/v1/metrics")
        assert resp.headers["Content-Type"].startswith("text/plain")
        assert "version=0.0.4" in resp.headers["Content-Type"]

    def test_counter_and_gauge_thread_safety(self):
        import threading

        from trino_tpu.runtime.metrics import Counter, Gauge, Histogram

        c, g, h = Counter(), Gauge(), Histogram(buckets=[0.5, 1.0])
        n, k = 8, 5000

        def work():
            for _ in range(k):
                c.inc()
                g.inc(2)
                g.dec()
                h.observe(0.25)

        threads = [threading.Thread(target=work) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n * k
        assert g.value == n * k
        assert h.count == n * k
        assert h.bucket_counts[0] == n * k


class TestHistogram:
    def test_exposition_cumulative_buckets(self):
        from trino_tpu.runtime.metrics import MetricsRegistry

        reg = MetricsRegistry()
        h = reg.histogram(
            "lat_secs", {"stage": "x"}, help="latency", buckets=[0.1, 1.0, 10.0]
        )
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        text = reg.render()
        assert "# TYPE lat_secs histogram" in text
        assert 'lat_secs_bucket{stage="x",le="0.1"} 1' in text
        assert 'lat_secs_bucket{stage="x",le="1"} 3' in text
        assert 'lat_secs_bucket{stage="x",le="10"} 4' in text
        assert 'lat_secs_bucket{stage="x",le="+Inf"} 5' in text
        assert 'lat_secs_count{stage="x"} 5' in text
        assert 'lat_secs_sum{stage="x"} 56.05' in text

    def test_exponential_buckets(self):
        from trino_tpu.runtime.metrics import exponential_buckets

        assert exponential_buckets(0.001, 2.0, 4) == (0.001, 0.002, 0.004, 0.008)

    def test_boundary_lands_in_bucket(self):
        from trino_tpu.runtime.metrics import Histogram

        h = Histogram(buckets=[1.0, 2.0])
        h.observe(1.0)  # le="1" is inclusive
        assert h.bucket_counts[0] == 1

    def test_quantile_interpolation(self):
        import math

        from trino_tpu.runtime.metrics import histogram_quantile

        # 10 observations uniform in (0, 1], 10 in (1, 2]
        buckets = [(1.0, 10), (2.0, 20), (math.inf, 20)]
        assert histogram_quantile(buckets, 20, 0.5) == 1.0
        assert histogram_quantile(buckets, 20, 0.25) == 0.5
        assert abs(histogram_quantile(buckets, 20, 0.95) - 1.9) < 1e-9
        # empty series -> None; rank past the last finite bound clamps to it
        assert histogram_quantile(buckets, 0, 0.5) is None
        assert histogram_quantile([(1.0, 0), (math.inf, 5)], 5, 0.5) == 1.0


class TestTraceContextPropagation:
    def test_pool_thread_spans_join_parent_trace(self):
        """Spans opened on a pooled thread re-parent into the submitting
        thread's trace via capture()/attach() (the OOC prefetcher / FTE
        task-thread fix) instead of starting an orphan trace."""
        from concurrent.futures import ThreadPoolExecutor

        from trino_tpu.runtime.tracing import Tracer

        tr = Tracer()
        pool = ThreadPoolExecutor(max_workers=1)
        try:
            with tr.span("query") as root:
                ctx = tr.capture()

                def job():
                    with tr.attach(ctx):
                        with tr.span("prefetch") as child:
                            return child

                child = pool.submit(job).result()
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            spans = tr.trace(root.trace_id)
            assert [s["name"] for s in spans] == ["query", "prefetch"]
        finally:
            pool.shutdown()

    def test_wrap_captures_at_wrap_time(self):
        from concurrent.futures import ThreadPoolExecutor

        from trino_tpu.runtime.tracing import Tracer

        tr = Tracer()
        pool = ThreadPoolExecutor(max_workers=1)
        try:
            with tr.span("query") as root:
                def job():
                    with tr.span("inner") as s:
                        return s

                wrapped = tr.wrap(job)
            # runs AFTER the parent closed — parentage still holds
            child = pool.submit(wrapped).result()
            assert child.trace_id == root.trace_id
        finally:
            pool.shutdown()

    def test_remote_ids_cross_wire_boundary(self):
        """capture_ids()/attach_remote(): trace parentage shipped in a task
        descriptor over HTTP (the FTE task-thread path — a same-process
        capture can't carry it)."""
        from trino_tpu.runtime.tracing import Tracer
        from trino_tpu.server.worker import (
            TaskDescriptor,
            decode_task,
            encode_task,
        )

        tr = Tracer()
        with tr.span("query") as root:
            ids = tr.capture_ids()
        assert ids == {"trace_id": root.trace_id, "span_id": root.span_id}
        desc = decode_task(encode_task(TaskDescriptor(trace=ids)))
        assert desc.trace == ids
        with tr.attach_remote(desc.trace):
            with tr.span("task") as s:
                pass
        assert s.trace_id == root.trace_id
        assert s.parent_id == root.span_id
        assert tr.capture_ids() is None  # phantom popped cleanly

    def test_attach_none_is_noop(self):
        from trino_tpu.runtime.tracing import Tracer

        tr = Tracer()
        with tr.attach(tr.capture()):  # nothing current -> no parent
            with tr.span("solo") as s:
                pass
        assert s.parent_id is None

    def test_ooc_prefetch_spans_join_query_trace(self):
        """End-to-end: the OOC bucket prefetcher's pool-side spans land in
        the enclosing query trace."""
        from trino_tpu.runtime import LocalQueryRunner
        from trino_tpu.runtime.ooc import OutOfCoreRunner
        from trino_tpu.runtime.tracing import TRACER

        r = LocalQueryRunner.tpch(scale=0.001)
        plan = r.plan_sql(
            "SELECT o_custkey, count(*) FROM orders, lineitem "
            "WHERE o_orderkey = l_orderkey GROUP BY o_custkey"
        )
        with TRACER.span("query") as root:
            ooc = OutOfCoreRunner(
                plan, r.metadata, r.session, n_buckets=4, split_batch=2
            )
            ooc.execute()
        names = [s["name"] for s in TRACER.trace(root.trace_id)]
        assert "ooc.prefetch" in names


class TestFlightRecorder:
    def test_disabled_records_nothing(self):
        from trino_tpu.runtime.observability import FlightRecorder

        rec = FlightRecorder()
        with rec.span("x", "test"):
            rec.instant("y", "test")
        assert rec.events() == []

    def test_bounded_ring(self):
        from trino_tpu.runtime.observability import FlightRecorder

        rec = FlightRecorder(capacity=16)
        rec.enable()
        for i in range(100):
            rec.instant(f"e{i}", "test")
        events = rec.events()
        assert len(events) == 16
        assert events[-1]["name"] == "e99"

    def test_dropped_events_counted(self):
        """Ring truncation is visible: dropped_events counts overflow and
        rides the chrome_trace export (never silent loss)."""
        from trino_tpu.runtime.observability import FlightRecorder

        rec = FlightRecorder(capacity=16)
        rec.enable()
        for i in range(100):
            rec.instant(f"e{i}", "test")
        assert rec.dropped_events == 84
        assert rec.chrome_trace()["droppedEvents"] == 84
        rec.clear()
        assert rec.dropped_events == 0
        rec.instant("after", "test")
        assert rec.chrome_trace()["droppedEvents"] == 0

    def test_ring_capacity_from_env(self, monkeypatch):
        from trino_tpu.runtime.observability import FlightRecorder

        monkeypatch.setenv("TRINO_TPU_FLIGHT_RING", "32")
        rec = FlightRecorder()
        assert rec._buf.maxlen == 32
        monkeypatch.setenv("TRINO_TPU_FLIGHT_RING", "not-a-number")
        assert FlightRecorder()._buf.maxlen == 65536
        monkeypatch.delenv("TRINO_TPU_FLIGHT_RING")
        assert FlightRecorder()._buf.maxlen == 65536

    def test_chrome_trace_validates(self):
        from trino_tpu.runtime.observability import (
            FlightRecorder,
            validate_chrome_trace,
        )

        rec = FlightRecorder()
        rec.enable()
        with rec.span("outer", "test", tag=1):
            with rec.span("inner", "test"):
                rec.instant("point", "test", bytes=7)
        rec.complete("compile", "test", 0.001)
        trace = rec.chrome_trace()
        assert validate_chrome_trace(trace) == []
        names = [e["name"] for e in trace["traceEvents"]]
        assert "process_name" in names and "thread_name" in names

    def test_validator_catches_unpaired_and_nonmonotonic(self):
        from trino_tpu.runtime.observability import validate_chrome_trace

        meta = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "p"}},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "t"}},
        ]
        unpaired = meta + [
            {"name": "a", "cat": "c", "ph": "B", "ts": 10, "pid": 1, "tid": 1}
        ]
        assert any("unclosed" in p for p in validate_chrome_trace(
            {"traceEvents": unpaired}
        ))
        backwards = meta + [
            {"name": "a", "cat": "c", "ph": "i", "ts": 10, "pid": 1, "tid": 1},
            {"name": "b", "cat": "c", "ph": "i", "ts": 5, "pid": 1, "tid": 1},
        ]
        assert any("monotonic" in p for p in validate_chrome_trace(
            {"traceEvents": backwards}
        ))
        unknown_tid = meta + [
            {"name": "a", "cat": "c", "ph": "i", "ts": 1, "pid": 1, "tid": 9}
        ]
        assert any("undeclared tid" in p for p in validate_chrome_trace(
            {"traceEvents": unknown_tid}
        ))

    def test_flightrecorder_endpoint(self, server, client):
        from trino_tpu.runtime.observability import RECORDER, validate_chrome_trace

        RECORDER.clear()
        RECORDER.enable()
        try:
            client.execute("SELECT count(*) FROM region")
        finally:
            RECORDER.disable()
        info = json.loads(
            urllib.request.urlopen(
                f"http://{server.address}/v1/flightrecorder"
            ).read()
        )
        assert validate_chrome_trace(info) == []
        cats = {e.get("cat") for e in info["traceEvents"]}
        assert "query" in cats


class TestQueryStatsPlane:
    def test_explain_analyze_verbose_reports_attribution(self):
        from trino_tpu.runtime import LocalQueryRunner

        r = LocalQueryRunner.tpch(scale=0.001)
        res = r.execute(
            "EXPLAIN ANALYZE VERBOSE "
            "SELECT n_name, count(*) FROM supplier, nation "
            "WHERE s_nationkey = n_nationkey GROUP BY n_name"
        )
        text = "\n".join(line for (line,) in res.rows)
        assert "Join" in text
        assert "device=" in text and "host=" in text and "compile=" in text
        # plain ANALYZE keeps the compact annotation
        res2 = r.execute(
            "EXPLAIN ANALYZE SELECT count(*) FROM nation"
        )
        text2 = "\n".join(line for (line,) in res2.rows)
        assert "time=" in text2 and "device=" not in text2

    def test_query_stats_collected_async(self):
        from trino_tpu.runtime import LocalQueryRunner

        r = LocalQueryRunner.tpch(scale=0.001)
        res = r.execute("SELECT count(*) FROM lineitem")
        qs = res.query_stats
        assert qs is not None and not qs["syncMode"]
        assert qs["times"]["dispatch_secs"] > 0

    def test_query_stats_sync_mode_per_operator(self):
        from trino_tpu.metadata import Session
        from trino_tpu.runtime import LocalQueryRunner

        r = LocalQueryRunner.tpch(scale=0.001)
        r.session.set("query_stats_sync", True)
        res = r.execute("SELECT l_returnflag, count(*) FROM lineitem GROUP BY l_returnflag")
        qs = res.query_stats
        assert qs["syncMode"]
        assert "AggregationNode" in qs["operators"]
        agg = qs["operators"]["AggregationNode"]
        assert agg["invocations"] >= 1 and agg["rows"] >= 1

    def test_v1_query_exposes_plane_fields(self, server, client):
        res = client.execute("SELECT count(*) FROM nation")
        info = json.loads(
            urllib.request.urlopen(
                f"http://{server.address}/v1/query/{res.query_id}"
            ).read()
        )
        qs = info["queryStats"]
        for field in (
            "deviceBusyTime", "hostWaitTime", "analysisTime",
            "spilledDataSize", "internalNetworkInputDataSize",
            "internalNetworkOutputDataSize", "compileCount",
        ):
            assert field in qs, field

    def test_spill_counters_reach_plane(self):
        from trino_tpu.runtime import LocalQueryRunner

        r = LocalQueryRunner.tpch(scale=0.001)
        r.session.set("spill_operator_threshold_bytes", 1024)
        res = r.execute(
            "SELECT o_custkey, count(*) FROM orders GROUP BY o_custkey"
        )
        qs = res.query_stats
        assert qs["counts"]["spill_write_bytes"] > 0
        assert qs["counts"]["spill_read_bytes"] > 0


class TestSmokeCheck:
    """The tier-1 observability smoke check (satellite: CI/tooling)."""

    def test_smoke_check_passes(self):
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_smoke() == []

    def test_exchange_smoke_passes(self):
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_exchange_smoke() == []

    def test_memory_smoke_passes(self):
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_memory_smoke() == []

    def test_kernelcost_smoke_passes(self):
        """The kernel cost plane smoke: roofline lines in EXPLAIN ANALYZE
        VERBOSE, hbm_watermark counter track + paired kernel_cost spans in
        a valid Perfetto export (counter-event conformance mutation-checked
        inside the smoke), schema-checked system.runtime.kernel_costs with
        a federated fold."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_kernelcost_smoke() == []

    def test_hostprof_smoke_passes(self):
        """The host-path observability plane smoke: session-scoped sampler
        with named-thread collapsed stacks, valid speedscope export, paired
        proto_* phase spans, schema-checked system.runtime.host_profile,
        host-thread gauges, and a numeric contention-probe summary."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_hostprof_smoke() == []

    def test_stats_smoke_passes(self):
        """The statistics-feedback-plane smoke: paired/monotonic
        cardinality_misestimate events + schema-checked operator_stats."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_stats_smoke() == []

    def test_cache_smoke_passes(self):
        """The warm-path cache-plane smoke: paired cache_lookup/cache_store/
        cache_invalidate spans with hit/miss outcomes, schema-checked
        system.runtime.caches, HELP-linted tier counters."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_cache_smoke() == []

    def test_batching_smoke_passes(self):
        """The device-batching-plane smoke: paired batch_admit/batch_launch/
        batch_demux spans with lane counts and packed rows on the E-args,
        bit-identical concurrent burst, shared-scan elimination, HELP-linted
        batching metrics."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_batching_smoke() == []

    def test_megakernel_smoke_passes(self):
        """The megakernel-plane smoke: paired pallas_compile/pallas_launch
        spans with shape class + fused-op list on the E-args, bit-identical
        fused vs serial run, strictly fewer device programs, HELP-linted
        launch/fallback counters."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_megakernel_smoke() == []

    def test_tensor_smoke_passes(self):
        """The tensor-plane smoke: paired vector_kernel/topk_fusion spans
        with rows/dim/k on the E-args, fused top-k bit-identical to the
        serial pair, strictly fewer device programs, HELP-linted
        launch/fallback counters."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_tensor_smoke() == []

    def test_vector_serving_smoke_passes(self):
        """The vector-serving-plane smoke: concurrent same-shape vector
        top-k statements coalesce into stacked launches (paired
        vector_batch_launch spans, strictly fewer device programs,
        bit-identical per query), an ANN probe leaves a paired ann_probe
        span plus an on-schema system.runtime.ann_recall row, and the
        three serving counters pass the HELP lint."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_vector_serving_smoke() == []

    def test_ha_smoke_passes(self):
        """The serving-fabric-plane smoke: paired leader_lease/
        dispatch_replay/worker_drain spans, lease takeover under chaos
        expiry, a crash->resume round trip bit-identical to the oracle,
        torn-tail journal recovery, HELP-linted failover/renewal/torn
        counters."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_ha_smoke() == []

    def test_objectstore_smoke_passes(self):
        """The object-store-substrate smoke: lease takeover, warm-tier
        publish, and a crash->resume round trip all on the rename-free
        object backend with throttle/torn-put/list-lag chaos armed —
        paired object_store_request spans with ok + recovered outcomes,
        HELP-linted trino_tpu_object_store_* counters."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_objectstore_smoke() == []

    def test_fleet_smoke_passes(self):
        """The coordinator-fleet-plane smoke: a three-node fleet converges,
        a non-owner 307s to the owner (client follows to a correct result),
        a mid-run owner kill lapses its heartbeat and reassigns ONLY the
        dead hash range, a follower serves the dead owner's query status
        during failover, paired proto_route/fleet_reassign spans, and
        HELP-linted fleet counters."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_fleet_smoke() == []


class TestSchemaFilterRules:
    def test_table_scoped_deny_does_not_hide_schema(self):
        from trino_tpu.spi.security import RuleBasedAccessControl

        ac = RuleBasedAccessControl.from_config(
            {
                "tables": [
                    {"schema": "sales", "table": "secret", "privileges": []},
                    {"schema": "sales", "privileges": ["SELECT"]},
                ]
            }
        )
        assert ac.filter_schemas("bob", "c", ["sales"]) == ["sales"]

    def test_whole_schema_deny_hides(self):
        from trino_tpu.spi.security import RuleBasedAccessControl

        ac = RuleBasedAccessControl.from_config(
            {
                "tables": [
                    {"user": "bob", "schema": "secret", "privileges": []},
                    {"privileges": ["SELECT"]},
                ]
            }
        )
        assert ac.filter_schemas("bob", "c", ["secret", "open"]) == ["open"]
        assert ac.filter_schemas("alice", "c", ["secret"]) == ["secret"]


class TestClusterSmoke:
    def test_cluster_smoke_passes(self):
        """The cluster-observability-plane smoke: two leased coordinators +
        two real workers, coordinator_crash chaos mid-query, standby resume
        -> ONE merged Perfetto trace (>=2 worker lanes, both leader epochs,
        skew-aligned monotonic), HELP-linted federated exposition, and a
        persisted profile whose stage breakdown sums to within 5% of wall
        time."""
        import importlib.util
        import os

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        spec = importlib.util.spec_from_file_location(
            "obs_smoke", os.path.join(tools, "obs_smoke.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_cluster_smoke() == []


class TestClockSync:
    """Clock-skew alignment edges (satellite): zero-RTT, negative offset,
    min-RTT sample selection, and a worker restart's fresh monotonic
    epoch."""

    def test_zero_rtt_exact_offset(self):
        from trino_tpu.runtime.clusterobs import ClockSync

        cs = ClockSync()
        assert cs.observe("w", 1_000, rtt_us=0, local_mono_us=5_000) == 4_000
        assert cs.offset_us("w") == 4_000

    def test_negative_offset_remote_clock_ahead(self):
        from trino_tpu.runtime.clusterobs import ClockSync

        cs = ClockSync()
        # the remote monotonic clock reads AHEAD of ours: offset negative
        assert cs.observe("w", 9_000, rtt_us=0, local_mono_us=1_000) == -8_000

    def test_min_rtt_sample_wins(self):
        from trino_tpu.runtime.clusterobs import ClockSync

        cs = ClockSync()
        cs.observe("w", 1_000, rtt_us=100, local_mono_us=5_000)
        tight = cs.offset_us("w")
        # a later, LOOSER (higher-RTT) sample must not displace the tight one
        cs.observe("w", 2_000, rtt_us=50_000, local_mono_us=9_000)
        assert cs.offset_us("w") == tight

    def test_worker_restart_resets_monotonic_epoch(self):
        from trino_tpu.runtime.clusterobs import ClockSync

        cs = ClockSync()
        cs.observe("w", 50_000_000, rtt_us=10, local_mono_us=60_000_000)
        # restart: the remote clock REGRESSES far past jitter slack — the
        # stale best sample must be discarded even at a worse RTT, or every
        # post-restart segment would be aligned with the dead clock
        off = cs.observe("w", 1_000, rtt_us=40_000, local_mono_us=61_000_000)
        assert off == 61_000_000 - (1_000 + 20_000)
        assert cs.offset_us("w") == off

    def test_unmeasured_first_rtt_never_locks_in(self):
        """A worker's FIRST announcement has no RTT yet (rtt_us=None on the
        wire). It must yield a provisional offset but rank below ANY later
        measured sample — a claimed rtt=0 would win the min-RTT rule
        forever, freezing an offset biased by the full one-way delay."""
        from trino_tpu.runtime.clusterobs import ClockSync

        cs = ClockSync()
        # provisional: no midpoint correction applied, offset = local-remote
        assert cs.observe_announcement(
            "w", {"mono_us": 1_000, "rtt_us": None}, local_mono_us=42_000
        ) == 41_000
        # the first MEASURED sample supersedes it despite its nonzero RTT
        off = cs.observe("w", 2_000, rtt_us=10_000, local_mono_us=48_000)
        assert off == 48_000 - (2_000 + 5_000)
        assert cs.offset_us("w") == off


class TestTraceAssembly:
    """Deterministic tids (satellite regression), query filtering, and
    skew-aligned merging."""

    @staticmethod
    def _ring_with_threads(order):
        """A FlightRecorder ring whose named threads START in ``order`` —
        the arrival-order tid assignment differs per order, the canonical
        export must not. Every thread is held alive until all have
        recorded: CPython reuses thread idents after join, which would
        collapse the lanes."""
        import threading

        from trino_tpu.runtime.observability import FlightRecorder

        rec = FlightRecorder()
        rec.enabled = True
        hold = threading.Event()
        threads = []
        for name in order:
            recorded = threading.Event()

            def work(name=name, recorded=recorded):
                with rec.span("op", "operator", who=name):
                    pass
                recorded.set()
                hold.wait()

            t = threading.Thread(target=work, name=name)
            t.start()
            recorded.wait()  # serialize span order across threads
            threads.append(t)
        hold.set()
        for t in threads:
            t.join()
        return rec

    def test_repeated_export_of_same_ring_byte_identical(self):
        import json

        from trino_tpu.runtime.clusterobs import canonicalize_trace, local_segment

        rec = self._ring_with_threads(["beta", "alpha"])
        t1 = canonicalize_trace(local_segment([], recorder=rec))
        t2 = canonicalize_trace(local_segment([], recorder=rec))
        assert json.dumps(t1, sort_keys=True) == json.dumps(t2, sort_keys=True)

    def test_tids_derive_from_thread_names_not_arrival(self):
        from trino_tpu.runtime.clusterobs import canonicalize_trace, local_segment

        for order in (["beta", "alpha"], ["alpha", "beta"]):
            rec = self._ring_with_threads(order)
            trace = canonicalize_trace(local_segment([], recorder=rec))
            names = {
                e["tid"]: e["args"]["name"]
                for e in trace["traceEvents"]
                if e.get("ph") == "M" and e.get("name") == "thread_name"
            }
            # sorted (thread-name) -> tid regardless of start order
            assert names == {1: "alpha", 2: "beta"}

    def test_filter_keeps_window_nested_events_and_pairing(self):
        from trino_tpu.runtime.clusterobs import filter_events_for_query

        events = [
            {"name": "task", "cat": "task", "ph": "B", "ts": 1, "pid": 1,
             "tid": 1, "args": {"task_id": "q1_f0_p0"}},
            {"name": "op", "cat": "operator", "ph": "B", "ts": 2, "pid": 1,
             "tid": 1},
            {"name": "spill_write", "cat": "spill", "ph": "i", "ts": 3,
             "pid": 1, "tid": 1},
            {"name": "op", "cat": "operator", "ph": "E", "ts": 4, "pid": 1,
             "tid": 1},
            {"name": "task", "cat": "task", "ph": "E", "ts": 5, "pid": 1,
             "tid": 1},
            # another query's task on another thread: excluded entirely
            {"name": "task", "cat": "task", "ph": "B", "ts": 2, "pid": 1,
             "tid": 2, "args": {"task_id": "q2_f0_p0"}},
            {"name": "task", "cat": "task", "ph": "E", "ts": 6, "pid": 1,
             "tid": 2},
            # stray instant outside any window, no query reference
            {"name": "noise", "cat": "x", "ph": "i", "ts": 7, "pid": 1,
             "tid": 1},
        ]
        kept = filter_events_for_query(events, ["q1"])
        assert [e["name"] for e in kept] == [
            "task", "op", "spill_write", "op", "task"
        ]
        b = sum(1 for e in kept if e["ph"] == "B")
        e_ = sum(1 for e in kept if e["ph"] == "E")
        assert b == e_ == 2

    def test_merge_aligns_negative_offset_and_stays_monotonic(self):
        from trino_tpu.runtime.clusterobs import assemble_cluster_trace
        from trino_tpu.runtime.observability import validate_chrome_trace

        def seg(ts0):
            return {"traceEvents": [
                {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
                 "args": {"name": "x"}},
                {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
                 "args": {"name": "t"}},
                {"name": "s", "ph": "B", "ts": ts0, "pid": 1, "tid": 1},
                {"name": "s", "ph": "E", "ts": ts0 + 10, "pid": 1, "tid": 1},
            ]}

        merged = assemble_cluster_trace(
            {"worker-a": seg(1_000_000), "worker-b": seg(500)},
            offsets={"worker-a": -999_000, "worker-b": 1_500},
        )
        assert validate_chrome_trace(merged) == []
        by_node = {}
        lanes = {
            e["pid"]: e["args"]["name"]
            for e in merged["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        for e in merged["traceEvents"]:
            if e.get("ph") == "B":
                by_node[lanes[e["pid"]]] = e["ts"]
        assert by_node == {"worker-a": 1_000, "worker-b": 2_000}

    def test_merge_clamps_regressed_timestamps_per_lane(self):
        """A restarted worker's ring can hold two monotonic epochs; after
        alignment the lane must still satisfy Perfetto's per-track order."""
        from trino_tpu.runtime.clusterobs import assemble_cluster_trace
        from trino_tpu.runtime.observability import validate_chrome_trace

        seg = {"traceEvents": [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "w"}},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "t"}},
            {"name": "a", "ph": "B", "ts": 10_000, "pid": 1, "tid": 1},
            {"name": "a", "ph": "E", "ts": 10_010, "pid": 1, "tid": 1},
            # fresh monotonic epoch after restart: clock regressed
            {"name": "b", "ph": "B", "ts": 5, "pid": 1, "tid": 1},
            {"name": "b", "ph": "E", "ts": 15, "pid": 1, "tid": 1},
        ]}
        merged = assemble_cluster_trace({"worker": seg})
        assert validate_chrome_trace(merged) == []

    def test_journal_records_become_their_own_lane(self):
        from trino_tpu.runtime.clusterobs import assemble_cluster_trace

        seg = {"traceEvents": [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "c"}},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "t"}},
            {"name": "q", "ph": "i", "ts": 100, "pid": 1, "tid": 1},
        ]}
        merged = assemble_cluster_trace(
            {"coordinator": seg},
            journal_records=[
                {"kind": "begin", "epoch": 1, "ts": 10.0, "query_id": "q"},
                {"kind": "finished", "epoch": 2, "ts": 11.0},
            ],
        )
        marks = [e for e in merged["traceEvents"]
                 if e.get("cat") == "journal"]
        assert [m["name"] for m in marks] == [
            "journal:begin", "journal:finished"
        ]
        assert {m["args"]["epoch"] for m in marks} == {1, 2}

    def test_merged_trace_monotonic_under_lease_expire_failover(
        self, tmp_path, monkeypatch
    ):
        """Satellite: mid-query the leader's renewal forfeits under
        ``lease_expire`` chaos (a GC pause), the standby claims epoch 2,
        and the fenced old leader aborts at its next journal append; the
        standby resumes from the orphaned journal and the merged cluster
        trace stays monotonic per lane with ``task_attempt`` spans from
        BOTH leader epochs."""
        import time

        from trino_tpu.parallel.runner import DistributedQueryRunner
        from trino_tpu.runtime.clusterobs import (
            assemble_cluster_trace,
            local_segment,
        )
        from trino_tpu.runtime.failure import ChaosInjector
        from trino_tpu.runtime.ha import (
            DispatchJournal,
            FencedWriteError,
            LeaderLease,
            orphaned_journals,
            resume_fte_query,
        )
        from trino_tpu.runtime.observability import (
            RECORDER,
            validate_chrome_trace,
        )

        sql = ("SELECT count(*) FROM lineitem JOIN orders "
               "ON l_orderkey = o_orderkey")
        exdir = str(tmp_path / "ex")
        hadir = str(tmp_path / "ha")

        def make_runner(lease):
            r = DistributedQueryRunner.tpch(scale=0.0005, n_workers=2)
            r.session.set("retry_policy", "TASK")
            r.session.set("join_distribution_type", "PARTITIONED")
            r.session.set("target_partition_rows", 500)
            r.session.set("fte_exchange_dir", exdir)
            r.session.set("ha_plane", True)
            r.session.set("cluster_obs", True)
            r.ha_lease = lease
            return r

        lease_a = LeaderLease(hadir, "coord-a", ttl=0.2)
        lease_b = LeaderLease(hadir, "coord-b", ttl=10.0)
        assert lease_a.acquire() and lease_a.epoch == 1

        orig_stage_done = DispatchJournal.stage_done
        failed_over = []

        def stage_done_with_failover(journal, fid):
            if not failed_over:
                failed_over.append(True)
                # the GC pause: lease_expire chaos forfeits the renewal,
                # the lease lapses, the standby takes epoch 2 — the
                # delegated append below is then fenced
                with ChaosInjector() as chaos:
                    chaos.arm("lease_expire", times=1)
                    assert not lease_a.renew()
                time.sleep(0.25)
                assert lease_b.acquire() and lease_b.epoch == 2
            return orig_stage_done(journal, fid)

        monkeypatch.setattr(
            DispatchJournal, "stage_done", stage_done_with_failover
        )
        RECORDER.clear()
        RECORDER.enable()
        try:
            with pytest.raises(FencedWriteError):
                make_runner(lease_a).execute(sql)
            orphans = orphaned_journals(exdir)
            assert len(orphans) == 1
            result = resume_fte_query(make_runner(lease_b), orphans[0])
            assert result.rows and result.rows[0][0]
            journal_records = (result.query_stats or {}).get("journal") or []
            qid = next(
                str(r["query_id"]) for r in journal_records
                if r.get("kind") == "begin"
            )
            merged = assemble_cluster_trace(
                {"coordinator": local_segment([qid])},
                journal_records=journal_records,
            )
        finally:
            RECORDER.disable()
            RECORDER.clear()
        assert validate_chrome_trace(merged) == []  # paired B/E + monotonic
        epochs = {
            (e.get("args") or {}).get("epoch")
            for e in merged["traceEvents"]
            if e.get("name") == "task_attempt" and e.get("ph") == "B"
        }
        assert {1, 2} <= epochs


class TestFederatedMetrics:
    def test_announcement_snapshot_bounded_and_drop_counted(self):
        """Satellite: the piggybacked snapshot is capped; overflow is
        dropped and counted, so heartbeats never bloat."""
        from trino_tpu.runtime.clusterobs import announcement_metrics
        from trino_tpu.runtime.metrics import MetricsRegistry

        reg = MetricsRegistry()
        for i in range(6):
            reg.counter(f"m{i}_total", help="a counter").inc()
        series, dropped = announcement_metrics(reg, max_series=4)
        assert len(series) == 4
        assert dropped == 2
        drop_counter = reg.counter(
            "trino_tpu_announcement_metrics_dropped_total",
            help="metric series dropped from announcement snapshots by the "
                 "size bound",
        )
        assert drop_counter.value == 2

    def test_render_preserves_help_adds_node_labels_merges_buckets(self):
        from trino_tpu.runtime.clusterobs import (
            ClusterMetrics,
            announcement_metrics,
        )
        from trino_tpu.runtime.metrics import MetricsRegistry

        cm = ClusterMetrics()
        for node, n in (("w1", 2), ("w2", 3)):
            reg = MetricsRegistry()
            reg.counter("jobs_total", help="jobs processed").inc(n)
            h = reg.histogram(
                "lat_secs", help="latency", buckets=[0.1, 1.0]
            )
            for _ in range(n):
                h.observe(0.05)
            series, _ = announcement_metrics(reg, max_series=100)
            cm.ingest(node, series)
        text = cm.render()
        assert text.count("# HELP jobs_total jobs processed") == 1
        assert 'jobs_total{node="w1"} 2' in text
        assert 'jobs_total{node="w2"} 3' in text
        # cross-node merged histogram under node="all": bucket-wise sums
        assert 'lat_secs_bucket{node="all",le="0.1"} 5' in text
        assert 'lat_secs_count{node="all"} 5' in text

    def test_cluster_tables_sql_queryable(self):
        from trino_tpu.runtime import LocalQueryRunner
        from trino_tpu.runtime.clusterobs import (
            ClusterMetrics,
            announcement_metrics,
        )
        from trino_tpu.runtime.metrics import MetricsRegistry

        runner = LocalQueryRunner.tpch(scale=0.001)
        cm = ClusterMetrics()
        reg = MetricsRegistry()
        reg.counter("remote_things_total", help="things").inc(7)
        series, _ = announcement_metrics(reg, max_series=100)
        cm.ingest("worker-9", series)
        runner.metadata.system_context.cluster_metrics = cm
        res = runner.execute(
            "SELECT node, value FROM system.metrics.cluster_counters "
            "WHERE name = 'remote_things_total'"
        )
        assert ("worker-9", 7.0) in res.rows
        hist = runner.execute(
            "SELECT count(*) FROM system.metrics.cluster_histograms "
            "WHERE node = 'coordinator'"
        )
        # the coordinator's own histograms fold in with a node column
        assert hist.rows[0][0] >= 0

    def test_departed_node_snapshot_evicted_after_ttl(self):
        """A node that stops announcing (drained/dead) must age out of the
        fold — not serve its frozen last snapshot in the exposition and
        SQL tables forever."""
        import time

        from trino_tpu.runtime.clusterobs import ClusterMetrics

        cm = ClusterMetrics(ttl_secs=0.05)
        cm.ingest("gone", [{"name": "x_total", "type": "counter",
                            "value": 1.0, "help": "x", "labels": {}}])
        assert any(r[2] == "gone" for r in cm.counters_rows())
        time.sleep(0.1)
        cm.ingest("alive", [{"name": "x_total", "type": "counter",
                             "value": 2.0, "help": "x", "labels": {}}])
        nodes = {r[2] for r in cm.counters_rows()}
        assert nodes == {"alive"}
        assert 'node="gone"' not in cm.render()
        # ttl<=0 keeps forever (the default store is long-lived regardless)
        keep = ClusterMetrics(ttl_secs=0)
        keep.ingest("gone", [{"name": "x_total", "type": "counter",
                              "value": 1.0, "help": "x", "labels": {}}])
        time.sleep(0.02)
        assert any(r[2] == "gone" for r in keep.counters_rows())


class TestQueryProfiles:
    def test_query_manager_auto_persists_over_threshold(self, tmp_path,
                                                        monkeypatch):
        from trino_tpu.runtime import LocalQueryRunner
        from trino_tpu.runtime.clusterobs import profile_store
        from trino_tpu.runtime.query_manager import QueryManager, QueryState

        import threading

        monkeypatch.setenv("TRINO_TPU_QUERY_PROFILE_DIR", str(tmp_path))
        runner = LocalQueryRunner.tpch(scale=0.001)
        runner.session.set("cluster_obs", True)
        mgr = QueryManager(runner.execute)
        # profile persistence happens BEFORE query_completed dispatch, so a
        # completion listener is the hook-finished synchronization point
        completed = threading.Event()
        mgr.add_listener(lambda _q: completed.set())
        q = mgr.submit("SELECT count(*) FROM nation")
        assert q.wait_done(120) and q.state is QueryState.FINISHED
        assert completed.wait(30)
        store = profile_store(str(tmp_path))
        profile = store.read(q.query_id)
        assert profile is not None
        assert profile["queryId"] == q.query_id
        assert profile["state"] == "FINISHED"
        assert profile["version"] == 1
        # a threshold above the query's wall time suppresses persistence
        runner.session.set("slow_query_threshold", 3600.0)
        completed.clear()
        q2 = mgr.submit("SELECT count(*) FROM region")
        assert q2.wait_done(120) and q2.state is QueryState.FINISHED
        assert completed.wait(30)
        assert store.read(q2.query_id) is None

    def test_profiles_sql_table_and_gate_off_path(self, tmp_path,
                                                  monkeypatch):
        from trino_tpu.runtime import LocalQueryRunner
        from trino_tpu.runtime.clusterobs import build_profile, profile_store
        from trino_tpu.runtime.query_manager import QueryManager, QueryState

        monkeypatch.setenv("TRINO_TPU_QUERY_PROFILE_DIR", str(tmp_path))
        store = profile_store(str(tmp_path))
        store.write(build_profile(
            "q_profiled", "SELECT 1", wall_secs=0.5,
            query_stats={"times": {"device_busy_secs": 0.3,
                                   "host_wait_secs": 0.1}},
        ))
        runner = LocalQueryRunner.tpch(scale=0.001)
        res = runner.execute(
            "SELECT query_id, diagnosis FROM system.runtime.query_profiles"
        )
        assert any(r[0] == "q_profiled" for r in res.rows)
        diag = next(r[1] for r in res.rows if r[0] == "q_profiled")
        assert "device" in diag
        # cluster_obs OFF: a completed query persists nothing
        mgr = QueryManager(runner.execute)
        q = mgr.submit("SELECT count(*) FROM nation")
        assert q.wait_done(120) and q.state is QueryState.FINISHED
        assert store.read(q.query_id) is None

    def test_explain_analyze_verbose_diagnosis_line(self):
        from trino_tpu.runtime import LocalQueryRunner

        runner = LocalQueryRunner.tpch(scale=0.001)
        sql = ("EXPLAIN ANALYZE VERBOSE SELECT l_returnflag, count(*) "
               "FROM lineitem GROUP BY 1")
        plain = "\n".join(r[0] for r in runner.execute(sql).rows)
        assert "dominant cost" not in plain  # gated off by default
        runner.session.set("cluster_obs", True)
        verbose = "\n".join(r[0] for r in runner.execute(sql).rows)
        assert "dominant cost — " in verbose
        tail = verbose.split("dominant cost — ", 1)[1]
        assert "%" in tail

    def test_dominant_cost_renders_stage_and_component(self):
        from trino_tpu.runtime.clusterobs import dominant_cost

        line = dominant_cost([
            ("stage 1", 1.0, {"device_secs": 0.8, "host_secs": 0.2}),
            ("stage 2", 3.0, {"exchange_pull_secs": 2.5,
                              "device_secs": 0.5}),
        ])
        assert line.startswith("stage 2: ")
        assert line.endswith("% exchange pull")
        assert dominant_cost([]) is None


class TestClusterEndpoints:
    def test_worker_announcement_off_path_byte_identical(self, monkeypatch):
        from trino_tpu.metadata import CatalogManager
        from trino_tpu.server.worker import WorkerServer

        monkeypatch.delenv("TRINO_TPU_CLUSTER_OBS", raising=False)
        w = WorkerServer(CatalogManager())
        assert set(w.announcement_body()) == {
            "uri", "version", "device", "memory"
        }
        monkeypatch.setenv("TRINO_TPU_CLUSTER_OBS", "1")
        body = w.announcement_body()
        assert isinstance(body["metrics"], list)
        assert "mono_us" in body["clock"] and "rtt_us" in body["clock"]

    def test_worker_flightrecorder_route_gated_and_signed(self, monkeypatch):
        import urllib.error
        import urllib.request

        from trino_tpu.metadata import CatalogManager
        from trino_tpu.server.worker import (
            SIGNATURE_HEADER,
            WorkerServer,
            sign,
        )

        monkeypatch.delenv("TRINO_TPU_CLUSTER_OBS", raising=False)
        w = WorkerServer(CatalogManager(), secret="obs-secret").start()
        try:
            url = f"http://{w.address}/v1/flightrecorder"
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(url, timeout=10)
            assert err.value.code == 404  # flag off: route absent
            monkeypatch.setenv("TRINO_TPU_CLUSTER_OBS", "1")
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(url, timeout=10)
            assert err.value.code == 401  # unsigned
            req = urllib.request.Request(url + "?query_id=qx")
            req.add_header(
                SIGNATURE_HEADER, sign("obs-secret", "GET", "/v1/flightrecorder")
            )
            payload = json.loads(
                urllib.request.urlopen(req, timeout=10).read()
            )
            assert payload["node"]
            assert "traceEvents" in payload["trace"]
        finally:
            w.stop()

    def test_coordinator_cluster_routes_gated(self, monkeypatch):
        import urllib.error
        import urllib.request

        from trino_tpu.runtime import LocalQueryRunner
        from trino_tpu.server.coordinator import CoordinatorServer

        monkeypatch.delenv("TRINO_TPU_CLUSTER_OBS", raising=False)
        srv = CoordinatorServer(LocalQueryRunner.tpch(scale=0.001)).start()
        try:
            for rel in ("/v1/metrics/cluster", "/v1/query/qx/profile"):
                with pytest.raises(urllib.error.HTTPError) as err:
                    urllib.request.urlopen(
                        f"http://{srv.address}{rel}", timeout=10
                    )
                assert err.value.code == 404
            monkeypatch.setenv("TRINO_TPU_CLUSTER_OBS", "1")
            text = urllib.request.urlopen(
                f"http://{srv.address}/v1/metrics/cluster", timeout=10
            ).read().decode()
            assert 'node="coordinator"' in text
            assert "# HELP" in text
        finally:
            srv.stop()

    def test_coordinator_query_id_filter_gated_off(self, monkeypatch):
        """With the flag off the coordinator's /v1/flightrecorder ignores
        ?query_id= (unknown params always were ignored) — the response is
        byte-identical to the pre-plane full-ring export."""
        import urllib.request

        from trino_tpu.runtime import LocalQueryRunner
        from trino_tpu.server.coordinator import CoordinatorServer

        monkeypatch.delenv("TRINO_TPU_CLUSTER_OBS", raising=False)
        srv = CoordinatorServer(LocalQueryRunner.tpch(scale=0.001)).start()
        try:
            base = f"http://{srv.address}/v1/flightrecorder"
            plain = urllib.request.urlopen(base, timeout=10).read()
            filtered = urllib.request.urlopen(
                base + "?query_id=qx", timeout=10
            ).read()
            assert filtered == plain
            # flag on: the same request returns the filtered segment
            monkeypatch.setenv("TRINO_TPU_CLUSTER_OBS", "1")
            seg = json.loads(urllib.request.urlopen(
                base + "?query_id=qx", timeout=10
            ).read())
            # nothing recorded for qx: metadata-only export
            assert [e for e in seg["traceEvents"] if e.get("ph") != "M"] == []
        finally:
            srv.stop()

    def test_announcement_riders_feed_clock_and_metrics(self, monkeypatch):
        import urllib.request

        from trino_tpu.runtime import LocalQueryRunner
        from trino_tpu.server.coordinator import CoordinatorServer

        srv = CoordinatorServer(LocalQueryRunner.tpch(scale=0.001)).start()
        try:
            body = json.dumps({
                "uri": "http://w:1", "clock": {"mono_us": 10, "rtt_us": 4},
                "metrics": [{"name": "x_total", "type": "counter",
                             "value": 2.0, "help": "x", "labels": {}}],
            }).encode()
            req = urllib.request.Request(
                f"http://{srv.address}/v1/announcement/w-obs",
                data=body, method="PUT",
            )
            urllib.request.urlopen(req, timeout=10)
            assert srv.clock_sync.offset_us("w-obs") != 0
            rows = srv.cluster_metrics.counters_rows()
            assert any(r[0] == "x_total" and r[2] == "w-obs" for r in rows)
        finally:
            srv.stop()
