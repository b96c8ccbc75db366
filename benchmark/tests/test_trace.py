"""The reducer from trace events to busy time, idle share, launches and the
breakdown: on events written out by hand, and on a
small trace recorded on the chip and kept beside this file."""

import json
from pathlib import Path

import pytest

from benchmark import trace

MS = 1e6
DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def by_hand():
    return [
        (HOST, "main", trace.WINDOW_SPAN, 0.0, 100 * MS),
        (HOST, "client-0", "stmt:q06", 5 * MS, 40 * MS),      # wholly inside
        (HOST, "client-0", "stmt:q14", 50 * MS, 70 * MS),     # cut by the window's end
        (HOST, "client-0", "something else", 0.0, 5 * MS),
        (HOST, "query-1", "np.asarray(jax.Array)", 40 * MS, 50 * MS),   # the host waits, 40 to 90
        (HOST, "tpu-2", "ReadSyncFlag", 30 * MS, 20 * MS),             # 30 to 50
        (DEV0, trace.MODULES_LINE, "jit_filter", 10 * MS, 12 * MS),
        (DEV0, trace.MODULES_LINE, "jit_sum", 30 * MS, 5 * MS),
        (DEV0, trace.MODULES_LINE, "jit_late", 110 * MS, 5 * MS),   # after the window
        (DEV0, trace.OPS_LINE, "fusion.1", 10 * MS, 10 * MS),
        (DEV0, trace.OPS_LINE, "fusion.2", 15 * MS, 7 * MS),        # overlaps fusion.1
        (DEV0, trace.OPS_LINE, "all-to-all.3", 30 * MS, 5 * MS),
        (DEV0, trace.OPS_LINE, "fusion.1", 95 * MS, 10 * MS),       # half outside
        (DEV1, trace.OPS_LINE, "fusion.1", 10 * MS, 4 * MS),
        (DEV1, trace.MODULES_LINE, "jit_filter", 10 * MS, 4 * MS),
    ]


def test_busy_union_idle_launches_by_hand():
    reduced = trace.reduce(by_hand())
    dev = reduced.fullest
    assert dev.name == DEV0 and [d.name for d in reduced.devices] == [DEV0, DEV1]
    assert dev.busy == [[10 * MS, 22 * MS], [30 * MS, 35 * MS], [95 * MS, 100 * MS]]
    assert dev.busy_s == pytest.approx(0.022) and reduced.window_s == pytest.approx(0.1)
    assert dev.launches == 2  # the one after the window is not counted
    assert reduced.busy_and_window() == {"busy_s": pytest.approx(0.013), "window_s": pytest.approx(0.1)}
    # q06 lies wholly in the window, q14 is cut by its end
    assert reduced.spans == [("q06", 5 * MS, 45 * MS, True), ("q14", 50 * MS, 100 * MS, False)]
    assert reduced.busy_inside({"q06", "q14"}) == (pytest.approx(0.017), 1)
    # gaps 0-10 and 22-30 have their middle in q06's span, 35-95 in q14's
    assert reduced.idle_gaps() == {"in q06": pytest.approx(0.018), "in q14": pytest.approx(0.060)}
    top = reduced.breakdown()
    assert top["device_ops"][0] == ["jit_filter fusion.1", pytest.approx(0.010)]
    assert ["jit_sum all-to-all.3", pytest.approx(0.005)] in top["device_ops"]
    assert trace.short("%sort.82 = (s8[37748736]{0:T(1024)}, u32[3]) sort(%a, %b)") == "%sort.82"
    assert trace.short("jit__jit_compact(7061929836682903344)") == "jit__jit_compact"
    assert sum(s for _, s in top["idle_gaps"][:-1]) == pytest.approx(0.078)
    # the one longest gap, 35 to 95: what the host was doing, by the seconds of the gap covered
    assert top["idle_gaps"][-1] == [
        "longest gap, in q14; host: np.asarray(jax.Array) 0.050, ReadSyncFlag 0.015",
        pytest.approx(0.060),
    ]


def test_collectives_on_both_lines_count_once_and_async_ones_as_no_busy_time():
    from benchmark.layer_metrics import collective_pct

    plain = trace.reduce(by_hand())
    assert plain.fullest.collective == [[30 * MS, 35 * MS]]       # the all-to-all of jit_sum
    assert collective_pct.of(plain.devices) == pytest.approx(100 * 5 / 22)
    assert collective_pct.of(plain.devices[1:]) is None           # device 1 shows none
    more = trace.reduce(by_hand() + [
        # in flight from its start to its done, beside fusion.1 and fusion.2, and past them
        (DEV0, trace.ASYNC_LINE, "%all-gather-start.1", 12 * MS, 12 * MS),
        (DEV0, trace.OPS_LINE, "%all-gather-done.1", 23 * MS, 1 * MS),
        (DEV0, trace.OPS_LINE, "%all-reduce.7", 33 * MS, 1 * MS),    # inside the all-to-all's 30 to 35
        (DEV0, trace.OPS_LINE, "%all-reduce-scatter-fusion", 60 * MS, 1 * MS),   # a fusion, not by name
    ])
    dev = more.fullest
    assert dev.collective == [[12 * MS, 24 * MS], [30 * MS, 35 * MS]]
    # busy: what ran on the `XLA Ops` line only; the done op and the odd fusion add 1 ms each
    assert dev.busy == [[10 * MS, 22 * MS], [23 * MS, 24 * MS], [30 * MS, 35 * MS],
                        [60 * MS, 61 * MS], [95 * MS, 100 * MS]]
    assert collective_pct.of(more.devices) == pytest.approx(100 * 17 / 24)
    assert trace.COLLECTIVE.match(trace.short("%collective-permute-start.12 = (f32[8]{0}, f32[8]{0}) collective-permute-start(%x)"))
    assert not trace.COLLECTIVE.match("%fusion.3") and not trace.COLLECTIVE.match("%copy-start.18")


def test_longest_gap_names_the_innermost_host_span_first():
    """Nested spans all cover the gap: the innermost (latest start) says most;
    an event that covers less than half of it comes after them."""
    nested = by_hand() + [
        (HOST, "query-1", "trino:statement", 36 * MS, 62 * MS),        # 36 to 98
        (HOST, "query-1", "trino:drain", 38 * MS, 58 * MS),            # 38 to 96
        (HOST, "query-1", "trino:mesh:gather", 45 * MS, 50 * MS),      # 45 to 95
        (HOST, "query-1", "trino:encode", 80 * MS, 10 * MS),           # late, but a sixth of the gap
    ]
    seconds, doing = trace.reduce(nested).longest_gap()
    assert seconds == pytest.approx(0.060)
    assert doing == ("longest gap, in q14; host: trino:mesh:gather 0.050, "
                     "np.asarray(jax.Array) 0.050, trino:drain 0.057")


def test_a_trace_without_a_device_or_a_window_is_refused():
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        trace.reduce([e for e in by_hand() if e[0] == HOST])
    with pytest.raises(ValueError, match="bench_window"):
        trace.reduce([e for e in by_hand() if e[2] != trace.WINDOW_SPAN])


RECORDED = Path(__file__).with_name("recorded_trace.json")


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace beside the test")
def test_the_recorded_trace_reduces_to_what_was_read_on_the_chip():
    kept = json.loads(RECORDED.read_text())
    reduced = trace.reduce([tuple(e) for e in kept["events"]])
    want = kept["read_on_the_chip"]
    assert reduced.fullest.launches == want["launches"]
    assert reduced.fullest.busy_s == pytest.approx(want["busy_s"])
    assert reduced.window_s == pytest.approx(want["window_s"])
    assert 0 < reduced.fullest.busy_s < reduced.window_s
    assert [name for name, _ in reduced.breakdown()["device_ops"]][:3] == want["top_ops"]


MESH = json.loads(Path(__file__).with_name("recorded_mesh.json").read_text())


def test_the_recorded_mesh_trace_reads_its_collectives_where_the_profiler_names_them():
    from benchmark.layer_metrics import collective_pct

    reduced = trace.reduce([tuple(e) for e in MESH["events"]])
    want = MESH["by_hand"]
    assert reduced.fullest.name == want["fullest"] and reduced.window_s == pytest.approx(want["window_s"])
    assert reduced.fullest.busy_s == pytest.approx(want["busy_s"])
    assert reduced.fullest.launches == want["launches"]
    dev0, dev1 = reduced.devices
    # device 0 ran the same programs; the profiler calls Q14's operations `region.<n>` there,
    # so it shows q06's all-reduces only
    assert dev0.collective_s < 1e-2 * dev1.collective_s
    assert dev1.name == want["collective_device"] and dev1.busy_s == pytest.approx(want["collective_device_busy_s"])
    assert dev1.collective_s == pytest.approx(want["collective_s"])    # none overlaps another
    assert collective_pct.of(reduced.devices) == pytest.approx(want["collective_pct"])
    assert 0.1 < want["collective_pct"] < 1.0
    # the gap's innermost covering span comes first: the statement's root, no span inside it covers half
    assert reduced.breakdown()["idle_gaps"][-1][0].startswith("longest gap, in q14v; host: trino:statement")
