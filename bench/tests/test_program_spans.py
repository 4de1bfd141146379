"""The readers of the program's serving counters on hand-made context, and
``program_spans``' reduction on hand-made spans and operations."""
import os

import pytest

import program_spans as ps
import run
import trace_reduce as tr

MS = 1_000_000


def reader(name):
    return run.load_module(os.path.join(run.BENCH, "metrics", f"{name}.py"),
                           f"m_{name.replace('.', '_')}").read


def ctx_of(snap):
    return {"setup_s": 1.0, "window_s": 3.0, "seconds": 3.0,
            "service": snap}


SNAP = {"submitted": 80, "batch_flushes": 10, "admit_s": 0.04,
        "stack_s": 0.25, "batch_solve_s": 1.5, "resolve_s": 0.6,
        "lane_levels": 600, "lane_level_slots": 800}


def test_stage_means_per_request_and_per_flush():
    ctx = ctx_of(SNAP)
    assert reader("admit_ms.overload")(ctx) == pytest.approx(0.5)
    assert reader("stack_ms.overload")(ctx) == pytest.approx(25.0)
    assert reader("batch_solve_ms.overload")(ctx) == pytest.approx(150.0)
    assert reader("resolve_ms.overload")(ctx) == pytest.approx(60.0)
    assert reader("lane_level_share.overload")(ctx) == pytest.approx(75.0)


@pytest.mark.parametrize("name", ["admit_ms.overload", "stack_ms.overload",
                                  "batch_solve_ms.overload",
                                  "resolve_ms.overload",
                                  "lane_level_share.overload"])
def test_counter_readers_report_nothing_without_the_counters(name):
    # a program without the counters: the snapshot of an older service
    old = {"submitted": 80, "batch_padded": 88, "occupancy": 80 / 88}
    assert reader(name)(ctx_of(old)) is None
    # a service that never flushed
    idle = dict(SNAP, submitted=0, batch_flushes=0, lane_level_slots=0)
    assert reader(name)(ctx_of(idle)) is None


def trace_of(ops, window=(0, 100 * MS)):
    return {"devices": {"/device:TPU:0": [("fusion.1", s * MS, e * MS)
                                          for s, e in ops]},
            "spans": [(tr.WINDOW_SPAN, *window)]}


def spans_of(*spans):
    return [(f"repro.serve.{n}", s * MS, e * MS) for n, s, e in spans]


FLUSH_HOST = ("repro.serve.stack", "repro.serve.resolve")


def test_idle_under_the_flush_thread_host_stages():
    # device busy 10-40 and 60-90 ms of a 100 ms window: idle 50 ms
    trace = trace_of([(10, 40), (60, 90)])
    spans = spans_of(("flush", 0, 100),          # the parent: not counted
                     ("stack", 0, 12),           # 10 ms idle under it
                     ("resolve", 38, 50),        # 10 ms idle (40-50)
                     ("wait", 50, 60),           # idle, but not a host stage
                     ("stack", 55, 65),          # 5 ms idle (55-60)
                     ("resolve", 88, 120))       # 10 ms idle, clipped at 100
    got = ps.idle_under(trace, spans, FLUSH_HOST)
    assert got == pytest.approx(35.0)
    # a decomposition of the window's idle share, never above it
    idle = 100.0 * (1 - sum(e - s for s, e in [(10, 40), (60, 90)]) / 100)
    assert got <= idle == 40.0
    # the 50-55 ms gap lies under no host stage: it stays out
    assert ps.idle_under(trace, spans, ("repro.serve.wait",)) \
        == pytest.approx(10.0)


def test_idle_under_averages_over_devices():
    trace = trace_of([(0, 100)])
    trace["devices"]["/device:TPU:1"] = [("fusion.2", 0, 50 * MS)]
    spans = spans_of(("stack", 40, 80))
    # device 0 never idle, device 1 idle 50-80: 30 ms over two devices
    assert ps.idle_under(trace, spans, FLUSH_HOST) == pytest.approx(15.0)


def test_idle_under_reports_nothing_without_the_spans():
    trace = trace_of([(10, 40)])
    assert ps.idle_under(trace, [], FLUSH_HOST) is None
    assert ps.idle_under(trace, spans_of(("wait", 0, 50)), FLUSH_HOST) is None
    # spans outside the window
    assert ps.idle_under(trace, spans_of(("stack", 150, 160)),
                         FLUSH_HOST) is None
    assert ps.idle_under({"devices": {}, "spans": []},
                         spans_of(("stack", 0, 10)), FLUSH_HOST) is None


def test_the_reader_needs_a_traced_run(tmp_path, monkeypatch):
    read = reader("flush_idle_share.overload")
    assert read({"setup_s": 1.0, "window_s": 1.0, "seconds": 1.0}) is None
    monkeypatch.setattr(ps, "OUT", str(tmp_path))    # no trace there
    assert ps.load() is None
    assert read({"trace": {"busy_s": 1.0, "window_s": 2.0}}) is None
