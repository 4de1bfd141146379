"""Every metric reader on hand-made context: what it reads, and that it
returns nothing where there is nothing to read."""
import math
import os

import pytest

import run

SPEC = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
NAMES = sorted({m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}
               | {os.path.splitext(f)[0] for f in os.listdir(
                   os.path.join(run.BENCH, "metrics")) if f.endswith(".py")})


def reader(name):
    return run.load_module(os.path.join(run.BENCH, "metrics", f"{name}.py"),
                           f"m_{name.replace('.', '_')}").read


def test_every_metric_has_a_reader():
    for name in NAMES:
        assert callable(reader(name))


@pytest.mark.parametrize("name", [n for n in NAMES if n != "setup_s"])
def test_reader_returns_nothing_without_its_data(name):
    assert reader(name)({"setup_s": 1.0, "window_s": 1.0,
                         "seconds": 1.0}) is None


def test_solve_and_counters():
    solves = [{"graph": 0, "start": 0.0, "end": 4.0, "phases": 2,
               "size": 900},
              {"graph": 1, "start": 4.0, "end": 8.5, "phases": 1,
               "size": 1100}]
    ctx = {"setup_s": 7.0, "window_s": 8.5, "seconds": 5.0, "solves": solves,
           "warm_sizes": [855, 1045]}
    assert reader("setup_s")(ctx) == 7.0
    assert reader("solve_s")(ctx) == 8.5 / 2
    assert reader("phases_per_solve")(ctx) == 1.5
    assert reader("warm_matched_share")(ctx) == pytest.approx(95.0)


def test_rate():
    ctx = {"setup_s": 1.0, "window_s": 10.0, "seconds": 10.0,
           "answered_s": [9.0, 10.0, 10.5, None, 3.0]}
    assert reader("solved_per_s")(ctx) == pytest.approx(0.3)


def test_service_counters():
    snap = {"completed": 10, "batch_padded": 16, "occupancy": 10 / 16,
            "queue_wait_p50_ms": 3.5}
    ctx = {"setup_s": 1.0, "window_s": 1.0, "seconds": 1.0, "service": snap}
    assert reader("batch_occupancy.overload")(ctx) == pytest.approx(62.5)


def test_trace_shares():
    trace = {"busy_s": 3.0, "window_s": 4.0,
             "class_s": {"scatter": 1.5, "fusion": 1.0}}
    ctx = {"setup_s": 1.0, "window_s": 4.0, "seconds": 4.0, "trace": trace}
    for name in ("device_idle_share.solve", "device_idle_share.overload"):
        assert reader(name)(ctx) == pytest.approx(25.0)
    assert reader("scatter_share.solve")(ctx) == pytest.approx(50.0)
    trace["class_s"] = {"fusion": 3.0}
    assert reader("scatter_share.solve")(ctx) is None
    assert not math.isnan(reader("device_idle_share.solve")(ctx))
