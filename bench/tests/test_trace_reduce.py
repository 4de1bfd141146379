"""``trace_reduce`` on a hand-made trace, and on a small trace recorded on
a TPU v5e (``bench/tests/data/``), read through ``jax.profiler``."""
import glob
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_op_class():
    assert tr.op_class("scatter.12") == "scatter"
    assert tr.op_class("%scatter.3 = s32[9]{0} scatter(s32[9]{0} %a)") \
        == "scatter"
    fusion = ("%fusion.195 = s32[1048577]{0:T(1024)S(1)} fusion(s32[16777216]"
              "{0:T(1024)} %bitcast.51), kind=kCustom, "
              "calls=%fused_computation.97.clone.clone")
    assert tr.instruction(fusion) == "fusion.195"
    assert tr.op_class(fusion) == "fusion"
    assert tr.op_class(fusion, {"fusion.195": "scatter"}) == "scatter"
    assert tr.op_class("%while.7 = (s32[]{:T(128)}) while((s32[]) %t)") \
        == "while"
    assert tr.op_class("%cond.54 = (s32[9]{0}) conditional(s32[] %c)") \
        == "conditional"
    assert tr.op_class("copy-start.4") == "copy-start"


def test_op_kinds_from_hlo_text():
    # as Compiled.as_text() prints them on the v5e: the scatter of fusion.221
    # sits two fusions deep, the op name says what it is
    hlo = """%fused_computation.36 (param_0: s32[9], p1: s32[4]) -> s32[9] {
  ROOT %scatter-min.3 = s32[9]{0} scatter(%param_0, %p1), to_apply=%min, metadata={op_name="jit(<lambda>)/while/body/scatter-min"}
}

ENTRY %main (a: s32[9], b: s32[4]) -> s32[9] {
  %fusion.221 = s32[9]{0} fusion(%a, %b), kind=kCustom, calls=%fused_computation.152.clone.clone, metadata={op_name="jit(<lambda>)/while/body/while/body/scatter-min" stack_frame_id=36}
  %fusion.189 = s32[4]{0} fusion(%a, %b), kind=kCustom, calls=%fused_computation.12, metadata={op_name="jit(<lambda>)/while/body/jit(_take)/gather"}
  %while.82 = (s32[9]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(<lambda>)/while"}
  %add.1 = s32[9]{0} add(%a, %a), metadata={op_name="jit(<lambda>)/add"}
}
"""
    assert tr.op_kinds(hlo) == {"fusion.221": "scatter",
                                "scatter-min.3": "scatter",
                                "fusion.189": "gather"}


def test_reduce_hand_made_trace():
    ns = 1_000_000
    trace = {
        "devices": {"/device:TPU:0": [("scatter.1", 10 * ns, 40 * ns),
                                      ("fusion.2", 30 * ns, 50 * ns),
                                      ("fusion.3", 80 * ns, 90 * ns),
                                      ("copy.9", 200 * ns, 300 * ns)]},
        "spans": [(tr.WINDOW_SPAN, 0, 100 * ns),
                  ("bench.submit", 55 * ns, 75 * ns),
                  ("bench.submit", 92 * ns, 93 * ns),
                  ("bench.solve", 0, 8 * ns)]}
    r = tr.reduce(trace)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.05)           # 10-50, 80-90
    assert r["class_s"]["scatter"] == pytest.approx(0.03)
    gaps = dict((k, v) for k, v in r["breakdown"]["idle_gaps"])
    assert gaps["bench.submit"] == pytest.approx(0.03)  # 50-80
    assert gaps["bench.solve"] == pytest.approx(0.01)   # 0-10
    assert gaps["host"] == pytest.approx(0.01)          # 90-100
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    ops = dict((k, v) for k, v in r["breakdown"]["device_ops"])
    assert ops["scatter.1:scatter"] == pytest.approx(0.03)
    assert "copy.9:copy" not in ops                      # outside the window
    ctx = {"trace": r}
    assert tr.idle_share(ctx) == pytest.approx(50.0)
    assert tr.class_share(ctx, "scatter") == pytest.approx(60.0)


def test_reduce_without_device_operations():
    r = tr.reduce({"devices": {}, "spans": [(tr.WINDOW_SPAN, 0, 10)]})
    assert r["busy_s"] == 0.0
    assert tr.idle_share({"trace": r}) is None


RECORDED = sorted(glob.glob(os.path.join(DATA, "*", "plugins", "profile",
                                         "*", "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[p.split(os.sep)[-5] for p in RECORDED])
def test_recorded_tpu_trace(path):
    trace = tr.load(os.path.dirname(path))
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert any(n == tr.WINDOW_SPAN for n, _, _ in trace["spans"])
    r = tr.reduce(trace)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert 0 < sum(r["class_s"].values()) <= r["busy_s"] * (1 + 1e-6)
    ctx = {"trace": r}
    assert 0 <= tr.idle_share(ctx) < 100
    share = tr.class_share(ctx, "scatter")
    assert share is None or 0 < share <= 100
