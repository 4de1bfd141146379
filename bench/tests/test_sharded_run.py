"""The four-chip cell ``sharded.er21`` on four CPU devices at a tiny size,
and the arithmetic of :mod:`collective` on hand-made input.

A whole run goes through ``run.run_cell`` in a subprocess, since the forced
device count has to be set before JAX starts.  The CPU's trace has no
device planes, so the traced run reads a hand-made one that names the
compiled program's own merge instructions.
"""
import json
import os
import subprocess
import sys

import pytest

import collective
import run

SCRIPT = """
import argparse, contextlib, dataclasses, io, json, sys
import jax, jax.numpy as jnp
import run, collective, roofline, trace_reduce
from traffic import generate

real_load = generate.load
generate.load = lambda name: dict(real_load(name), log2_n=10)
spec = run.load_json(run.ROOT + "/BENCHMARK.json")
cell = next(w for w in spec["workloads"] if w["name"] == "sharded.er21")
config = run.load_json(run.BENCH + "/configs/sharded-transversal.json")
scenario = sys.argv[1]

if scenario == "traced":
    merges = []
    real_instructions = collective.merge_instructions

    def merge_instructions(hlo):
        merges[:] = real_instructions(hlo)
        return merges

    def load(_):
        us = 1000
        ops = [("%fusion.1 = s32[8]{0} fusion(%a)", 0, 50 * us)]
        ops += [(f"%{n} = s32[1025]{{0}} all-reduce(%b)", (60 + 20 * i) * us,
                 (70 + 20 * i) * us) for i, n in enumerate(merges)]
        return {"devices": {f"/device:TPU:{d}": ops for d in range(4)},
                "spans": [("bench.window", 0, 1000 * us)]}

    collective.merge_instructions = merge_instructions
    trace_reduce.load = load
    roofline.peaks = lambda kind: {"ici_bits_per_s": 1.6e12}

if scenario == "dropped_pair":
    from repro.matching import ShardedMatcher
    real_program = ShardedMatcher.program

    def program(self, graph, cold=True):
        fn = real_program(self, graph, cold)

        def dropped(g, s):
            out = fn(g, s)
            c = jnp.argmax(out.cmatch[:-1] >= 0)
            return dataclasses.replace(
                out, cmatch=out.cmatch.at[c].set(-1),
                rmatch=out.rmatch.at[out.cmatch[c]].set(-1))
        return dropped

    ShardedMatcher.program = program

args = argparse.Namespace(workload=cell["name"], seed=2**33 + 5,
                          seconds=1.0, trace=int(scenario == "traced"))
out = io.StringIO()
with contextlib.redirect_stdout(out):
    rc = run.run_cell(spec, cell, config, args, jax.devices()[:4])
print("RESULT", rc, out.getvalue().strip().splitlines()[-1])
"""


def run_scenario(scenario):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([run.BENCH,
                                           os.path.join(run.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, scenario],
                       cwd=run.ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, p.stderr[-3000:]
    _, rc, result = line[-1].split(" ", 2)
    assert rc == "0"
    return json.loads(result)


def test_sound_run_is_correct():
    result = run_scenario("sound")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"solve_s", "setup_s"}
    assert result["device"]["count"] == 4


def test_traced_run_reports_the_merge():
    result = run_scenario("traced")
    assert result["correct"] is True
    metrics = result["metrics"]
    assert {"merge_share.sharded", "merge_roofline_share.sharded",
            "phases_per_solve", "device_idle_share.solve"} <= set(metrics)
    assert 0 < metrics["merge_share.sharded"]["value"] < 100
    assert metrics["merge_roofline_share.sharded"]["value"] > 0
    assert any(op.endswith(":merge")
               for op, _ in result["breakdown"]["device_ops"])


def test_dropped_pair_is_not_correct():
    result = run_scenario("dropped_pair")
    assert result["correct"] is False and result["failed"] > 0
    assert result["checks"]["card_gap"]["value"] == 1
    assert result["checks"]["invalid_entries"]["value"] == 0


def test_allreduce_bytes():
    # a ring all-reduce sends (D-1)/D of the vector twice
    assert collective.allreduce_bytes(2**21 + 1, 4) == 1.5 * 4 * (2**21 + 1)
    assert collective.allreduce_bytes(100, 1) == 0


HLO = """%region_17.16 (pmin.7: s32[], pmin.8: s32[]) -> s32[] {
  %pmin.8 = s32[]{:T(128)} parameter(1), metadata={op_name="while/body/phase/while/body/bfs_level/merge_shards/pmin"}
}

ENTRY %main (a: s32[9]) -> s32[9] {
  %pmin.14 = s32[9]{0} all-reduce(%a), channel_id=1, to_apply=%region_17.16, metadata={op_name="jit(fn)/shard_map/while/body/phase/while/body/bfs_level/merge_shards/pmin" stack_frame_id=29}
  %all-reduce.11 = s32[9]{0} all-reduce(%fusion.172), channel_id=7, metadata={op_name="jit(fn)/warm_start/while/body/scatter-min" stack_frame_id=3}
  %fusion.2 = s32[9]{0} fusion(%a), kind=kCustom, metadata={op_name="jit(fn)/shard_map/while/body/phase/while/body/bfs_level/jit(_take)/gather"}
}
"""


def test_merge_instructions():
    # the merge and its reduction's parameter; not the warm start's
    # all-reduce, nor the level's gather
    assert collective.merge_instructions(HLO) == ["pmin.8", "pmin.14"]
    assert collective.merge_instructions(HLO, scope="nothing") == []


def _trace(ops):
    return {"devices": {"/device:TPU:0": ops, "/device:TPU:1": ops},
            "spans": [("bench.window", 100, 1000)]}


def test_merge_seconds_sync_and_async():
    sync = [("%pmin.14 = s32[9]{0} all-reduce(%a)", 150, 250),
            ("%fusion.2 = s32[9]{0} fusion(%a)", 250, 400),
            ("%pmin.14 = s32[9]{0} all-reduce(%a)", 50, 120),   # clipped
            ("%pmin.14 = s32[9]{0} all-reduce(%a)", 950, 1100)]
    assert collective.merge_seconds(_trace(sync), ["pmin.14"]) \
        == pytest.approx((100 + 20 + 50) / 1e9)
    # an asynchronous merge counts from start to done, work in between too
    split = [("%all-reduce-start.3 = s32[9]{0} all-reduce-start(%a)", 200,
              210),
             ("%fusion.2 = s32[9]{0} fusion(%a)", 210, 400),
             ("%all-reduce-done.3 = s32[9]{0} all-reduce-done(%s)", 400,
              450)]
    names = ["all-reduce-start.3", "all-reduce-done.3"]
    assert collective.merge_seconds(_trace(split), names) \
        == pytest.approx(250 / 1e9)


def test_merge_seconds_reads_nothing_without_a_merge():
    ops = [("%fusion.2 = s32[9]{0} fusion(%a)", 200, 400)]
    assert collective.merge_seconds(_trace(ops), []) is None
    assert collective.merge_seconds(_trace(ops), ["pmin.14"]) is None
    assert collective.merge_seconds({"devices": {}, "spans": []},
                                    ["pmin.14"]) is None
