"""The solver's BFS level counter and the service's spans and counters.

``MatchState.levels`` is checked against the instrumented Python
re-execution of the phase loop (``benchmarks/fig2_bfs_iters``) on a first
phase, against ``phases`` on full solves, lane by lane between ``run_many``
and ``run``, and between ``ShardedMatcher`` and ``Matcher``.  The service's
stage counters are checked after a handful of requests, and its
``repro.serve.*`` spans in a profiler trace of the same.
"""
import dataclasses
import glob
import os
import subprocess
import sys
import time

import jax
import pytest

from benchmarks.fig2_bfs_iters import instrumented_phases
from repro.graphs import grid_graph, random_bipartite
from repro.matching import DeviceCSR, Matcher, MatcherConfig
from repro.serving import Bucketizer, MatchingService, SizeBucket

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GRAPHS = {
    "grid": lambda: grid_graph(12),                  # long paths, many levels
    "random": lambda: random_bipartite(200, 200, 3.0, seed=2),
}
BUCKET = SizeBucket(256, 256, 2048)


def _padded(g):
    return DeviceCSR.from_host(g).pad_vertices(BUCKET.nc, BUCKET.nr).pad_to(
        BUCKET.nnz_pad)


@pytest.mark.parametrize("algo", ["apfb", "apsb"])
@pytest.mark.parametrize("family", sorted(GRAPHS))
def test_levels_count_the_bfs_levels(family, algo):
    g = GRAPHS[family]()
    graph = DeviceCSR.from_host(g)
    cfg = MatcherConfig(algo=algo)

    # one phase: the instrumented re-execution's first phase, level by level
    first = Matcher(dataclasses.replace(cfg, max_phases=1), "cheap")
    one = first.run(graph)
    stats = first.stats(one).as_dict()
    assert stats["phases"] == 1
    assert stats["levels"] == instrumented_phases(g, algo, max_phases=1)[0]

    # a full solve expands at least one level per phase
    full = Matcher(cfg, "cheap")
    st = full.run(graph)
    assert 1 <= int(st.phases) <= int(st.levels)

    # each lane of run_many counts only its own levels
    lanes = [g] + [random_bipartite(180, 190, 2.5, seed=s) for s in (5, 6)]
    batch = full.run_many(DeviceCSR.stack([_padded(h) for h in lanes]))
    many = full.stats(batch).as_dict()["levels"]
    alone = [int(full.run(_padded(h)).levels) for h in lanes]
    assert list(many) == alone


def test_fresh_and_warm_started_states_count_no_levels():
    graph = DeviceCSR.from_host(GRAPHS["random"]())
    m = Matcher(MatcherConfig(), "karp_sipser")
    assert int(m.init(graph).levels) == 0
    assert m.stats(m.init(graph)).as_dict()["levels"] == 0


def test_a_state_built_without_levels_counts_none():
    m = Matcher(MatcherConfig(), "cheap")
    graphs = [_padded(random_bipartite(150, 160, 3.0, seed=s)) for s in (1, 2)]
    out = m.run_many(DeviceCSR.stack(graphs))
    rebuilt = type(out)(cmatch=out.cmatch, rmatch=out.rmatch,
                        phases=out.phases, fallbacks=out.fallbacks,
                        certified=out.certified)
    assert rebuilt.levels.shape == out.phases.shape == (2,)
    assert not rebuilt.levels.any()
    # a tree of None leaves (a tree.map to None) unflattens unchanged
    assert jax.tree.map(lambda x: None, out).levels is None


SHARDED = """
import jax
from repro.graphs import grid_graph
from repro.matching import DeviceCSR, Matcher, MatcherConfig, ShardedMatcher
assert jax.device_count() == 4
mesh = jax.make_mesh((4,), ("data",))
graph = DeviceCSR.from_host(grid_graph(14))
single = Matcher(MatcherConfig(), "cheap").run(graph)
st = ShardedMatcher(mesh, config=MatcherConfig(), warm_start="cheap").run(
    graph.shard(mesh, "data"))
assert int(st.phases) == int(single.phases)
assert int(st.levels) == int(single.levels) > int(st.phases), (
    int(st.levels), int(single.levels))
print("LEVELS_OK")
"""


def test_sharded_levels_are_the_replicated_loop_count():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "LEVELS_OK" in r.stdout, r.stderr[-3000:]


STAGES = ("admit", "wait", "flush", "stack", "solve", "resolve")


def _serve(n_requests=6):
    """A warmed service given ``n_requests`` graphs at once, drained; the
    service is returned closed, with its counters."""
    svc = MatchingService(bucketizer=Bucketizer((BUCKET,)), max_batch=4,
                          max_delay_ms=1.0)
    try:
        svc.warm_up()
        graphs = [random_bipartite(150 + 10 * i, 160, 3.0, seed=i)
                  for i in range(n_requests)] + [grid_graph(12)]
        futures = [svc.submit(g) for g in graphs]
        svc.drain()
        results = [f.result(timeout=120) for f in futures]
    finally:
        svc.close()
    return svc, results


def test_service_stage_counters():
    svc, results = _serve()
    snap = svc.metrics.snapshot()
    assert snap["completed"] == len(results)
    for key in ("admit_s", "wait_s", "stack_s", "batch_solve_s",
                "resolve_s"):
        assert snap[key] > 0, key
    assert "flush_s" not in snap
    batched = (snap["flushes_full"] + snap["flushes_deadline"]
               + snap["flushes_drain"])
    assert snap["batch_flushes"] == batched == snap["dispatches"] >= 2
    # every real lane's levels, and no more than the lock-step slots
    assert snap["lane_levels"] == sum(int(r.stats.levels) for r in results)
    assert 0 < snap["lane_levels"] <= snap["lane_level_slots"]
    assert "queue_wait_p99_ms" not in snap


def test_open_stage_counts_up_to_the_snapshot():
    # an idle service's flush thread sits in one open wait: the difference
    # of two snapshots is the time between them, with no flush to close it
    svc = MatchingService(bucketizer=Bucketizer((BUCKET,)))
    try:
        a, t0 = svc.metrics.snapshot(), time.perf_counter()
        time.sleep(0.2)
        b, t1 = svc.metrics.snapshot(), time.perf_counter()
    finally:
        svc.close()
    assert 0.9 * (t1 - t0) <= b["wait_s"] - a["wait_s"] <= t1 - t0 + 0.05
    assert b["stack_s"] == b["batch_solve_s"] == b["resolve_s"] == 0


def _host_spans(trace_dir):
    """``[(line, name, start_ns, end_ns, stats)]`` of the ``repro.`` spans
    on the host plane of the trace in ``trace_dir``."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend((i, e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for e in line.events if e.name.startswith("repro."))
    return out


def test_service_spans_in_a_profiler_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        svc, results = _serve()
    spans = _host_spans(str(tmp_path))
    names = {n for _, n, *_ in spans}
    assert {f"repro.serve.{s}" for s in STAGES} <= names
    admits = [st["seq"] for _, n, *_, st in spans if n == "repro.serve.admit"]
    assert sorted(admits) == list(range(len(results)))
    flushes = [(ln, s, e, st) for ln, n, s, e, st in spans
               if n == "repro.serve.flush"]
    assert len(flushes) == svc.metrics.snapshot()["batch_flushes"]
    assert all(st["first"] <= st["last"] for *_, st in flushes)
    for ln, n, s, e, _ in spans:
        if n in ("repro.serve.stack", "repro.serve.solve",
                 "repro.serve.resolve"):
            # nested in one flush, on the flush thread's line
            assert any(fl == ln and fs <= s and e <= fe
                       for fl, fs, fe, _ in flushes), n
    wait_lines = {ln for ln, n, *_ in spans if n == "repro.serve.wait"}
    assert wait_lines == {ln for ln, *_ in flushes}
