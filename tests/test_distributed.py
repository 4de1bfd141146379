"""ShardedMatcher (shard_map, one pmin per BFS level) on a forced 4-device
CPU host.

Each scenario runs in a subprocess because the forced device count
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) must be set before
JAX initializes, and the rest of the suite runs single-device.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = """
import jax, numpy as np
from repro.core import (MatcherConfig, maximum_cardinality, validate_matching)
from repro.graphs import grid_graph, random_bipartite, scaled_free
from repro.matching import (DeviceCSR, Matcher, ShardedMatcher,
                            compile_cache_info)

assert jax.device_count() == 4, jax.device_count()
mesh = jax.make_mesh((4,), ("data",))
cases = {
    "rand": random_bipartite(500, 500, 4.0, seed=2),
    "grid": grid_graph(18),                       # adversarial: long paths
    "rect": random_bipartite(300, 450, 3.0, seed=3),
    "free": scaled_free(400, 400, 5.0, seed=4).permuted(1),  # skewed degrees
}
"""

# ShardedMatcher == single-device Matcher.run cardinality (== optimal),
# across the generator suite, per algo / warm start.
EQUALITY = PRELUDE + """
for name, g in cases.items():
    opt = maximum_cardinality(g)
    graph = DeviceCSR.from_host(g)
    sharded_g = graph.shard(mesh, "data")
    for algo in ("apfb", "apsb"):
        cfg = MatcherConfig(algo=algo, kernel="gpubfs_wr")
        single = Matcher(cfg, warm_start="cheap").run(graph)
        st = ShardedMatcher(mesh, config=cfg, warm_start="cheap").run(sharded_g)
        cm, rm = st.to_host()
        card = validate_matching(g, cm, rm)
        assert card == opt == int(single.cardinality), \\
            (name, algo, card, opt, int(single.cardinality))
print("DIST_OK")
"""

# Repeated same-bucket sharded calls must hit the compile cache, and a second
# mesh axis name / different bucket must miss.
CACHE = PRELUDE + """
g = cases["rand"]
sharded_g = DeviceCSR.from_host(g).shard(mesh, "data")
m = ShardedMatcher(mesh, config=MatcherConfig(), warm_start="cheap")
c0 = int(m.run(sharded_g).cardinality)
info1 = compile_cache_info()
c1 = int(m.run(sharded_g).cardinality)
info2 = compile_cache_info()
assert c0 == c1
assert info2["misses"] == info1["misses"], (info1, info2)   # no recompile
assert info2["hits"] == info1["hits"] + 1, (info1, info2)
g2 = cases["grid"]                                          # other bucket
m.run(DeviceCSR.from_host(g2).shard(mesh, "data"))
info3 = compile_cache_info()
assert info3["misses"] == info2["misses"] + 1, (info2, info3)
print("DIST_OK")
"""

# The fused Pallas frontier kernel as the per-shard sweep: each shard's
# winner merge happens inside its kernel, one pmin merges the shards, and
# the result must be BIT-identical to the single-device jnp path (the
# deterministic min-merge makes every sweep path interchangeable).
PALLAS = PRELUDE + """
import dataclasses
g = cases["rand"]
opt = maximum_cardinality(g)
graph = DeviceCSR.from_host(g)
sharded_g = graph.shard(mesh, "data")
for schedule in ("ct", "mt"):
    cfg = MatcherConfig(algo="apfb", kernel="gpubfs_wr", schedule=schedule,
                        use_pallas=True)
    single = Matcher(dataclasses.replace(cfg, use_pallas=False),
                     warm_start="cheap").run(graph)
    for fused in (True, False):
        fcfg = dataclasses.replace(cfg, pallas_fused=fused)
        st = ShardedMatcher(mesh, config=fcfg, warm_start="cheap").run(sharded_g)
        cm, rm = st.to_host()
        assert validate_matching(g, cm, rm) == opt, (schedule, fused)
        np.testing.assert_array_equal(np.asarray(st.cmatch),
                                      np.asarray(single.cmatch))
        np.testing.assert_array_equal(np.asarray(st.rmatch),
                                      np.asarray(single.rmatch))
print("DIST_OK")
"""

# Direction-optimizing engine on the sharded path: each shard pulls over
# its own CSC slice (jnp stream or the Pallas pull kernel), the one pmin
# still merges, and the result must be BIT-identical to the single-device
# jnp path across algos.  Also: the mirror must be attached before shard().
DIROP = PRELUDE + """
import dataclasses
g = cases["rand"]
opt = maximum_cardinality(g)
graph = DeviceCSR.from_host(g)
sharded_g = graph.with_csc().shard(mesh, "data")
for algo in ("apfb", "apsb"):
    for use_pallas in (False, True):
        cfg = MatcherConfig(algo=algo, kernel="gpubfs_wr", dirop=True,
                            use_pallas=use_pallas)
        single = Matcher(dataclasses.replace(cfg, dirop=False,
                                             use_pallas=False),
                         warm_start="cheap").run(graph)
        st = ShardedMatcher(mesh, config=cfg, warm_start="cheap").run(sharded_g)
        cm, rm = st.to_host()
        assert validate_matching(g, cm, rm) == opt, (algo, use_pallas)
        np.testing.assert_array_equal(np.asarray(st.cmatch),
                                      np.asarray(single.cmatch))
        np.testing.assert_array_equal(np.asarray(st.rmatch),
                                      np.asarray(single.rmatch))
try:
    ShardedMatcher(mesh, config=MatcherConfig(dirop=True)).run(
        DeviceCSR.from_host(g).shard(mesh, "data"))
except ValueError as e:
    assert "with_csc" in str(e), e
else:
    raise AssertionError("missing mirror must be a typed error")
print("DIST_OK")
"""

# The numpy-compat wrapper (old core.distributed surface) and warm-state
# resume via cmatch0/rmatch0.
COMPAT = PRELUDE + """
from repro.core import cheap_matching_jax
from repro.core.distributed import maximum_matching_distributed
g = cases["rect"]
opt = maximum_cardinality(g)
cm0, rm0 = cheap_matching_jax(g)
for algo in ("apfb", "apsb"):
    cfg = MatcherConfig(algo=algo, kernel="gpubfs_wr")
    cm, rm, st = maximum_matching_distributed(g, mesh, cfg,
                                              cmatch0=cm0, rmatch0=rm0)
    assert validate_matching(g, cm, rm) == opt, (algo, st)
    assert st["devices"] == 4 and st["variant"].startswith("dist-")
print("DIST_OK")
"""

# The benchmark's four-chip deployment at a small size: Karp-Sipser on a
# uniform graph, the sharded solve bit-identical to one device in its
# matching, its phases and its BFS levels; and the compiled program merges
# the shards of each level with one all-reduce under ``merge_shards``, the
# only one in a BFS level.
MERGE = PRELUDE + """
import re
from repro.core.oracles import hopcroft_karp
from repro.matching.state import empty_like_graph
g = random_bipartite(1 << 12, 1 << 12, 8.0, seed=5)
graph = DeviceCSR.from_host(g)
sharded_g = graph.shard(mesh, "data")
single = Matcher(MatcherConfig(), warm_start="karp_sipser").run(graph)
sm = ShardedMatcher(mesh, config=MatcherConfig(), warm_start="karp_sipser")
st = sm.run(sharded_g)
np.testing.assert_array_equal(np.asarray(st.cmatch), np.asarray(single.cmatch))
np.testing.assert_array_equal(np.asarray(st.rmatch), np.asarray(single.rmatch))
assert int(st.phases) == int(single.phases), (st.phases, single.phases)
assert int(st.levels) == int(single.levels) > 0, (st.levels, single.levels)
cm, rm = st.to_host()
hk = int((hopcroft_karp(g)[0] >= 0).sum())
assert validate_matching(g, cm, rm) == hk, (hk,)
hlo = sm.program(sharded_g).lower(
    sharded_g, empty_like_graph(sharded_g)).compile().as_text()
ops = [re.search(r'op_name="([^"]*)"', l).group(1) for l in hlo.splitlines()
       if re.search(r" all-reduce(-start)?\\(", l) and "op_name=" in l]
level = [op for op in ops if "/bfs_level/" in op]
assert level and all("/bfs_level/merge_shards/" in op for op in level), ops
print("DIST_OK")
"""

# The Karp-Sipser warm start on edge-sharded arrays (GSPMD partitions its
# segment scans and its per-edge gather and scatter by row): the same
# warm-start matching and the same count of rounds as on one device, and
# ShardedMatcher carries that count into the solved state.
WARM_ROUNDS = PRELUDE + """
from repro.graphs import kron_graph
from repro.matching.state import empty_like_graph
from repro.matching.warmstart import apply_warm_start
for g in (kron_graph(10, 8, seed=6), cases["free"]):
    graph = DeviceCSR.from_host(g)
    sharded_g = graph.shard(mesh, "data")
    one = Matcher(MatcherConfig(), warm_start="karp_sipser")
    warm = one.init(graph)
    cm, rm, rounds = jax.jit(lambda g, s: apply_warm_start(
        "karp_sipser", g, s.cmatch, s.rmatch))(sharded_g,
                                               empty_like_graph(sharded_g))
    np.testing.assert_array_equal(np.asarray(cm), np.asarray(warm.cmatch))
    np.testing.assert_array_equal(np.asarray(rm), np.asarray(warm.rmatch))
    assert int(rounds) == int(warm.warm_rounds) > 2, (rounds, warm.warm_rounds)
    st = ShardedMatcher(mesh, config=MatcherConfig(),
                        warm_start="karp_sipser").run(sharded_g)
    single = one.run(graph)
    assert int(st.warm_rounds) == int(single.warm_rounds) \
        == int(warm.warm_rounds)
    np.testing.assert_array_equal(np.asarray(st.cmatch),
                                  np.asarray(single.cmatch))
    assert validate_matching(g, *st.to_host()) == maximum_cardinality(g)
print("DIST_OK")
"""

SCENARIOS = {"equality": EQUALITY, "cache": CACHE, "pallas": PALLAS,
             "dirop": DIROP, "compat": COMPAT, "merge": MERGE,
             "warm_rounds": WARM_ROUNDS}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_sharded_matcher_4dev(scenario):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{REPO}/src")
    r = subprocess.run([sys.executable, "-c", SCENARIOS[scenario]], env=env,
                       capture_output=True, text=True, timeout=580)
    assert "DIST_OK" in r.stdout, r.stderr[-3000:]
