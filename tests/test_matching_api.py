"""Device-resident repro.matching API: pytree graphs, Matcher, match_many."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (maximum_cardinality, maximum_matching,
                        validate_matching)
from repro.graphs import random_bipartite
from repro.matching import (DeviceCSR, Matcher, MatcherConfig, MatchState,
                            compile_cache_info, match_many,
                            register_warm_start, warm_start_names)
from repro.matching.device_csr import bucket_nnz
from repro.matching.state import empty_like_graph


@pytest.fixture(scope="module")
def g():
    return random_bipartite(200, 180, 3.0, seed=3)


@pytest.fixture(scope="module")
def graph(g):
    return DeviceCSR.from_host(g)


# ---------------------------------------------------------------------------
# DeviceCSR pytree behaviour
# ---------------------------------------------------------------------------
def test_device_csr_flatten_roundtrip(g, graph):
    leaves, treedef = jax.tree.flatten(graph)
    assert all(isinstance(x, jax.Array) for x in leaves)
    back = jax.tree.unflatten(treedef, leaves)
    assert (back.nc, back.nr) == (graph.nc, graph.nr)
    np.testing.assert_array_equal(np.asarray(back.cadj),
                                  np.asarray(graph.cadj))
    host = back.to_host()
    assert host.nnz == g.nnz
    np.testing.assert_array_equal(host.cxadj, g.cxadj)


def test_device_csr_jit_passthrough(graph):
    """A DeviceCSR crosses a jit boundary as a pytree, no host transfer."""
    @jax.jit
    def edge_degree_sum(gr: DeviceCSR):
        return jnp.sum((gr.ecol < gr.nc).astype(jnp.int32))

    assert int(edge_degree_sum(graph)) == int(graph.nnz)


def test_device_csr_pad_and_bucket(g):
    graph = DeviceCSR.from_host(g)
    grown = graph.pad_to(graph.nnz_pad + 256)
    assert grown.nnz_pad == graph.nnz_pad + 256
    assert int(grown.nnz) == g.nnz
    # sentinel padding is inert: same matching as the original bucket
    st_a = Matcher(MatcherConfig()).run(graph)
    st_b = Matcher(MatcherConfig()).run(grown)
    assert int(st_a.cardinality) == int(st_b.cardinality)
    assert bucket_nnz(200) == 256
    assert bucket_nnz(1) == 128
    assert grown.bucketed().nnz_pad == bucket_nnz(grown.nnz_pad)


def test_match_state_roundtrip(g):
    cm = np.full(g.nc, -1, np.int32)
    rm = np.full(g.nr, -1, np.int32)
    cm[3], rm[7] = 7, 3
    st = MatchState.from_host(cm, rm)
    assert int(st.cardinality) == 1
    cm2, rm2 = st.to_host()
    np.testing.assert_array_equal(cm, cm2)
    np.testing.assert_array_equal(rm, rm2)


# ---------------------------------------------------------------------------
# Matcher facade: warm starts, jit closure, zero host hops
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("ws", ["none", "cheap", "karp_sipser"])
def test_warm_start_registry_parity(g, graph, ws):
    """Every registered warm start composes with the solver to the same
    (maximum) cardinality."""
    st = Matcher(MatcherConfig(), warm_start=ws).run(graph)
    cm, rm = st.to_host()
    assert validate_matching(g, cm, rm) == maximum_cardinality(g)


def test_run_composes_under_jit_end_to_end(g, graph):
    """Acceptance: warm-start init + solve trace into ONE jitted program —
    any host transfer between them would raise a ConcretizationTypeError
    under this outer jax.jit."""
    matcher = Matcher(MatcherConfig(), warm_start="karp_sipser")
    fused = jax.jit(matcher.run)
    st = fused(graph)
    assert isinstance(st.cardinality, jax.Array)   # stats stay on device
    assert int(st.cardinality) == maximum_cardinality(g)
    cm, rm = st.to_host()
    validate_matching(g, cm, rm)


def test_resume_from_state_skips_warm_start(g, graph):
    warm = Matcher(MatcherConfig(), warm_start="cheap").init(graph)
    st = Matcher(MatcherConfig()).run(graph, warm)
    assert int(st.cardinality) == maximum_cardinality(g)


def test_custom_warm_start_registration(g, graph):
    def reversed_greedy(ecol, cadj, cmatch, rmatch):
        return cmatch, rmatch                      # intentionally lazy

    register_warm_start("noop", reversed_greedy)
    assert "noop" in warm_start_names()
    st = Matcher(MatcherConfig(), warm_start="noop").run(graph)
    assert int(st.cardinality) == maximum_cardinality(g)
    with pytest.raises(KeyError):
        Matcher(MatcherConfig(), warm_start="not-a-warm-start")


def test_compile_cache_reuse(graph):
    before = compile_cache_info()
    m = Matcher(MatcherConfig(algo="apsb"), warm_start="cheap")
    m.run(graph)
    mid = compile_cache_info()
    m.run(graph)                                   # same bucket: cache hit
    after = compile_cache_info()
    assert mid["misses"] == before["misses"] + 1
    assert after["misses"] == mid["misses"]
    assert after["hits"] == mid["hits"] + 1


# ---------------------------------------------------------------------------
# match_many — batched serving path
# ---------------------------------------------------------------------------
def test_match_many_agrees_with_looped_maximum_matching():
    """Acceptance: identical cardinalities to looped maximum_matching on an
    8-graph batch."""
    gs = [random_bipartite(128, 128, 3.0, seed=s, pad_to=512)
          for s in range(8)]
    batch = DeviceCSR.stack([DeviceCSR.from_host(x) for x in gs])
    assert batch.batch_shape == (8,)
    out = match_many(batch, MatcherConfig(), warm_start="cheap")
    got = np.asarray(out.cardinality).tolist()
    want = [maximum_matching(x, MatcherConfig())[2]["cardinality"]
            for x in gs]
    assert got == want
    # each batched matching is itself valid
    for i, x in enumerate(gs):
        validate_matching(x, np.asarray(out.cmatch[i])[:-1],
                          np.asarray(out.rmatch[i])[:-1])


def test_match_many_mixed_nnz_same_bucket():
    """Graphs with different true nnz share a bucket via sentinel padding."""
    gs = [random_bipartite(96, 96, d, seed=s)
          for s, d in enumerate((2.0, 5.0, 8.0))]
    batch = DeviceCSR.stack([DeviceCSR.from_host(x) for x in gs])
    out = match_many(batch, warm_start="karp_sipser")
    for i, x in enumerate(gs):
        card = validate_matching(x, np.asarray(out.cmatch[i])[:-1],
                                 np.asarray(out.rmatch[i])[:-1])
        assert card == maximum_cardinality(x)


def test_stacked_state_shapes(graph):
    batch = DeviceCSR.stack([graph, graph])
    st = empty_like_graph(batch)
    assert st.cmatch.shape == (2, graph.nc + 1)
    assert st.phases.shape == (2,)


# ---------------------------------------------------------------------------
# bring-up guards: meshes, the persistent cache, the checker
# ---------------------------------------------------------------------------
def test_sharded_matcher_accepts_the_default_make_mesh(g):
    """jax.make_mesh types its axes Explicit; the matcher works on an Auto
    view of it, and a graph placed on the caller's mesh is re-placed."""
    import dataclasses

    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.matching import ShardedMatcher
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    assert set(mesh.axis_types) == {AxisType.Explicit}
    graph = DeviceCSR.from_host(g).shard(mesh, "data")
    edges = NamedSharding(mesh, P("data"))
    placed = dataclasses.replace(graph,
                                 ecol=jax.device_put(graph.ecol, edges),
                                 cadj=jax.device_put(graph.cadj, edges))
    assert placed.ecol.sharding.mesh.axis_types == mesh.axis_types
    assert set(placed.shard(mesh, "data").ecol.sharding.mesh.axis_types) \
        == {AxisType.Auto}
    state = ShardedMatcher(mesh, warm_start="cheap").run(placed)
    assert validate_matching(g, *state.to_host()) == maximum_cardinality(g)


def test_persistent_compile_cache_location(monkeypatch, tmp_path):
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from repro.matching import enable_persistent_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_persistent_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads it
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        path = enable_persistent_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def _validate_matching_loop(g, cmatch, rmatch):
    """The straightforward per-vertex checker ``validate_matching`` replaced
    with array operations: the reference it must agree with."""
    cmatch, rmatch = np.asarray(cmatch)[: g.nc], np.asarray(rmatch)[: g.nr]
    edges = set(zip(g.ecol[: g.nnz].tolist(), g.cadj[: g.nnz].tolist()))
    card = 0
    for c in range(g.nc):
        r = int(cmatch[c])
        if r == -1:
            continue
        assert 0 <= r < g.nr, f"cmatch[{c}]={r} out of range"
        assert int(rmatch[r]) == c, f"asymmetric match c={c} r={r}"
        assert (c, r) in edges, f"matched non-edge ({c},{r})"
        card += 1
    for r in range(g.nr):
        c = int(rmatch[r])
        if c != -1:
            assert 0 <= c < g.nc and int(cmatch[c]) == r, \
                f"asymmetric match r={r} c={c}"
    return card


@pytest.mark.parametrize("corrupt,message", [
    ("row_out_of_range", "out of range"),
    ("asymmetric", "asymmetric match c="),
    ("non_edge", "matched non-edge"),
    ("row_side_only", "asymmetric match r="),
])
def test_validate_matching_rejects_each_violation(corrupt, message):
    from repro.core.oracles import hopcroft_karp
    g = random_bipartite(120, 110, 2.0, seed=11)
    cm, rm = hopcroft_karp(g)
    assert validate_matching(g, cm, rm) == maximum_cardinality(g) \
        == _validate_matching_loop(g, cm, rm)
    cm, rm = cm.copy(), rm.copy()
    matched = np.flatnonzero(cm >= 0)
    free_c, free_r = np.flatnonzero(cm < 0), np.flatnonzero(rm < 0)
    edges = set(zip(g.ecol[: g.nnz].tolist(), g.cadj[: g.nnz].tolist()))
    if corrupt == "row_out_of_range":
        cm[matched[0]] = g.nr + 3
    elif corrupt == "asymmetric":
        cm[matched[0]] = cm[matched[1]]
    elif corrupt == "non_edge":
        c, r = next((int(c), int(r)) for c in free_c for r in free_r
                    if (c, r) not in edges)
        cm[c], rm[r] = r, c
    else:
        rm[free_r[0]] = matched[0]
    for check in (validate_matching, _validate_matching_loop):
        with pytest.raises(AssertionError, match=message):
            check(g, cm, rm)


@pytest.mark.parametrize("seed", range(4))
def test_validate_matching_agrees_with_the_loop_reference(seed):
    """Cheap (maximal, not maximum) matchings on rectangular graphs, with
    free vertices on both sides: the same cardinality from both checkers."""
    from repro.core.oracles import cheap_matching
    g = random_bipartite(150 + 10 * seed, 130, 1.5 + seed, seed=seed)
    cm, rm = cheap_matching(g)
    assert validate_matching(g, cm, rm) == _validate_matching_loop(g, cm, rm)
