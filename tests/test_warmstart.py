"""The warm starts' rounds as segment scans are bit-identical to the
per-edge gather/scatter rounds they replace.

``_reference_cheap`` and ``_reference_karp_sipser`` keep the old formulas:
every round gathers the columns' and the rows' state per edge by ``ecol``
and by ``cadj`` and scatters by both.  The built-ins must reach the same ``cmatch`` and
``rmatch`` after the same number of rounds, whichever way they are called.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import maximum_cardinality, validate_matching
from repro.core.csr import BipartiteCSR
from repro.graphs import grid_graph, kron_graph, random_bipartite, scaled_free
from repro.matching import DeviceCSR, Matcher, MatcherConfig, match_many
from repro.matching.solve import IINF, _fix_matching, scatter_min
from repro.matching.state import MatchState
from repro.matching.warmstart import (WARM_STARTS, _prefix_sum, _Segments,
                                      apply_warm_start, cheap_init,
                                      get_warm_start, register_warm_start)


def _reference_cheap(ecol, cadj, cmatch, rmatch):
    nc = cmatch.shape[0] - 1
    nr = rmatch.shape[0] - 1

    def round_fn(carry):
        cmatch, rmatch, _, rounds = carry
        col_free = cmatch[ecol] == -1
        row_free = rmatch[cadj] == -1
        cand = jnp.where(col_free & row_free, cadj, IINF)
        best_r = scatter_min(nc, ecol, cand)
        cols = jnp.arange(nc + 1, dtype=jnp.int32)
        propose = best_r < IINF
        best_c = scatter_min(nr, jnp.where(propose, best_r, nr),
                             jnp.where(propose, cols, IINF))
        won = best_c < IINF
        rows = jnp.arange(nr + 1, dtype=jnp.int32)
        rmatch = jnp.where(won, best_c, rmatch)
        cmatch = cmatch.at[jnp.where(won, best_c, nc)].set(
            jnp.where(won, rows, cmatch[nc]))
        cmatch = cmatch.at[nc].set(jnp.int32(-3))
        return cmatch, rmatch, jnp.any(won), rounds + 1

    cmatch, rmatch, _, rounds = jax.lax.while_loop(
        lambda c: c[2], round_fn,
        (cmatch, rmatch, jnp.bool_(True), jnp.int32(0)))
    return cmatch, rmatch, rounds


def _reference_karp_sipser(ecol, cadj, cmatch, rmatch):
    nc = cmatch.shape[0] - 1
    nr = rmatch.shape[0] - 1

    def degree_round(carry):
        cmatch, rmatch, _, rounds = carry
        alive = (cmatch[ecol] == -1) & (rmatch[cadj] == -1)
        one = jnp.int32(1)
        cdeg = jnp.zeros(nc + 1, jnp.int32).at[
            jnp.where(alive, ecol, nc)].add(one)
        rdeg = jnp.zeros(nr + 1, jnp.int32).at[
            jnp.where(alive, cadj, nr)].add(one)
        forced = alive & ((cdeg[ecol] == 1) | (rdeg[cadj] == 1))
        prop_r = scatter_min(nc, jnp.where(forced, ecol, nc),
                             jnp.where(forced, cadj, IINF))
        col_has = prop_r < IINF
        cols = jnp.arange(nc + 1, dtype=jnp.int32)
        prop_c = scatter_min(nr, jnp.where(col_has, prop_r, nr),
                             jnp.where(col_has, cols, IINF))
        rows = jnp.arange(nr + 1, dtype=jnp.int32)
        won_r = prop_c < IINF
        rmatch = jnp.where(won_r & (rmatch == -1), prop_c, rmatch)
        won_pair = won_r & (rmatch == prop_c)
        cmatch = cmatch.at[jnp.where(won_pair, jnp.clip(prop_c, 0, nc), nc)
                           ].max(jnp.where(won_pair, rows, jnp.int32(-1)))
        cmatch = cmatch.at[nc].set(jnp.int32(-3))
        rmatch = rmatch.at[nr].set(jnp.int32(-3))
        return cmatch, rmatch, jnp.any(forced), rounds + 1

    cmatch, rmatch, _, rounds = jax.lax.while_loop(
        lambda c: c[2], degree_round,
        (cmatch, rmatch, jnp.bool_(True), jnp.int32(0)))
    cmatch, rmatch, cheap = _reference_cheap(ecol, cadj, cmatch, rmatch)
    return _fix_matching(cmatch, rmatch) + (rounds + cheap,)


REFERENCE = {"cheap": jax.jit(_reference_cheap),
             "karp_sipser": jax.jit(_reference_karp_sipser)}


def _unsorted_with_holes():
    """Empty columns inside and at both ends, rows never touched, rows in
    no order within a column, and repeated edges (which count twice in a
    degree)."""
    rng = np.random.default_rng(11)
    nc, nr = 90, 70
    deg = rng.integers(0, 4, nc)
    deg[[0, 1, 40, 41, 42, nc - 1]] = 0
    rows = rng.integers(0, nr - 10, int(deg.sum())).astype(np.int32)
    cxadj = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    dup = cxadj[5]
    if deg[5] >= 2:
        rows[dup + 1] = rows[dup]
    return BipartiteCSR.from_csr(cxadj, rows, nc, nr)


# Every case shares one bucket (isolated padding vertices, sentinel
# padding slots), except "exact", whose edge list has no padding slot:
# there the sentinel column starts past the last slot.
BUCKET = (520, 600, 4096)
CASES = {
    "rand": random_bipartite(300, 300, 3.0, seed=1),
    "rect": random_bipartite(200, 350, 4.0, seed=2),
    "kron": kron_graph(8, 6, seed=3),
    "grid": grid_graph(14),
    "free": scaled_free(250, 250, 5.0, seed=4).permuted(1),
    "holes": _unsorted_with_holes(),
}


def _exact():
    g = random_bipartite(64, 48, 2.0, seed=7)
    return BipartiteCSR.from_csr(g.cxadj, g.cadj[: g.nnz], g.nc, g.nr,
                                 pad_to=g.nnz)


def _device(name):
    if name == "exact":
        return DeviceCSR.from_host(_exact())
    return DeviceCSR.from_host(CASES[name]).pad_vertices(
        *BUCKET[:2]).pad_to(BUCKET[2])


def _host(state):
    return [np.asarray(x) for x in (state.cmatch, state.rmatch,
                                    state.warm_rounds)]


# the exact-capacity graph has a bucket of its own, so no batch
CALLS = [(case, call) for case in sorted(CASES) + ["exact"]
         for call in ("init", "registry", "match_many")
         if (case, call) != ("exact", "match_many")]


@pytest.mark.parametrize("ws", ["cheap", "karp_sipser"])
@pytest.mark.parametrize("case,call", CALLS)
def test_rounds_equal_the_gather_scatter_reference(case, call, ws):
    graph = _device(case)
    fresh = MatchState.fresh(graph.nc, graph.nr)
    want = [np.asarray(x) for x in REFERENCE[ws](
        graph.ecol, graph.cadj, fresh.cmatch, fresh.rmatch)]
    assert want[2] >= 2        # at least one round did work
    if call == "init":
        got = _host(Matcher(warm_start=ws).init(graph))
    elif call == "registry":
        # the registry's four-argument contract (offsets derived from
        # ecol) returns the matching alone
        got = [np.asarray(x) for x in get_warm_start(ws)(
            graph.ecol, graph.cadj, fresh.cmatch, fresh.rmatch)]
        assert len(got) == 2
    else:
        names = sorted(CASES)
        batch = DeviceCSR.stack([_device(n) for n in names])
        lanes = jax.vmap(Matcher(warm_start=ws).init)(batch)
        i = names.index(case)
        got = [x[i] for x in _host(lanes)]
        # and the solve that match_many fuses behind it keeps the count
        solved = match_many(batch, MatcherConfig(), warm_start=ws)
        assert int(solved.warm_rounds[i]) == int(want[2])
        host = CASES[case]
        cm, rm = (np.asarray(x)[i] for x in (solved.cmatch, solved.rmatch))
        assert validate_matching(host, cm[: host.nc], rm[: host.nr]) \
            == maximum_cardinality(host)
    for w, g, what in zip(want, got, ("cmatch", "rmatch", "rounds")):
        np.testing.assert_array_equal(g, w, err_msg=what)


def test_warm_rounds_counts_the_rounds_the_warm_start_ran():
    graph = DeviceCSR.from_host(kron_graph(9, 8, seed=5))
    fresh = MatchState.fresh(graph.nc, graph.nr)
    for ws in ("karp_sipser", "cheap"):
        want = int(REFERENCE[ws](graph.ecol, graph.cadj, fresh.cmatch,
                                 fresh.rmatch)[2])
        m = Matcher(MatcherConfig(), warm_start=ws)
        assert int(m.init(graph).warm_rounds) == want
        assert int(m.run(graph).warm_rounds) == want
        assert int(apply_warm_start(ws, graph, fresh.cmatch,
                                    fresh.rmatch)[2]) == want
    assert int(Matcher(warm_start="none").run(graph).warm_rounds) == 0
    # a custom initializer of the four-argument contract returning two
    # arrays still runs, and counts no rounds
    register_warm_start("two_arrays", lambda e, c, cm, rm: cheap_init(
        e, c, cm, rm))
    try:
        st = Matcher(warm_start="two_arrays").init(graph)
        np.testing.assert_array_equal(
            st.cmatch, Matcher(warm_start="cheap").init(graph).cmatch)
        assert int(st.warm_rounds) == 0
    finally:
        WARM_STARTS.pop("two_arrays")
    # resuming from a given state runs no warm start and adds nothing
    warm = Matcher(warm_start="karp_sipser").init(graph)
    cold = MatchState.from_host(*(x[:-1] for x in _host(warm)[:2]))
    assert int(Matcher(warm_start="karp_sipser").run(graph, cold)
               .warm_rounds) == 0
    assert int(Matcher(warm_start="karp_sipser").solve(graph, warm)
               .warm_rounds) == int(warm.warm_rounds)


@pytest.mark.parametrize("m", [1, 127, 128, 129, 128 * 128 + 5, 1 << 17])
def test_segment_scans_equal_numpy(m):
    """The blocked scans at lengths around the block and across three
    levels of blocks: columns of every length (one longer than 128 blocks,
    empty ones between), the sentinel column over the padding last."""
    rng = np.random.default_rng(m)
    nc = 40
    deg = rng.integers(0, 2 * m // nc + 1, nc)
    deg[rng.integers(0, nc, 6)] = 0
    deg[7] = m // 2
    nnz = int(min(m, deg.sum()))
    ecol = np.repeat(np.arange(nc), deg)[:nnz]
    ecol = np.concatenate([ecol, np.full(m - nnz, nc)]).astype(np.int32)
    x = rng.integers(0, 1000, m).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(_prefix_sum(jnp.asarray(x))),
                                  np.cumsum(x))
    seg = _Segments(jnp.asarray(ecol), nc)
    cols = np.arange(nc + 1)
    want_sum = np.bincount(ecol, weights=x, minlength=nc + 1)
    want_min = np.full(nc + 1, IINF)
    np.minimum.at(want_min, ecol, x)
    np.testing.assert_array_equal(np.asarray(seg.sum(jnp.asarray(x))),
                                  want_sum)
    (got_min,) = seg.min(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got_min), want_min)
    np.testing.assert_array_equal(
        np.asarray(seg.broadcast(jnp.asarray(cols * 3 + 1))), ecol * 3 + 1)
