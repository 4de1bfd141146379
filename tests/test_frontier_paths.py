"""Every frontier-sweep execution path against the one deterministic
min-merge contract: the push sweep and both pull sweeps of the
direction-optimizing engine must give the per-row winners of the pure-jnp
oracle (:mod:`repro.matching.sweep_ref`) bit for bit, plus the ALTERNATE
micro-optimizations.

Split by concern:
* the XLA sweep: its column-side gather plus row-side mask == the oracle,
  on random mid-phase states (alone and under vmap), on level-L0 states of
  graphs of several shapes and on a power-law graph one level deeper, WR
  and plain;
* the pull sweeps: the compact row-gather and the stream over the CSC
  mirror == the push winners == the pull oracle;
* CSC mirror: `DeviceCSR.with_csc` agrees with the host transpose and rides
  every shape operation (pad_to / pad_vertices / stack);
* solver-level: the push and dirop paths give bit-identical matchings
  across the paper's variant matrix, both WR encodings and three graph
  families;
* dirop: forced-pull and forced-push runs agree; the compact pull falls
  back cleanly on skewed degrees; config plumbing (mirror errors,
  hysteresis bounds) fails loudly;
* ALTERNATE: the gather-hoisted, scatter-skipping loop is a step-count-
  preserving rewrite of the straightforward body.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MatcherConfig, VARIANTS, cheap_matching_jax,
                        maximum_cardinality, maximum_matching,
                        validate_matching)
from repro.graphs import grid_graph, random_bipartite, scaled_free
from repro.matching import DeviceCSR, Matcher, SOLVE_PATHS
from repro.matching.solve import (FOUND, IINF, NEG, UNVISITED, _alternate,
                                  _expand_level, _unreached_rows,
                                  _winner_pull_compact, _winner_xla,
                                  level0_state, scatter_min)
from repro.matching.sweep_ref import (frontier_expand_fused_ref,
                                      frontier_expand_pull_ref)


def _bfs_state(g):
    """Level-L0 probe state via the solver's own ``level0_state`` init."""
    cm, rm = cheap_matching_jax(g)
    cmj = jnp.concatenate([jnp.asarray(cm), jnp.array([-3], jnp.int32)])
    rmj = jnp.concatenate([jnp.asarray(rm), jnp.array([-3], jnp.int32)])
    bfs, root = level0_state(cmj)
    return bfs, root, rmj


# ---------------------------------------------------------------------------
# the XLA sweep: the proposal predicate factored into a column and a row side
# ---------------------------------------------------------------------------
def _mid_phase_state(seed, nc=150, nr=170, nnz=1200, pad=200, level=3):
    """A random BFS state at ``level`` with every kind of column (frontier,
    visited earlier or just now, UNVISITED, FOUND, exact-WR endpoint
    encodings -(r+1)) and of row (unmatched, endpoint -2, matched to a
    column of each kind), over random edges plus padding edges (``ecol =
    nc``, ``cadj = nr``) scattered through the edge order."""
    rng = np.random.default_rng(seed)
    ecol = np.concatenate([rng.integers(0, nc, nnz), np.full(pad, nc)])
    cadj = np.concatenate([rng.integers(0, nr, nnz), np.full(pad, nr)])
    order = rng.permutation(nnz + pad)
    bfs = rng.choice([level, level, level - 1, level + 1, int(UNVISITED),
                      int(UNVISITED), int(FOUND)], nc + 1)
    enc = rng.random(nc + 1) < 0.1
    bfs[enc] = -rng.integers(1, nr + 1, int(enc.sum()))
    bfs[nc] = int(NEG)
    root = rng.integers(0, nc + 1, nc + 1)
    root[nc] = nc
    rmatch = rng.choice([-1, -2, 0], nr + 1, p=[0.3, 0.1, 0.6])
    matched = rmatch == 0
    rmatch[matched] = rng.integers(0, nc, int(matched.sum()))
    rmatch[nr] = -3
    kinds = {"frontier": bfs == level, "found": bfs == FOUND,
             "exact": bfs < 0, "unvisited": bfs == UNVISITED}
    assert all(k[:nc].any() for k in kinds.values())
    assert {-1, -2} <= set(rmatch[:nr].tolist()) and (rmatch[:nr] >= 0).any()
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    return (i32(ecol[order]), i32(cadj[order]), i32(bfs), i32(root),
            i32(rmatch), jnp.int32(level))


# level-L0 states of random graphs: (nc, nr, average degree, edge slots),
# square and rectangular, the slots padded past the edges (to a power of
# two or not)
GRAPH_SHAPES = {
    "256x256": (256, 256, 3.0, 1024),
    "500x700": (500, 700, 4.0, 3000),
    "500x700-pow2": (500, 700, 4.0, 4096),
    "300x200": (300, 200, 5.0, 2048),
    "1000x1000": (1000, 1000, 6.0, 8192),
    "64x64": (64, 64, 2.0, 128),
    "777x333": (777, 333, 5.0, 4096),
}


def _graph_state(nc, nr, deg, pad):
    g = random_bipartite(nc, nr, deg, seed=nc + nr, pad_to=pad)
    bfs, root, rmj = _bfs_state(g)
    return (jnp.asarray(g.ecol), jnp.asarray(g.cadj), bfs, root, rmj,
            jnp.int32(2))


def _deeper_state():
    """A power-law graph advanced one level by the solver's own expansion,
    so the frontier is level L0+1 and some rows are already reached."""
    g = scaled_free(512, 512, 6.0, seed=3, pad_to=8192)
    bfs, root, rmj = _bfs_state(g)
    ecol, cadj = jnp.asarray(g.ecol), jnp.asarray(g.cadj)
    pred = jnp.full(g.nr + 1, jnp.int32(g.nc))
    bfs, root, _, rmj, _, _ = _expand_level(ecol, cadj, bfs, root, pred, rmj,
                                            jnp.int32(2), wr=True,
                                            wr_exact=False)
    return ecol, cadj, bfs, root, rmj, jnp.int32(3)


def _winner_factored(ecol, cadj, bfs, root, rmatch, level):
    return _winner_xla(ecol, cadj, bfs, root, rmatch, level,
                       rmatch.shape[0] - 1)


@pytest.mark.parametrize("case", ["one", "vmap", *GRAPH_SHAPES,
                                  "free-deeper"])
@pytest.mark.parametrize("wr", [True, False], ids=["wr", "plain"])
def test_xla_sweep_factored_winners_bit_identical(wr, case):
    ref, got = frontier_expand_fused_ref, _winner_factored
    if case == "one":
        states = [_mid_phase_state(seed) for seed in range(3)]
    elif case == "vmap":
        states = [jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[_mid_phase_state(s) for s in range(b, b + 4)])
                  for b in (10, 20)]
        ref, got = jax.vmap(ref), jax.vmap(got)
    elif case == "free-deeper":
        states = [_deeper_state()]
    else:
        states = [_graph_state(*GRAPH_SHAPES[case])]
    for i, args in enumerate(states):
        if not wr:
            args = args[:3] + (None,) + args[4:]
        want = np.asarray(ref(*args))
        assert (want < IINF).any() and (want == IINF).any()
        np.testing.assert_array_equal(np.asarray(got(*args)), want,
                                      err_msg=f"state {i}")


# ---------------------------------------------------------------------------
# the pull sweeps over the CSC mirror
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nc,nr,deg,pad", [
    (256, 256, 3.0, 1024),
    (500, 700, 4.0, 3000),
    (300, 200, 5.0, 2048),
])
@pytest.mark.parametrize("sweep", ["compact", "stream"])
def test_pull_sweep_bit_identical_to_push_winners(sweep, nc, nr, deg, pad):
    """Both pull sweeps enumerate the same proposals as the push sweep, over
    the row-sorted edges; min is the merge, so their winners must equal the
    push winners bit for bit.  The compact row-gather runs at the tightest
    geometry that still gathers every unreached row in full."""
    g = random_bipartite(nc, nr, deg, seed=nc + 3 * nr, pad_to=pad)
    bfs, root, rmj = _bfs_state(g)
    ecol, cadj = jnp.asarray(g.ecol), jnp.asarray(g.cadj)
    d = DeviceCSR.from_host(g).with_csc()
    level = jnp.int32(2)
    unreached = _unreached_rows(bfs, rmj)
    rdeg = np.diff(np.asarray(d.rxadj))
    cap = int(unreached.sum())
    dmax = int(rdeg[np.asarray(unreached)].max())
    for rt in (root, None):
        push = _winner_xla(ecol, cadj, bfs, rt, rmj, level, nr)
        if sweep == "compact":
            pull = _winner_pull_compact(d.rxadj, d.radj, bfs, rt, rmj, level,
                                        nr, unreached, cap=cap, dmax=dmax)
        else:
            pull = _winner_xla(d.radj, d.erow, bfs, rt, rmj, level, nr)
        ref = frontier_expand_pull_ref(d.radj, d.erow, bfs, rt, rmj, level)
        assert (np.asarray(push) < IINF).any()
        np.testing.assert_array_equal(np.asarray(pull), np.asarray(push))
        np.testing.assert_array_equal(np.asarray(pull), np.asarray(ref))


# ---------------------------------------------------------------------------
# the CSC mirror
# ---------------------------------------------------------------------------
def test_csc_mirror_matches_host_transpose_and_threads_through_ops():
    g = random_bipartite(60, 50, 3.0, seed=5)
    t = g.transpose()
    d = DeviceCSR.from_host(g).with_csc()
    np.testing.assert_array_equal(np.asarray(d.rxadj), t.cxadj)
    np.testing.assert_array_equal(np.asarray(d.radj)[: g.nnz],
                                  t.cadj[: t.nnz])
    np.testing.assert_array_equal(np.asarray(d.erow)[: g.nnz],
                                  t.ecol[: t.nnz])
    # eperm is a true permutation mapping row-sorted slots to CSR slots
    perm = np.asarray(d.eperm)
    assert sorted(perm.tolist()) == list(range(d.nnz_pad))
    np.testing.assert_array_equal(np.asarray(d.ecol)[perm],
                                  np.asarray(d.radj))
    np.testing.assert_array_equal(np.asarray(d.cadj)[perm],
                                  np.asarray(d.erow))
    assert d.has_csc and d.bucket_key == (60, 50, d.nnz_pad, "csc")
    assert not d.drop_csc().has_csc

    # pad_to: mirror sentinels extend, eperm stays a permutation
    d2 = d.pad_to(2 * d.nnz_pad)
    perm2 = np.asarray(d2.eperm)
    assert sorted(perm2.tolist()) == list(range(d2.nnz_pad))
    np.testing.assert_array_equal(np.asarray(d2.ecol)[perm2],
                                  np.asarray(d2.radj))

    # pad_vertices: new rows are edgeless, sentinels re-encoded
    d3 = d.pad_vertices(64, 64)
    assert d3.rxadj.shape == (65,) and int(d3.rxadj[-1]) == g.nnz
    assert (np.asarray(d3.erow)[g.nnz:] == 64).all()
    np.testing.assert_array_equal(np.asarray(d3.radj)[: g.nnz],
                                  t.cadj[: t.nnz])

    # stack: mirror leaves gain the batch axis; mixing is refused
    b = DeviceCSR.stack([d, d])
    assert b.bucket_key == (2, 60, 50, d.nnz_pad, "csc")
    np.testing.assert_array_equal(np.asarray(b.unstack()[1].radj),
                                  np.asarray(d.radj))
    with pytest.raises(AssertionError, match="with_csc"):
        DeviceCSR.stack([d, d.drop_csc()])


# ---------------------------------------------------------------------------
# solver level: the full variant matrix, every sweep path
# ---------------------------------------------------------------------------
def _encoding_matrix():
    """All four variants, and for the WR kernel both endpoint encodings."""
    out = {}
    for v in VARIANTS:
        for e in ((False, True) if v.kernel == "gpubfs_wr" else (False,)):
            cfg = dataclasses.replace(v, wr_exact=e)
            out[cfg.name] = cfg
    return [out[name] for name in sorted(out)]


# the registered solve paths ARE the sweep-path list: anything added to
# repro.matching.SOLVE_PATHS is automatically held to the bit-identical
# contract here (jnp is the reference; sharded re-dispatches these configs)
PATHS = {name: dict(p.overrides)
         for name, p in SOLVE_PATHS.items()
         if not p.sharded and p.runner is None and name != "jnp"}

# uniform and long-diameter graphs, whose late BFS levels leave few
# unreached rows, so dirop pulls there, and a power-law one, whose
# high-degree rows the compact pull's degree bound turns away
FAMILIES = {
    "random_bipartite": lambda: random_bipartite(180, 170, 3.0, seed=17),
    "scaled_free": lambda: scaled_free(200, 200, 4.0, seed=5),
    "grid_graph": lambda: grid_graph(14),
}


def test_registry_covers_every_single_device_path():
    assert set(PATHS) == {"dirop"}


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("cfg", _encoding_matrix(), ids=lambda c: c.name)
def test_sweep_paths_bit_identical(cfg, family):
    g = FAMILIES[family]()
    opt = maximum_cardinality(g)
    cm0, rm0 = cheap_matching_jax(g)
    ref_cm, ref_rm, st = maximum_matching(g, cfg, cm0, rm0)
    assert validate_matching(g, ref_cm, ref_rm) == opt, st
    for pname, overrides in PATHS.items():
        pcfg = dataclasses.replace(cfg, **overrides)
        cm, rm, pst = maximum_matching(g, pcfg, cm0, rm0)
        np.testing.assert_array_equal(ref_cm, cm, err_msg=pname)
        np.testing.assert_array_equal(ref_rm, rm, err_msg=pname)


# ---------------------------------------------------------------------------
# the direction-optimizing engine
# ---------------------------------------------------------------------------
def test_dirop_forced_directions_agree():
    """Pin the heuristic to each extreme: always-pull-if-possible vs
    never-pull must still produce the reference matching bit for bit (the
    direction decision is a pure performance choice)."""
    g = random_bipartite(220, 200, 3.5, seed=29)
    cfg = MatcherConfig(algo="apfb", kernel="gpubfs_wr")
    ref_cm, ref_rm, _ = maximum_matching(g, cfg)
    for alpha, beta in ((1e6, 1e6), (1e-6, 1e-6)):
        dcfg = dataclasses.replace(cfg, dirop=True, dirop_alpha=alpha,
                                   dirop_beta=beta)
        cm, rm, _ = maximum_matching(g, dcfg)
        np.testing.assert_array_equal(ref_cm, cm, err_msg=str(alpha))
        np.testing.assert_array_equal(ref_rm, rm, err_msg=str(alpha))


def test_dirop_compact_pull_fallback_on_skewed_degrees():
    """Power-law rows exceed pull_dmax -> the compact pull is ineligible
    and the engine stays on the push sweep; results stay bit-identical."""
    g = scaled_free(300, 300, 5.0, seed=7).permuted(2)
    cfg = MatcherConfig(algo="apfb", kernel="gpubfs_wr")
    ref_cm, ref_rm, _ = maximum_matching(g, cfg)
    dcfg = dataclasses.replace(cfg, dirop=True, pull_cap=64, pull_dmax=2)
    cm, rm, _ = maximum_matching(g, dcfg)
    np.testing.assert_array_equal(ref_cm, cm)
    np.testing.assert_array_equal(ref_rm, rm)
    assert validate_matching(g, cm, rm) == maximum_cardinality(g)


def test_dirop_requires_the_csc_mirror():
    g = random_bipartite(64, 64, 2.0, seed=1)
    m = Matcher(MatcherConfig(dirop=True))
    with pytest.raises(ValueError, match="with_csc"):
        m.run(DeviceCSR.from_host(g))
    st = m.run(DeviceCSR.from_host(g).with_csc())
    assert int(st.cardinality) == maximum_cardinality(g)


def test_dirop_config_validation():
    with pytest.raises(AssertionError, match="hysteresis"):
        MatcherConfig(dirop_alpha=8.0, dirop_beta=4.0)  # beta < alpha
    # the dirop knobs are dataclass fields -> part of every cache key
    a = MatcherConfig(dirop=True)
    b = MatcherConfig(dirop=True, dirop_alpha=2.0, dirop_beta=2.0)
    assert a != b and hash(a) != hash(b)


# ---------------------------------------------------------------------------
# ALTERNATE: optimized loop == straightforward loop, step for step
# ---------------------------------------------------------------------------
def _alternate_reference(cmatch, rmatch, pred, start_mask, max_steps):
    """The pre-optimization ALTERNATE body (two pred gathers per step, both
    scatters unconditional) with the step count exposed."""
    nc = cmatch.shape[0] - 1
    nr = rmatch.shape[0] - 1
    rows = jnp.arange(nr + 1, dtype=jnp.int32)
    cur0 = jnp.where(start_mask, rows, jnp.int32(-1))

    def cond(carry):
        cur, _, _, steps = carry
        return jnp.any(cur >= 0) & (steps < max_steps)

    def body(carry):
        cur, cmatch, rmatch, steps = carry
        active = cur >= 0
        curc = jnp.clip(cur, 0, nr)
        mc = pred[curc]
        mcc = jnp.clip(mc, 0, nc)
        mr = cmatch[mcc]
        brk = active & (mr >= 0) & (pred[jnp.clip(mr, 0, nr)] == mc)
        act = active & ~brk
        cprop = scatter_min(nc, jnp.where(act, mcc, nc),
                            jnp.where(act, cur, IINF))
        cmatch = jnp.where(cprop < IINF, cprop, cmatch)
        rprop = scatter_min(nr, jnp.where(act, curc, nr),
                            jnp.where(act, mc, IINF))
        rmatch = jnp.where(rprop < IINF, rprop, rmatch)
        cur = jnp.where(act, mr, jnp.int32(-1))
        return cur, cmatch, rmatch, steps + 1

    _, cmatch, rmatch, steps = jax.lax.while_loop(
        cond, body, (cur0, cmatch, rmatch, jnp.int32(0)))
    return cmatch, rmatch, steps


@pytest.mark.parametrize("seed", range(6))
def test_alternate_optimized_is_step_count_preserving(seed):
    rng = np.random.default_rng(seed)
    nc = nr = 60
    pred = jnp.asarray(rng.integers(0, nc + 1, size=nr + 1), jnp.int32)
    cmatch = jnp.asarray(rng.integers(-1, nr, size=nc + 1), jnp.int32)
    rmatch = jnp.asarray(rng.integers(-2, nc, size=nr + 1), jnp.int32)
    start = jnp.asarray(rng.random(nr + 1) < 0.2)
    start = start.at[nr].set(False)
    max_steps = jnp.int32(12)
    ref = _alternate_reference(cmatch, rmatch, pred, start, max_steps)
    opt = _alternate(cmatch, rmatch, pred, start, max_steps)
    for a, b, what in zip(ref, opt, ("cmatch", "rmatch", "steps")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=what)
