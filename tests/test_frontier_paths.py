"""Every frontier-sweep execution path against the one deterministic
min-merge contract (ISSUE 4: the fused kernel must be bit-identical to the
scatter_min-merged proposals on all variants; ISSUE 5: so must the pull
sweeps of the direction-optimizing engine), plus the edge-tile geometry
fixes and the ALTERNATE micro-optimizations.

Split by concern:
* kernel-level: fused winners == scatter_min(legacy proposals) == fused ref
  == pull winners over the CSC-permuted edges;
* the XLA sweep: its column-side gather plus row-side mask == scatter_min of
  the per-edge proposal formula, on random mid-phase states, WR and plain,
  alone and under vmap;
* CSC mirror: `DeviceCSR.with_csc` agrees with the host transpose and rides
  every shape operation (pad_to / pad_vertices / stack);
* solver-level: jnp / Pallas-interpret / adaptive / dirop sweeps give
  bit-identical matchings across the paper's variant matrix and both WR
  encodings (compiled Pallas is refused on the chip, see
  tests/test_tpu_compile.py);
* dirop: forced-pull and forced-push runs agree; the compact pull falls
  back cleanly on skewed degrees; config plumbing (mirror errors, the
  adaptive/dirop exclusion, hysteresis bounds) fails loudly;
* geometry: `default_block_edges` no longer degenerates on prime edge
  counts, bad tiles raise a typed ValueError at trace time;
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
from repro.graphs import random_bipartite, scaled_free
from repro.kernels.frontier_expand import (frontier_expand,
                                           frontier_expand_fused,
                                           frontier_expand_fused_ref,
                                           frontier_expand_pull,
                                           frontier_expand_pull_ref,
                                           resolve_interpret)
from repro.matching import DeviceCSR, Matcher, SOLVE_PATHS
from repro.kernels.frontier_expand.frontier_expand import _proposals
from repro.matching.solve import (FOUND, IINF, NEG, UNVISITED, _alternate,
                                  _winner_full, default_block_edges,
                                  level0_state, scatter_min)

def _bfs_state(g):
    """Level-L0 probe state via the solver's own ``level0_state`` init."""
    cm, rm = cheap_matching_jax(g)
    cmj = jnp.concatenate([jnp.asarray(cm), jnp.array([-3], jnp.int32)])
    rmj = jnp.concatenate([jnp.asarray(rm), jnp.array([-3], jnp.int32)])
    bfs, root = level0_state(cmj)
    return bfs, root, rmj


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nc,nr,deg,pad,blk", [
    (256, 256, 3.0, 1024, 256),
    (500, 700, 4.0, 3000, 512),      # pad not a multiple of the tile
    (300, 200, 5.0, 2048, 999),      # tile not a divisor of anything nice
    (64, 64, 2.0, 128, 4096),        # tile bigger than the edge array
])
def test_fused_kernel_bit_identical_to_scatter_min(nc, nr, deg, pad, blk):
    g = random_bipartite(nc, nr, deg, seed=nc + nr, pad_to=pad)
    bfs, root, rmj = _bfs_state(g)
    ecol, cadj = jnp.asarray(g.ecol), jnp.asarray(g.cadj)
    for rt in (root, None):
        prop = frontier_expand(ecol, cadj, bfs, rt, rmj, 2, block_edges=blk)
        merged = scatter_min(nr, jnp.where(prop < IINF, cadj, nr), prop)
        fused = frontier_expand_fused(ecol, cadj, bfs, rt, rmj, 2,
                                      block_edges=blk)
        ref = frontier_expand_fused_ref(ecol, cadj, bfs, rt, rmj,
                                        jnp.int32(2))
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(merged))
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))


@pytest.mark.parametrize("nc,nr,deg,pad,blk", [
    (256, 256, 3.0, 1024, 256),
    (500, 700, 4.0, 3000, 512),      # pad not a multiple of the tile
    (300, 200, 5.0, 2048, 999),      # tile not a divisor of anything nice
])
def test_pull_kernel_bit_identical_to_push_winners(nc, nr, deg, pad, blk):
    """The pull kernel streams the CSC-permuted edges; min is the merge, so
    its winners must equal the fused/push winners bit for bit."""
    g = random_bipartite(nc, nr, deg, seed=nc + 3 * nr, pad_to=pad)
    bfs, root, rmj = _bfs_state(g)
    ecol, cadj = jnp.asarray(g.ecol), jnp.asarray(g.cadj)
    d = DeviceCSR.from_host(g).with_csc()
    for rt in (root, None):
        push = frontier_expand_fused(ecol, cadj, bfs, rt, rmj, 2,
                                     block_edges=blk)
        pull = frontier_expand_pull(d.radj, d.erow, bfs, rt, rmj, 2,
                                    block_edges=blk)
        ref = frontier_expand_pull_ref(d.radj, d.erow, bfs, rt, rmj,
                                       jnp.int32(2))
        np.testing.assert_array_equal(np.asarray(pull), np.asarray(push))
        np.testing.assert_array_equal(np.asarray(pull), np.asarray(ref))


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


def _winner_per_edge(ecol, cadj, bfs, root, rmatch, level):
    """The per-edge formula the Pallas kernels tile, merged by scatter_min."""
    nr = rmatch.shape[0] - 1
    t = _proposals(level, ecol, cadj, bfs, root, rmatch)
    return scatter_min(nr, jnp.where(t, cadj, nr), jnp.where(t, ecol, IINF))


def _winner_factored(ecol, cadj, bfs, root, rmatch, level):
    return _winner_full(ecol, cadj, bfs, root, rmatch, level,
                        rmatch.shape[0] - 1, use_pallas=False,
                        pallas_fused=False, block_edges=128, interpret=None)


@pytest.mark.parametrize("batch", [0, 4], ids=["one", "vmap"])
@pytest.mark.parametrize("wr", [True, False], ids=["wr", "plain"])
def test_xla_sweep_factored_winners_bit_identical(wr, batch):
    seeds = range(3) if batch == 0 else [list(range(s, s + batch))
                                         for s in (10, 20)]
    for seed in seeds:
        if batch == 0:
            args = _mid_phase_state(seed)
            ref, got = _winner_per_edge, _winner_factored
        else:
            args = jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[_mid_phase_state(s) for s in seed])
            ref, got = jax.vmap(_winner_per_edge), jax.vmap(_winner_factored)
        if not wr:
            args = args[:3] + (None,) + args[4:]
        want = np.asarray(ref(*args))
        assert (want < IINF).any() and (want == IINF).any()
        np.testing.assert_array_equal(np.asarray(got(*args)), want,
                                      err_msg=f"seed {seed}")


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
# geometry
# ---------------------------------------------------------------------------
def test_default_block_edges_never_degenerate():
    """The old gcd collapsed to 1-lane tiles on prime edge counts; the tile
    is now clamped-desired with a 128-lane floor (padding absorbs the rest).
    """
    for nnz in (997, 1, 130, 2048, 4096, 65536, 99991):
        for schedule in ("ct", "mt"):
            blk = default_block_edges(nnz, schedule)
            assert blk >= 128, (nnz, schedule, blk)
            assert blk % 128 == 0, (nnz, schedule, blk)
    assert default_block_edges(65536, "ct") == 4096    # CT coarse tiles
    assert default_block_edges(65536, "mt") == 512     # MT fine tiles
    assert default_block_edges(997, "ct") == 1024      # clamped to the pad
    assert default_block_edges(64, "mt") == 128        # floor


def test_bad_block_edges_raises_typed_error():
    g = random_bipartite(64, 64, 2.0, seed=0, pad_to=256)
    bfs, root, rmj = _bfs_state(g)
    ecol, cadj = jnp.asarray(g.ecol), jnp.asarray(g.cadj)
    for entry in (frontier_expand, frontier_expand_fused,
                  frontier_expand_pull):
        with pytest.raises(ValueError, match=r"block_edges=0 for nnz=256"):
            entry(ecol, cadj, bfs, root, rmj, 2, block_edges=0)
        with pytest.raises(ValueError, match="block_edges"):
            entry(ecol, cadj, bfs, root, rmj, 2, block_edges=-4)


# ---------------------------------------------------------------------------
# solver level: the full variant matrix, every sweep path
# ---------------------------------------------------------------------------
def _encoding_matrix():
    """All eight variants, and for the WR kernel both endpoint encodings."""
    out = {}
    for v in VARIANTS:
        encs = (False, True) if v.kernel == "gpubfs_wr" else (False,)
        for e in encs:
            cfg = dataclasses.replace(v, wr_exact=e)
            out[cfg.name + ("-exact" if e and not v.wr_exact else "")] = cfg
    return sorted(out.values(), key=lambda c: (c.name, c.wr_exact))


# the registered solve paths ARE the sweep-path list: anything added to
# repro.matching.SOLVE_PATHS is automatically held to the bit-identical
# contract here (jnp is the reference; sharded re-dispatches these configs)
PATHS = {name: dict(p.overrides)
         for name, p in SOLVE_PATHS.items()
         if not p.sharded and p.runner is None and name != "jnp"}


def test_registry_covers_every_single_device_path():
    assert set(PATHS) == {"legacy", "fused", "adaptive", "dirop",
                          "dirop_pallas"}


@pytest.mark.parametrize("cfg", _encoding_matrix(), ids=lambda c:
                         f"{c.name}{'-exact' if c.wr_exact else ''}")
def test_sweep_paths_bit_identical(cfg):
    g = random_bipartite(180, 170, 3.0, seed=17)
    opt = maximum_cardinality(g)
    cm0, rm0 = cheap_matching_jax(g)
    ref_cm, ref_rm, st = maximum_matching(g, cfg, cm0, rm0)
    assert validate_matching(g, ref_cm, ref_rm) == opt, st
    for pname, overrides in PATHS.items():
        pcfg = dataclasses.replace(cfg, **overrides)
        cm, rm, pst = maximum_matching(g, pcfg, cm0, rm0)
        np.testing.assert_array_equal(ref_cm, cm, err_msg=pname)
        np.testing.assert_array_equal(ref_rm, rm, err_msg=pname)


def test_adaptive_runtime_fallback_on_skewed_degrees():
    """Power-law columns exceed dmax -> runtime falls back to the dense
    sweep; the result must stay bit-identical and maximum."""
    g = scaled_free(300, 300, 5.0, seed=3)
    cfg = MatcherConfig(algo="apfb", kernel="gpubfs_wr")
    ref_cm, ref_rm, _ = maximum_matching(g, cfg)
    acfg = dataclasses.replace(cfg, adaptive_frontier=True,
                               compact_cap=64, compact_dmax=2)
    cm, rm, _ = maximum_matching(g, acfg)
    np.testing.assert_array_equal(ref_cm, cm)
    np.testing.assert_array_equal(ref_rm, rm)
    assert validate_matching(g, cm, rm) == maximum_cardinality(g)


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
    with pytest.raises(ValueError, match="generalizes"):
        MatcherConfig(dirop=True, adaptive_frontier=True)
    with pytest.raises(AssertionError, match="hysteresis"):
        MatcherConfig(dirop_alpha=8.0, dirop_beta=4.0)  # beta < alpha
    # the dirop knobs are dataclass fields -> part of every cache key
    a = MatcherConfig(dirop=True)
    b = MatcherConfig(dirop=True, dirop_alpha=2.0, dirop_beta=2.0)
    assert a != b and hash(a) != hash(b)


# ---------------------------------------------------------------------------
# config / cache plumbing
# ---------------------------------------------------------------------------
def test_interpret_resolution_in_cache_key():
    from repro.matching import Matcher
    auto = Matcher(MatcherConfig(use_pallas=True))
    assert auto.config.pallas_interpret == (jax.default_backend() == "cpu")
    assert resolve_interpret(None) == auto.config.pallas_interpret
    pinned = Matcher(MatcherConfig(use_pallas=True, pallas_interpret=True))
    assert pinned.config.pallas_interpret is True
    # the resolved bool (not the None marker) is what lands in cache keys
    assert auto.config == MatcherConfig(
        use_pallas=True, pallas_interpret=auto.config.pallas_interpret)


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
