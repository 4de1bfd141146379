"""Pure JAX solver for the paper's GPU matching algorithms (APFB / APsB).

Mapping from the paper's CUDA kernels to TPU-friendly vector ops
----------------------------------------------------------------
The paper launches one CUDA thread per column (MT) or a constant thread grid
(CT), each walking its CSR adjacency with benign write races.  Here a BFS
level is a single *edge-parallel* vector operation over all ``nnz`` edges,
so the paper's MT/CT thread-grid choice has no counterpart:

* the per-thread race "first writer wins" becomes a deterministic
  ``min``-merge (lowest proposing column wins) — same semantics class the
  paper relies on, but reproducible.  The push sweep is the XLA sweep
  :func:`_winner_xla`: the column side of the proposal predicate gathered
  once per edge, the scatter-min by row, then the row side applied to the
  O(nr) winner vector;
* beyond-paper, ``dirop`` is the direction-optimizing engine: each level a
  Beamer-style heuristic compares the frontier's outgoing-edge count
  against the unreached rows' incoming-edge count (both O(n) degree sums
  off ``cxadj``/``rxadj``) and ``lax.cond``-dispatches either the push
  sweep or a *pull* sweep over the CSC mirror — a compact row-gather
  (O(cap·dmax)) on one device, the XLA sweep over the row-sorted edge
  shard under ``shard_map``.  The proposal predicate factors into a
  column side and a row side, so pull and push enumerate the same
  proposals and the min-merge winner is bit-identical whichever direction
  ran — the heuristic is a pure performance decision;
* ``ALTERNATE`` (Alg. 3) walks all augmenting paths in lock-step inside a
  ``lax.while_loop``; the paper's line-8 predecessor check is a vector mask;
* ``FIXMATCHING`` is the paper's repair pass, applied in both directions so
  every phase ends with a *valid* (possibly sub-maximal) matching;
* a cardinality guard re-runs ``ALTERNATE`` with a single walker on the
  phase-start snapshot if the speculative phase failed to gain — this bounds
  the outer loop by ``nc`` phases (engineering safeguard; the speculative
  phase almost always gains, see benchmarks).

State layout (all int32, one sentinel slot at the end of every array):
``bfs``  (nc+1,)  BFS level per column; L0-1==1 means unvisited, L0==2 roots.
``root`` (nc+1,)  root column of the BFS tree (GPUBFS-WR only).
``pred`` (nr+1,)  predecessor column of a row in the BFS forest.
``cmatch`` (nc+1,) / ``rmatch`` (nr+1,) the matching; -1 unmatched,
rmatch==-2 flags an augmenting-path endpoint (paper's convention).

Everything here is a *pure function of its array arguments*: the problem
sizes are derived from the (static) array shapes at trace time, so the same
function composes under ``jax.jit``, ``jax.vmap`` (via :func:`make_solver`)
and the warm-start registry with zero host transfers.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .config import MatcherConfig

L0 = jnp.int32(2)            # paper's suggested start level (keeps bfs positive)
UNVISITED = jnp.int32(1)     # L0 - 1
FOUND = jnp.int32(0)         # L0 - 2 : root's augmenting path already found (WR)
NEG = jnp.int32(-(2**30))    # sentinel level: never active, never unvisited
IINF = jnp.int32(2**30)      # scatter-min identity


def scatter_min(n: int, index, values):
    """Deterministic "first writer wins": per-slot min over proposals.

    ``index`` may use slot ``n`` as the discard sentinel; the sentinel slot is
    reset to the identity so it never reads back as a winner.
    """
    out = jnp.full(n + 1, IINF, jnp.int32).at[index].min(values)
    return out.at[n].set(IINF)


def level0_state(cmatch):
    """BFS state at the paper's start level for a given matching: ``bfs``
    (unmatched columns are L0 roots, matched UNVISITED, sentinel NEG) and
    ``root`` (own index if root).  The exact init ``phase_bfs`` performs —
    shared with the sweep benches and tests so their probe states cannot drift
    from what the solver actually sweeps.
    """
    nc = cmatch.shape[0] - 1
    cols = jnp.arange(nc + 1, dtype=jnp.int32)
    bfs = jnp.where(cmatch >= 0, UNVISITED, L0).at[nc].set(NEG)
    root = jnp.where(cmatch >= 0, jnp.int32(nc), cols)
    return bfs, root


# ---------------------------------------------------------------------------
# BFS level expansion — the paper's Algorithms 2 (GPUBFS) and 4 (GPUBFS-WR)
# ---------------------------------------------------------------------------
def _winner_xla(ecol, cadj, bfs, root, rmatch, level, nr):
    """The XLA sweep: the proposal predicate factored into its two sides.

    An edge (c, r) proposes iff ``_active_cols[c] & _unreached_rows[r]``,
    and a row's winner is the min over its proposing edges, so the row side
    can wait for the winner vector.  Per edge this is ONE gather of the
    column side's value (the column, or IINF), then the scatter-min by row;
    both sides are O(n) work.  Bit-identical to scatter-merging the per-edge
    proposal formula (:mod:`repro.matching.sweep_ref`).  ``ecol``/``cadj`` may
    be any edge order (the CSC mirror's ``radj``/``erow`` too); padding
    edges (``ecol = nc``, ``cadj = nr``) read the sentinel column, which
    never proposes, into the discard slot.
    """
    cols = jnp.arange(bfs.shape[0], dtype=jnp.int32)
    colval = jnp.where(_active_cols(bfs, root, level), cols, IINF)
    prop = colval[ecol]                                  # the one edge gather
    # IINF is the min identity, so a non-proposing edge scatters it to its
    # own row; routing all of them to the discard slot measured slower on
    # a TPU v5e (the scatter took 20-25% longer)
    winner = scatter_min(nr, cadj, prop)
    rowok = jnp.append(_unreached_rows(bfs, rmatch), False)
    return jnp.where(rowok, winner, IINF)


def _active_cols(bfs, root, level):
    """The (nc+1,) column side of the proposal predicate: columns on the
    frontier at ``level`` whose BFS tree has not found its augmenting path
    yet (the GPUBFS-WR early exit, when ``root`` is given).  False at the
    sentinel slot, whose level is NEG."""
    nc = bfs.shape[0] - 1
    ok = bfs == level
    if root is not None:
        ok &= bfs[jnp.clip(root, 0, nc)] >= UNVISITED
    return ok


def _unreached_rows(bfs, rmatch):
    """The (nr,) mask of rows still reachable this phase — the row side of
    the proposal predicate: unmatched-and-not-yet-endpoint rows, or rows
    whose matched column is still UNVISITED.  Winners are IINF everywhere
    else, which is what makes a pull sweep restricted to these rows exact.
    """
    nc = bfs.shape[0] - 1
    rm = rmatch[:-1]
    return (rm == -1) | ((rm >= 0) & (bfs[jnp.clip(rm, 0, nc)] == UNVISITED))


def _winner_pull_compact(rxadj, radj, bfs, root, rmatch, level, nr,
                         unreached, *, cap: int, dmax: int):
    """Compact pull sweep: gather the unreached rows' adjacency via the CSC
    mirror, O(cap·dmax) instead of O(nnz).

    ``unreached`` is :func:`_unreached_rows` (passed in, not recomputed —
    XLA cannot CSE across the ``lax.cond`` boundary).  Only called when the
    eligibility guard holds (every unreached row gathered, every of its
    edges scanned), in which case each row's min over its proposing columns
    is exactly the dense sweep's min-merge winner — bit-identical.
    """
    nc = bfs.shape[0] - 1
    nnz_pad = radj.shape[0]
    colok = _active_cols(bfs, root, level)                       # (nc+1,)
    rows = jnp.nonzero(unreached, size=cap, fill_value=nr)[0]    # (cap,)
    starts = rxadj[jnp.minimum(rows, nr)]
    ends = rxadj[jnp.minimum(rows + 1, nr)]                      # fill -> deg 0
    offs = jnp.arange(dmax, dtype=jnp.int32)
    eidx = starts[:, None] + offs[None, :]                       # (cap, dmax)
    valid = offs[None, :] < (ends - starts)[:, None]
    cols = jnp.where(valid, radj[jnp.clip(eidx, 0, nnz_pad - 1)],
                     jnp.int32(nc))
    ok = valid & colok[cols]               # colok[nc] is False (bfs NEG)
    win_rows = jnp.min(jnp.where(ok, cols, IINF), axis=1)        # (cap,)
    return scatter_min(nr, jnp.minimum(rows, nr), win_rows)


def _apply_winner(winner, bfs, root, pred, rmatch, level, *, wr: bool,
                  wr_exact: bool):
    """Fold a per-row winner vector into the BFS state (the paper's Alg. 2
    lines 8-17 / Alg. 4 lines 11-18).  Shared by every sweep direction —
    once the winners agree, everything downstream is identical."""
    nc = bfs.shape[0] - 1
    nr = pred.shape[0] - 1
    upd_r = winner < IINF                                 # (nr+1,) rows reached

    pred = jnp.where(upd_r, winner, pred)
    cm_r = rmatch                                         # row-wise matched col
    visit_r = upd_r & (cm_r >= 0)                         # Alg.2 l.8-12
    end_r = upd_r & (cm_r == -1)                          # Alg.2 l.14-17

    bfs = bfs.at[jnp.where(visit_r, cm_r, nc)].set(level + 1)
    if wr:
        rootvals = root[jnp.clip(winner, 0, nc)]
        root = root.at[jnp.where(visit_r, cm_r, nc)].set(
            jnp.where(visit_r, rootvals, 0))
        # mark the root "satisfied": plain WR writes L0-2, the exact variant
        # encodes the endpoint row as -(r+1) so ALTERNATE can start only the
        # winning endpoint of each tree (paper Sec. 3, last paragraph).
        if wr_exact:
            enc = -(jnp.arange(nr + 1, dtype=jnp.int32) + 1)
        else:
            enc = jnp.full(nr + 1, FOUND, jnp.int32)
        bfs = bfs.at[jnp.where(end_r, rootvals, nc)].min(
            jnp.where(end_r, enc, IINF))
    rmatch = jnp.where(end_r, jnp.int32(-2), rmatch)
    bfs = bfs.at[nc].set(NEG)                             # restore sentinel

    vertex_inserted = jnp.any(visit_r)
    aug_found = jnp.any(end_r)
    return bfs, root, pred, rmatch, vertex_inserted, aug_found


def _expand_level(ecol, cadj, bfs, root, pred, rmatch, level, *, wr: bool,
                  wr_exact: bool, axis: Optional[str] = None):
    """One level-synchronous frontier expansion. Returns updated state.

    Edge-parallel: every edge (c, r) is one lane.  The per-row conflict
    (several frontier columns reaching the same row) is resolved with a
    deterministic min-merge, standing in for the paper's benign race.

    With ``axis`` set (inside ``shard_map``), ``ecol``/``cadj`` are this
    device's edge shard and the per-row winners of all shards merge with one
    ``lax.pmin`` over the mesh axis — the single collective any
    level-synchronous distributed BFS needs.  Everything after the merge
    operates on replicated O(n) state and is bit-identical on every device.
    """
    nr = pred.shape[0] - 1
    rt = root if wr else None
    winner = _winner_xla(ecol, cadj, bfs, rt, rmatch, level, nr)
    if axis is not None:                                  # merge edge shards
        with jax.named_scope("merge_shards"):
            winner = jax.lax.pmin(winner, axis)
    return _apply_winner(winner, bfs, root, pred, rmatch, level, wr=wr,
                         wr_exact=wr_exact)


def _expand_level_dirop(ecol, cadj, cxadj, rxadj, radj, erow, bfs, root,
                        pred, rmatch, level, dir_prev, *, wr: bool,
                        wr_exact: bool, axis: Optional[str],
                        dirop_alpha: float, dirop_beta: float, pull_cap: int,
                        pull_dmax: int):
    """Direction-optimizing frontier expansion (Beamer-style, in-jit).

    Estimates both directions' work from O(n) degree sums — the frontier
    columns' outgoing edges (``fe``, what a push sweep usefully does)
    against the unreached rows' incoming edges (``pe``, what a pull sweep
    must scan) — and ``lax.cond``-dispatches:

    * pull when ``fe * dirop_alpha > pe``;
    * once pulling, keep pulling while ``fe * dirop_beta > pe`` (the
      hysteresis band, ``beta > alpha`` — ``dir_prev`` carries the previous
      level's direction through the BFS loop);
    * on one device the pull is the compact row-gather and additionally
      requires the unreached rows to fit its (cap, dmax) geometry; the
      sharded path streams its CSC shard through the XLA sweep, no
      geometry constraint.

    Either branch produces the push sweep's exact winner vector, so the
    decision is invisible in the matching; with ``axis`` set the usual one
    ``lax.pmin`` merges the per-shard winners, whichever direction each
    level ran (the estimates are computed from replicated state, so every
    shard takes the same branch).  Returns the updated state plus this
    level's direction for the next level's hysteresis.
    """
    nr = pred.shape[0] - 1
    rt = root if wr else None

    def push(_):
        return _winner_xla(ecol, cadj, bfs, rt, rmatch, level, nr)

    isf = _active_cols(bfs, rt, level)[:-1]
    cdeg = cxadj[1:] - cxadj[:-1]
    fe = jnp.sum(jnp.where(isf, cdeg, 0)).astype(jnp.float32)
    unreached = _unreached_rows(bfs, rmatch)
    rdeg = rxadj[1:] - rxadj[:-1]
    pe = jnp.sum(jnp.where(unreached, rdeg, 0)).astype(jnp.float32)

    use_pull = (fe * dirop_alpha > pe) | (dir_prev & (fe * dirop_beta > pe))
    if axis is None:
        # compact pull: every unreached row must be gathered in full
        fits = ((jnp.sum(unreached.astype(jnp.int32)) <= pull_cap)
                & (jnp.max(jnp.where(unreached, rdeg, 0)) <= pull_dmax))
        use_pull &= fits
        pull = lambda _: _winner_pull_compact(  # noqa: E731
            rxadj, radj, bfs, rt, rmatch, level, nr, unreached,
            cap=pull_cap, dmax=pull_dmax)
    else:
        pull = lambda _: _winner_xla(           # noqa: E731
            radj, erow, bfs, rt, rmatch, level, nr)

    winner = jax.lax.cond(use_pull, pull, push, None)
    if axis is not None:                                  # merge edge shards
        with jax.named_scope("merge_shards"):
            winner = jax.lax.pmin(winner, axis)
    return _apply_winner(winner, bfs, root, pred, rmatch, level, wr=wr,
                         wr_exact=wr_exact) + (use_pull,)


# ---------------------------------------------------------------------------
# ALTERNATE (Alg. 3) + FIXMATCHING
# ---------------------------------------------------------------------------
def _alternate(cmatch, rmatch, pred, start_mask, max_steps):
    """Lock-step speculative alternation of all augmenting paths.

    ``start_mask`` selects the endpoint rows that launch walkers.  Writes of
    concurrent walkers are merged with min-scatters; the paper's line-8
    predecessor check breaks walkers that would chase another path.

    Per step this does ONE ``pred`` gather: the lookup for the next
    position (``pred[matched_row]``) doubles as the line-8 check, and its
    value is carried in the loop state so the old per-step
    ``pred[clip(cur)]`` re-gather is gone.  The two min-scatters only run on
    steps that still have an unbroken walker.  Returns
    ``(cmatch, rmatch, steps)`` — the step count is part of the contract so
    the optimization stays observable (see tests/test_frontier_paths.py).
    """
    nc = cmatch.shape[0] - 1
    nr = rmatch.shape[0] - 1
    rows = jnp.arange(nr + 1, dtype=jnp.int32)
    cur0 = jnp.where(start_mask, rows, jnp.int32(-1))
    pmc0 = pred[jnp.clip(cur0, 0, nr)]                    # pred[cur], hoisted

    def cond(carry):
        cur, _, _, _, steps = carry
        return jnp.any(cur >= 0) & (steps < max_steps)

    def body(carry):
        cur, pmc, cmatch, rmatch, steps = carry
        active = cur >= 0
        curc = jnp.clip(cur, 0, nr)
        mc = pmc                                          # matched_col = pred[cur]
        mcc = jnp.clip(mc, 0, nc)
        mr = cmatch[mcc]                                  # matched_row (snapshot)
        pmr = pred[jnp.clip(mr, 0, nr)]                   # the step's one gather
        # paper line 8: if predecessor[matched_row] == matched_col: break
        brk = active & (mr >= 0) & (pmr == mc)
        act = active & ~brk

        def scatters(ms):
            cm, rm = ms
            # cmatch[mc] <- cur ; rmatch[cur] <- mc  (speculative, min-merged)
            cprop = scatter_min(nc, jnp.where(act, mcc, nc),
                                jnp.where(act, cur, IINF))
            cm = jnp.where(cprop < IINF, cprop, cm)
            rprop = scatter_min(nr, jnp.where(act, curc, nr),
                                jnp.where(act, mc, IINF))
            rm = jnp.where(rprop < IINF, rprop, rm)
            return cm, rm

        # every walker broke this step -> both scatters would be all-sentinel
        cmatch, rmatch = jax.lax.cond(jnp.any(act), scatters,
                                      lambda ms: ms, (cmatch, rmatch))
        cur = jnp.where(act, mr, jnp.int32(-1))
        return cur, pmr, cmatch, rmatch, steps + 1

    _, _, cmatch, rmatch, steps = jax.lax.while_loop(
        cond, body, (cur0, pmc0, cmatch, rmatch, jnp.int32(0)))
    return cmatch, rmatch, steps


def _fix_matching(cmatch, rmatch):
    """Paper's FIXMATCHING, both directions -> a valid matching.

    rmatch[r] <- -1 where cmatch[rmatch[r]] != r, then the symmetric pass on
    columns (needed because deterministic merging can strand a cmatch entry).
    """
    nc = cmatch.shape[0] - 1
    nr = rmatch.shape[0] - 1
    rows = jnp.arange(nr + 1, dtype=jnp.int32)
    cols = jnp.arange(nc + 1, dtype=jnp.int32)
    rmatch = jnp.where(rmatch == -2, jnp.int32(-1), rmatch)
    ok_r = (rmatch >= 0) & (cmatch[jnp.clip(rmatch, 0, nc)] == rows)
    rmatch = jnp.where((rmatch >= 0) & ~ok_r, jnp.int32(-1), rmatch)
    ok_c = (cmatch >= 0) & (rmatch[jnp.clip(cmatch, 0, nr)] == cols)
    cmatch = jnp.where((cmatch >= 0) & ~ok_c, jnp.int32(-1), cmatch)
    return cmatch, rmatch


def _cardinality(cmatch):
    return jnp.sum((cmatch[:-1] >= 0).astype(jnp.int32))


# ---------------------------------------------------------------------------
# Drivers — Algorithm 1 (APsB) and its APFB variant
# ---------------------------------------------------------------------------
def make_solver(cfg: MatcherConfig, axis: Optional[str] = None):
    """Build the pure matcher ``(ecol, cadj, cmatch, rmatch[, cxadj]) ->
    (cmatch, rmatch, phases, fallbacks, certified, levels)``.

    ``certified`` is a device bool: True iff the final phase's BFS proved no
    augmenting path remains (the matching is maximum, Berge).  A run cut
    short by a positive ``cfg.max_phases`` budget returns ``certified=False``
    — the matching is valid but possibly sub-maximum; with
    ``cfg.degrade_maximal`` it is additionally made maximal by one greedy
    augmentation round (single-device path; :class:`~repro.matching.sharded.
    ShardedMatcher` applies the same round outside the ``shard_map`` region).
    ``levels`` counts the BFS levels expanded over all phases: under
    ``vmap`` each lane counts only the levels its own loop predicate
    allowed, and inside ``shard_map`` it is the replicated loop count.

    The program carries ``jax.named_scope`` names for the trace: ``phase``
    (one outer iteration), ``bfs_level`` (one level's sweep), ``alternate``
    and ``fix_matching``, and with ``axis`` set ``merge_shards`` (the
    level's ``pmin``).  They are metadata only; the last component of
    every ``op_name`` stays the JAX primitive.

    Shape-polymorphic: ``nc``/``nr`` are derived from the argument shapes
    at trace time, so one returned function serves every size bucket and
    closes under ``jit`` and ``vmap``.

    ``axis`` names a mesh axis for the distributed variant: the returned
    function then expects to run *inside* ``shard_map`` with ``ecol``/``cadj``
    edge-sharded over that axis and the O(n) state replicated.  The only
    communication is one ``pmin`` per BFS level in :func:`_expand_level`,
    which merges the shards' per-row winner vectors.  ALTERNATE and
    FIXMATCHING run redundantly-but-identically on the replicated state
    (their cost is O(n) per phase vs O(nnz/D) for expansion, so sharding
    them would buy nothing).

    ``cfg.dirop`` needs ``cxadj`` plus the CSC mirror arrays
    (``rxadj``/``radj``/``erow`` of ``DeviceCSR.with_csc``); it composes
    with ``axis`` — each shard pulls over its own CSC slice and the same
    single ``pmin`` merges the winners.
    """
    wr = cfg.kernel == "gpubfs_wr"

    def match_fn(ecol, cadj, cmatch, rmatch, cxadj=None, rxadj=None,
                 radj=None, erow=None):
        if cfg.dirop and (cxadj is None or rxadj is None or radj is None
                          or erow is None):
            raise ValueError(
                "dirop needs cxadj plus the CSC mirror (rxadj/radj/erow); "
                "build it with DeviceCSR.with_csc() — Matcher.solve passes "
                "it through when present")
        nc = cmatch.shape[0] - 1
        nr = rmatch.shape[0] - 1
        # pull geometry: the ONE auto rule lives on MatcherConfig (pure in
        # (config, bucket), so the 0 marker in cache keys is safe)
        pull_cap = cfg.resolve_cap(cfg.pull_cap, nr)
        pull_dmax = cfg.resolve_dmax(cfg.pull_dmax)

        def phase_bfs(cmatch, rmatch):
            """Inner while of Alg. 1: level-synchronous BFS to exhaustion/first hit."""
            bfs, root = level0_state(cmatch)
            pred = jnp.full(nr + 1, jnp.int32(nc), jnp.int32)   # fresh each phase

            def cond(c):
                _, _, _, _, level, ins, aug, aug_lvl, _ = c
                go = ins
                if cfg.algo == "apsb":
                    go = go & ~aug                               # Alg.1 l.9-10 break
                elif cfg.tail_levels > 0:
                    # bounded tail: expand at most tail_levels past the first
                    # augmenting level (beyond-paper, see MatcherConfig)
                    go = go & (level <= aug_lvl + cfg.tail_levels)
                return go

            @jax.named_scope("bfs_level")
            def body(c):
                bfs, root, pred, rmatch, level, _, aug, aug_lvl, dirp = c
                if cfg.dirop:
                    bfs, root, pred, rmatch, ins, aug_l, dirp = \
                        _expand_level_dirop(
                            ecol, cadj, cxadj, rxadj, radj, erow, bfs, root,
                            pred, rmatch, level, dirp, wr=wr,
                            wr_exact=cfg.wr_exact, axis=axis,
                            dirop_alpha=cfg.dirop_alpha,
                            dirop_beta=cfg.dirop_beta,
                            pull_cap=pull_cap, pull_dmax=pull_dmax)
                else:
                    bfs, root, pred, rmatch, ins, aug_l = _expand_level(
                        ecol, cadj, bfs, root, pred, rmatch, level, wr=wr,
                        wr_exact=cfg.wr_exact, axis=axis)
                aug_lvl = jnp.where(aug_l & (aug_lvl == IINF), level, aug_lvl)
                return (bfs, root, pred, rmatch, level + 1, ins, aug | aug_l,
                        aug_lvl, dirp)

            bfs, root, pred, rmatch, level, _, aug, _, _ = jax.lax.while_loop(
                cond, body, (bfs, root, pred, rmatch, L0, jnp.bool_(True),
                             jnp.bool_(False), IINF, jnp.bool_(False)))
            return bfs, root, pred, rmatch, aug, level - L0    # levels run

        def start_mask_fn(bfs, root, rmatch):
            mask = rmatch == -2
            if cfg.wr_exact:
                # only the winning endpoint of each satisfied tree starts a walker
                enc = bfs[:-1]                                   # (nc,)
                is_win = enc <= -1
                endpoint = jnp.where(is_win, -(enc + 1), nr)
                wins = jnp.zeros(nr + 1, bool).at[endpoint].set(True)
                wins = wins.at[nr].set(False)
                mask = mask & wins
            return mask

        max_steps = jnp.int32(2 * (min(nc, nr) + 2))

        def alternate(cmatch, rmatch, pred, mask):
            """ALTERNATE from ``mask``'s endpoints, then FIXMATCHING."""
            with jax.named_scope("alternate"):
                cmatch, rmatch, _ = _alternate(cmatch, rmatch, pred, mask,
                                               max_steps)
            with jax.named_scope("fix_matching"):
                return _fix_matching(cmatch, rmatch)

        @jax.named_scope("phase")
        def outer_body(carry):
            cmatch, rmatch, _, phases, fallbacks, levels = carry
            cm0, rm0 = cmatch, rmatch                            # phase snapshot
            card0 = _cardinality(cm0)
            bfs, root, pred, rmatch_b, aug, nlev = phase_bfs(cmatch, rmatch)

            def do_phase(_):
                mask = start_mask_fn(bfs, root, rmatch_b)
                cm1, rm1 = alternate(cm0, jnp.where(mask, jnp.int32(-2), rm0),
                                     pred, mask)

                def fallback(_):
                    # guard: speculative phase gained nothing -> augment exactly one
                    # shortest path on the snapshot (single walker cannot conflict).
                    any_ep = rmatch_b == -2
                    first = jnp.argmax(any_ep)                   # lowest endpoint row
                    one = jnp.zeros(nr + 1, bool).at[first].set(jnp.any(any_ep))
                    return alternate(cm0, rm0, pred, one) + (jnp.int32(1),)

                cm1, rm1, fb = jax.lax.cond(
                    _cardinality(cm1) > card0,
                    lambda _: (cm1, rm1, jnp.int32(0)), fallback, None)
                return cm1, rm1, fb

            cmatch, rmatch, fb = jax.lax.cond(
                aug, do_phase, lambda _: (cm0, rm0, jnp.int32(0)), None)
            return (cmatch, rmatch, aug, phases + 1, fallbacks + fb,
                    levels + nlev)

        def outer_cond(carry):
            *_, aug, phases, _, _ = carry
            limit = cfg.max_phases if cfg.max_phases > 0 else nc + 2
            return aug & (phases < limit)

        carry = (cmatch, rmatch, jnp.bool_(True), jnp.int32(0), jnp.int32(0),
                 jnp.int32(0))
        carry = jax.lax.while_loop(outer_cond, outer_body, carry)
        cmatch, rmatch, aug, phases, fallbacks, levels = carry
        # aug is the last BFS verdict: False means the phase found no
        # augmenting path — Berge certifies the matching maximum.  A
        # budget-truncated exit leaves aug True: valid but uncertified.
        certified = ~aug
        if cfg.degrade_maximal and cfg.max_phases > 0 and axis is None:
            # Budget exhausted -> the truncated matching may leave free
            # columns adjacent to free rows.  One speculative greedy round
            # (the `cheap` warm start's augment-only pass) restores
            # maximality without another BFS phase.  Local import:
            # warmstart.py imports solver internals from this module.
            from .warmstart import cheap_init
            cmatch, rmatch = jax.lax.cond(
                certified, lambda cr: cr,
                lambda cr: cheap_init(ecol, cadj, *cr, cxadj=cxadj),
                (cmatch, rmatch))
        return cmatch, rmatch, phases, fallbacks, certified, levels

    return match_fn
