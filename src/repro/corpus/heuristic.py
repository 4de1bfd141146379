"""Deterministic replay of the direction-optimizing push/pull heuristic.

The dirop engine decides push vs pull per BFS level from two O(n) degree
sums (``fe`` = frontier columns' outgoing edges, ``pe`` = unreached rows'
incoming edges — see :func:`repro.matching.solve._expand_level_dirop`).
Wall-clock benchmarks of that decision flake on shared CI runners, so the
per-family gate instead replays the *exact* level states the solver sweeps
(every sweep path is bit-identical, so the dense jnp replay sees the same
``bfs``/``rmatch`` trajectory dirop would) and prices the decisions with a
fixed work model:

* a push level sweeps every padded edge tile and merges:
  ``cost = ntiles * LANE`` (= the padded edge count);
* a pull level pays ``PULL_TILE_OVERHEAD`` lanes per CSC tile (the stream +
  skip decision) and full ``LANE`` cost only for tiles that actually
  contain an unreached row's edge — the tile-skip win of a streaming pull
  sweep, which is large when the remaining rows are clustered (late
  levels, road/comb-like instances) and small when RCP permutation
  scatters them.  Neither pull sweep the solver has skips tiles (the
  compact row-gather costs O(cap * dmax), the sharded pull is the XLA
  sweep), so the model prices a sweep the repository no longer runs: a
  recorded debt (ROADMAP.md), to be settled against chip measurements of
  ``dirop`` on a long-diameter cell.

``modelled_rel`` = dirop cost / push-only cost is then a pure function of
(instance, warm start, alpha, beta): deterministic, portable across
machines, and sensitive to exactly the regression class the gate is for —
an always-pull ``alpha``/``beta`` prices early levels (every tile occupied)
at ``~(1 + PULL_TILE_OVERHEAD/LANE)`` of a push sweep and the per-family
``rel`` rows move far past any gate tolerance.  The committed alpha/beta
sweep in ``BENCH_PR7.json`` (``corpus.alpha_sweep``) is what the
:class:`~repro.matching.MatcherConfig` dirop defaults cite.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import BipartiteCSR
from repro.core.matcher import maximum_matching
from repro.matching import MatcherConfig, MatchState
from repro.matching.solve import L0, UNVISITED, _expand_level, level0_state
from repro.matching.warmstart import get_warm_start

# the model's tile geometry: LANE matches the kernels' 128-lane tiles;
# PULL_TILE_OVERHEAD is the lanes-equivalent a pull sweep pays per tile
# just to stream it and decide to skip.  Model constants, not measurements:
# they only need to make always-pull measurably worse than push on early
# levels (every tile occupied) and tile-skipping pulls measurably better.
LANE = 128
PULL_TILE_OVERHEAD = 16
# kept as the documented ratio for reporting; the cost formulas use the
# tile constants directly
PULL_STREAM_FRACTION = PULL_TILE_OVERHEAD / LANE

# the replay's push expansion step: the solver's own _expand_level with the
# default-variant statics (APFB / gpubfs_wr).
_STEP = jax.jit(functools.partial(_expand_level, wr=True, wr_exact=False))

_BASE = MatcherConfig(algo="apfb", kernel="gpubfs_wr")


@dataclasses.dataclass(frozen=True)
class HeuristicTrace:
    """Per-phase, per-level ``(fe, pe, touched_tiles)`` for one instance +
    warm start.  ``fe``/``pe`` are the solver's exact decision inputs;
    ``touched_tiles`` counts CSC edge tiles containing at least one
    unreached row's edge (the pull sweep's non-skippable tiles)."""
    phases: Tuple[Tuple[Tuple[float, float, int], ...], ...]
    nnz_pad: int

    @property
    def ntiles(self) -> int:
        return -(-self.nnz_pad // LANE)

    @property
    def levels(self) -> int:
        return sum(len(p) for p in self.phases)


def _replay_phase(ecol, cadj, cdeg, rdeg, erow_host, tile_of_slot, cm, rm
                  ) -> List[Tuple[float, float, int]]:
    """Eagerly run one phase's BFS levels, recording (fe, pe) before each
    expansion — the exact sums ``_expand_level_dirop`` computes — plus the
    pull sweep's touched-tile count for the work model."""
    nc = cm.shape[0] - 1
    state = MatchState.from_host(cm, rm)
    bfs, root = level0_state(state.cmatch)
    pred = jnp.full(rm.shape[0] + 1, jnp.int32(nc), jnp.int32)
    rmatch = state.rmatch
    out: List[Tuple[float, float, int]] = []
    level = L0
    while True:
        bfs_h = np.asarray(bfs)
        isf = bfs_h[:-1] == level
        isf &= bfs_h[np.clip(np.asarray(root)[:-1], 0, nc)] >= UNVISITED
        fe = float(np.sum(np.where(isf, cdeg, 0)))
        rm_h = np.asarray(rmatch)[:-1]
        unreached = (rm_h == -1) | ((rm_h >= 0)
                                    & (bfs_h[np.clip(rm_h, 0, nc)]
                                       == UNVISITED))
        pe = float(np.sum(np.where(unreached, rdeg, 0)))
        touched = int(np.unique(tile_of_slot[unreached[erow_host]]).size)
        out.append((fe, pe, touched))
        bfs, root, pred, rmatch, ins, _ = _STEP(ecol, cadj, bfs, root, pred,
                                                rmatch, jnp.int32(level))
        if not bool(ins):
            return out
        level += 1


def trace_instance(g: BipartiteCSR, warm_start: str = "cheap",
                   max_phases: int = 128) -> HeuristicTrace:
    """Replay every BFS phase of the default solver on ``g`` and collect the
    per-level (fe, pe) direction inputs.

    Phase starting states advance through the *real* solver
    (``max_phases=1`` per step), so the trace is exactly the level sequence
    any sweep path executes on this instance — the decisions priced by
    :func:`modelled_rel` are the ones dirop would take online.
    """
    ecol = jnp.asarray(g.ecol)
    cadj = jnp.asarray(g.cadj)
    cdeg = np.diff(g.cxadj).astype(np.int64)
    rdeg = np.bincount(g.cadj[: g.nnz], minlength=g.nr)[: g.nr]
    # CSC slot -> (row, tile): which pull tiles an unreached-row set occupies
    order = np.argsort(g.cadj[: g.nnz], kind="stable")
    erow_host = g.cadj[: g.nnz][order]
    tile_of_slot = np.arange(g.nnz, dtype=np.int64) // LANE
    fresh = MatchState.fresh(g.nc, g.nr)
    cm, rm = (np.asarray(a, np.int32)[:-1]
              for a in get_warm_start(warm_start)(
                  ecol, cadj, fresh.cmatch, fresh.rmatch))
    step_cfg = dataclasses.replace(_BASE, max_phases=1)
    phases = []
    card = int(np.sum(cm >= 0))
    for _ in range(max_phases):
        phases.append(tuple(_replay_phase(ecol, cadj, cdeg, rdeg, erow_host,
                                          tile_of_slot, cm, rm)))
        cm, rm, _ = maximum_matching(g, step_cfg, cm, rm)
        gained = int(np.sum(cm >= 0)) - card
        card += gained
        if gained <= 0:
            break
    return HeuristicTrace(phases=tuple(phases), nnz_pad=g.nnz_pad)


def modelled_rel(trace: HeuristicTrace, alpha: float, beta: float
                 ) -> Tuple[float, int]:
    """(dirop cost / push-only cost, pull-level count) under the work model.

    Applies the solver's exact decision rule — ``pull = fe*alpha > pe`` or,
    while already pulling, ``fe*beta > pe`` (``dir_prev`` resets each phase,
    as in the solver's phase loop) — to the traced (fe, pe) sequence, then
    prices each level with the tile work model (module docstring).
    """
    ntiles = trace.ntiles
    push_level = float(ntiles * LANE)
    push_total = dirop_total = 0.0
    pulls = 0
    for phase in trace.phases:
        prev = False
        for fe, pe, touched in phase:
            pull = (fe * alpha > pe) or (prev and fe * beta > pe)
            dirop_total += ((ntiles * PULL_TILE_OVERHEAD + touched * LANE)
                            if pull else push_level)
            push_total += push_level
            pulls += int(pull)
            prev = pull
    return dirop_total / max(push_total, 1.0), pulls


def sweep_grid() -> Sequence[Tuple[float, float]]:
    """The committed (alpha, beta) sweep: never-pull and always-pull anchors
    around a log-spaced band (beta = 4*alpha keeps the hysteresis shape)."""
    return ((1e-6, 1e-6), (1.0, 4.0), (2.0, 8.0), (4.0, 16.0), (8.0, 32.0),
            (16.0, 64.0), (1e6, 1e6))
