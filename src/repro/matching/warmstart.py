"""Pluggable warm-start registry: pure on-device matching initializers.

Every entry is a pure function ``(ecol, cadj, cmatch, rmatch) ->
(cmatch, rmatch)`` over sentinel-padded int32 vectors, so
:meth:`repro.matching.Matcher.run` can fuse *init + solve* into one compiled
program — the warm start never round-trips through the host (the old
``cheap_matching_jax``/``karp_sipser_jax`` wrappers did numpy in/out between
init and matcher).  :func:`apply_warm_start` runs an entry on a
``DeviceCSR`` and also returns the rounds it ran, for
``MatchState.warm_rounds``: the built-ins count theirs and are handed the
graph's column offsets ``cxadj``; a custom entry counts 0.

Built-ins: ``"none"`` (cold), ``"cheap"`` (the paper's greedy warm start),
``"karp_sipser"`` (beyond-paper degree-1 peeling + greedy residual).  Register
custom initializers with :func:`register_warm_start`.

How a built-in round streams the edge list.  The built-ins rely on ``ecol``
ascending (as :func:`~repro.matching.device_csr.validate_structure`
enforces, padding last under the sentinel column), so each column owns a
contiguous segment of edge slots.  Everything a round needs from the column
side is then a *segment scan* over blocks of 128 slots, by doubling within
each block: a prefix sum (each block adding a prefix sum of the blocks'
totals), or a segmented min (each block's last value carried into its
column by a scatter of one index a block), read at the O(n) column
offsets ``cxadj``.  Only the row side (``cadj``, in no useful order) is
indexed per edge: one gather a round, plus one scatter-add in a
Karp–Sipser round.  Every reduction is an integer sum or min, so the
result is independent of the order and equal to gathering and scattering
by ``ecol``.  ``cxadj`` is the graph's device-resident offsets
(``Matcher`` and ``ShardedMatcher`` pass ``graph.cxadj``); a
four-argument call derives them once from ``ecol``.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from .solve import IINF, _fix_matching, scatter_min

InitFn = Callable[[jax.Array, jax.Array, jax.Array, jax.Array],
                  Tuple[jax.Array, jax.Array]]


# A block of edge slots: one row of the vector unit's 128 lanes.
_LANES = 128


def _blocks(x, fill):
    """``x`` padded with ``fill`` to whole blocks, one block a row."""
    return jnp.pad(x, (0, -x.shape[0] % _LANES),
                   constant_values=fill).reshape(-1, _LANES)


def _prefix_sum(x):
    """Inclusive prefix sum of the int32 vector ``x``: by doubling within
    each block (log2 of ``_LANES`` shifted adds), then each block adds the
    sum of the blocks before it, a prefix sum of the blocks' totals taken
    the same way.  (XLA's own cumsum compiles for seconds on the TPU at
    some lengths; this takes under one.)"""
    y = _blocks(x, 0)
    k = 1
    while k < _LANES:
        y = y + jnp.pad(y[:, :-k], ((0, 0), (k, 0)))
        k *= 2
    totals = y[:, -1]
    if totals.shape[0] > 1:
        y = y + (_prefix_sum(totals) - totals)[:, None]
    return y.reshape(-1)[:x.shape[0]]


class _Segments:
    """The column segments of a column-sorted edge list of ``m`` slots.

    ``bounds`` (nc+2,): each column's first slot, the sentinel column's
    (the true edge count), then ``m``.  For the segmented min the slots
    are viewed as blocks of ``_LANES`` (padded with empty slots to a whole
    block): ``dist`` (blocks, _LANES) is each slot's distance from its
    column's first slot, and ``tail_col`` (blocks,) the column of each
    block's last slot.
    """

    def __init__(self, ecol, nc: int, cxadj=None):
        self.m = m = ecol.shape[0]
        if cxadj is None:
            cxadj = jnp.searchsorted(
                ecol, jnp.arange(nc + 1, dtype=jnp.int32)).astype(jnp.int32)
        self.bounds = jnp.append(cxadj, jnp.int32(m))
        self.dist = _blocks(
            jnp.arange(m, dtype=jnp.int32) - self.broadcast(cxadj), 0)
        self.tail_col = _blocks(ecol, nc)[:, -1]

    def broadcast(self, vals):
        """``vals[ecol]`` for (nc+1,) column values, without a per-edge
        gather: each column's step from the previous column's value lands
        on its first slot (an O(n) scatter, where the steps of empty
        columns sharing a slot cancel), and a prefix sum carries it over
        the column's segment."""
        steps = jnp.diff(vals.astype(jnp.int32), prepend=jnp.int32(0))
        marks = jnp.zeros(self.m, jnp.int32).at[self.bounds[:-1]].add(
            steps, mode="drop", indices_are_sorted=True)
        return _prefix_sum(marks)

    def sum(self, x):
        """(nc+1,) per-column sums of the per-edge int32 ``x``: a prefix
        sum read at the O(n) offsets (wrapping int32 differences are exact
        for any column whose sum fits)."""
        csum = _prefix_sum(x.astype(jnp.int32))
        b = self.bounds
        at = jnp.where(b > 0, csum[jnp.maximum(b - 1, 0)], 0)
        return at[1:] - at[:-1]

    def min(self, *xs):
        """(nc+1,) per-column minima of each per-edge ``x`` (IINF for an
        empty column).  Within each block a segmented min-scan by doubling
        (log2 of ``_LANES`` steps, each taking the min with the slot ``k``
        lanes before if that one lies in the same column); each block's
        last slot then carries its run into its column by a scatter-min of
        m/_LANES indices, and a column's minimum is that carry against the
        scan at its last slot."""
        lo, hi = self.bounds[:-1], self.bounds[1:]
        last = jnp.maximum(hi - 1, 0)
        out = []
        for x in xs:
            y = _blocks(x, IINF)
            k = 1
            while k < _LANES:
                before = jnp.pad(y[:, :-k], ((0, 0), (k, 0)),
                                 constant_values=IINF)
                y = jnp.where(self.dist >= k, jnp.minimum(y, before), y)
                k *= 2
            carry = jnp.full(lo.shape, IINF, jnp.int32).at[self.tail_col].min(
                y[:, -1], indices_are_sorted=True)
            out.append(jnp.where(hi > lo, jnp.minimum(
                y.reshape(-1)[last], carry), IINF))
        return tuple(out)


def none_init(ecol, cadj, cmatch, rmatch):
    """Cold start: pass the incoming (all-unmatched) state through."""
    del ecol, cadj
    return cmatch, rmatch


def cheap_init(ecol, cadj, cmatch, rmatch, cxadj=None):
    """Parallel cheap matching (the paper's common warm start).

    Speculative round-based greedy (propose -> resolve -> commit): each round
    every unmatched column proposes its lowest-index unmatched neighbor row;
    each proposed row accepts its lowest proposing column; accepted pairs
    commit.  Rounds repeat until no proposal survives -> a maximal greedy
    matching (quality comparable to sequential cheap matching).

    Per round the edge list is streamed by one gather of the rows' free
    flags by ``cadj``; each column's lowest free row is a segmented min
    over its slots (``ecol`` ascending; ``cxadj`` the column offsets,
    derived from ``ecol`` when not given).
    """
    return _cheap(ecol, cadj, cmatch, rmatch, cxadj)[:2]


def _cheap(ecol, cadj, cmatch, rmatch, cxadj):
    """:func:`cheap_init` and its rounds, the last the one that found no
    proposal."""
    return _cheap_rounds(_Segments(ecol, cmatch.shape[0] - 1, cxadj), cadj,
                         cmatch, rmatch)


def _cheap_rounds(seg: _Segments, cadj, cmatch, rmatch):
    nc = cmatch.shape[0] - 1
    nr = rmatch.shape[0] - 1
    cols = jnp.arange(nc + 1, dtype=jnp.int32)
    rows = jnp.arange(nr + 1, dtype=jnp.int32)

    def round_fn(carry):
        cmatch, rmatch, _, rounds = carry
        row_free = (rmatch == -1)[cadj]                  # the one edge gather
        (low,) = seg.min(jnp.where(row_free, cadj, IINF))
        best_r = jnp.where(cmatch == -1, low, IINF)
        propose = best_r < IINF
        best_c = scatter_min(nr, jnp.where(propose, best_r, nr),
                             jnp.where(propose, cols, IINF))
        won = best_c < IINF                                  # per-row accept
        rmatch = jnp.where(won, best_c, rmatch)
        cmatch = cmatch.at[jnp.where(won, best_c, nc)].set(
            jnp.where(won, rows, cmatch[nc]))
        cmatch = cmatch.at[nc].set(jnp.int32(-3))
        return cmatch, rmatch, jnp.any(won), rounds + 1

    cmatch, rmatch, _, rounds = jax.lax.while_loop(
        lambda carry: carry[2], round_fn,
        (cmatch, rmatch, jnp.bool_(True), jnp.int32(0)))
    return cmatch, rmatch, rounds


def karp_sipser_init(ecol, cadj, cmatch, rmatch, cxadj=None):
    """Karp–Sipser peeling, data-parallel (beyond the paper's cheap init).

    While the residual graph has a degree-1 vertex, matching its only edge is
    optimal; the TPU adaptation peels *all* current degree-1 vertices per
    round (speculatively) with min-scatter conflict resolution, then finishes
    with the parallel cheap matching on the residual and a repair pass.  All
    three stages fuse into the caller's program — no host hop.

    A degree round factors its edge predicate into a column side and a row
    side.  Row side, per edge by ``cadj``: one scatter-add of the columns'
    free flags gives each free row its residual degree (a row's degree is
    its free flag times its count of free columns), and one gather brings
    each edge its row's free and degree-1 flags back.  Column side, segment
    scans over the slots (``ecol`` ascending; ``cxadj`` the column offsets,
    derived from ``ecol`` when not given): the columns' free flags are
    broadcast to their slots by a prefix sum, a column's degree is a
    segment sum, and its forced proposal a segmented min — the lowest free
    row when its degree is 1, else the lowest degree-1 row.
    """
    return _karp_sipser(ecol, cadj, cmatch, rmatch, cxadj)[:2]


def _karp_sipser(ecol, cadj, cmatch, rmatch, cxadj):
    """:func:`karp_sipser_init` and its rounds: the degree rounds (the last
    one forced nothing) plus the cheap rounds."""
    nc = cmatch.shape[0] - 1
    nr = rmatch.shape[0] - 1
    seg = _Segments(ecol, nc, cxadj)
    cols = jnp.arange(nc + 1, dtype=jnp.int32)
    rows = jnp.arange(nr + 1, dtype=jnp.int32)

    def degree_round(carry):
        cmatch, rmatch, _, rounds = carry
        cfree = cmatch == -1                  # the sentinels are never free
        rfree = rmatch == -1
        # residual degrees of the rows: the one edge scatter
        rcnt = jnp.zeros(nr + 1, jnp.int32).at[cadj].add(seg.broadcast(cfree))
        rdeg = jnp.where(rfree, rcnt, 0)
        # the row side to the edges: the one edge gather
        rv = (rfree.astype(jnp.int32) + 2 * (rdeg == 1))[cadj]
        free_e = (rv & 1) == 1
        cdeg = jnp.where(cfree, seg.sum(free_e), 0)
        low_free, low_one = seg.min(jnp.where(free_e, cadj, IINF),
                                    jnp.where(rv >= 2, cadj, IINF))
        # forced edges: the column's only live edge, or a degree-1 row's
        prop_r = jnp.where(cfree, jnp.where(cdeg == 1, low_free, low_one),
                           IINF)
        col_has = prop_r < IINF
        # rows accept lowest proposing column among columns that picked them
        prop_c = scatter_min(nr, jnp.where(col_has, prop_r, nr),
                             jnp.where(col_has, cols, IINF))
        won_r = prop_c < IINF                       # row r matched to prop_c[r]
        rmatch = jnp.where(won_r & (rmatch == -1), prop_c, rmatch)
        # commit winning columns (repair: only pairs where row accepted col)
        won_pair = won_r & (rmatch == prop_c)
        cmatch = cmatch.at[jnp.where(won_pair, jnp.clip(prop_c, 0, nc), nc)
                           ].max(jnp.where(won_pair, rows, jnp.int32(-1)))
        cmatch = cmatch.at[nc].set(jnp.int32(-3))
        rmatch = rmatch.at[nr].set(jnp.int32(-3))
        return cmatch, rmatch, jnp.any(col_has), rounds + 1

    cmatch, rmatch, _, rounds = jax.lax.while_loop(
        lambda carry: carry[2], degree_round,
        (cmatch, rmatch, jnp.bool_(True), jnp.int32(0)))
    cmatch, rmatch, cheap_rounds = _cheap_rounds(seg, cadj, cmatch, rmatch)
    # clear asymmetric remnants of the speculative commits (same symmetric
    # repair the solver uses; the -2 endpoint clear is a no-op here)
    return _fix_matching(cmatch, rmatch) + (rounds + cheap_rounds,)


WARM_STARTS: dict = {
    "none": none_init,
    "cheap": cheap_init,
    "karp_sipser": karp_sipser_init,
}
_VERSIONS: dict = {name: 0 for name in WARM_STARTS}
# the built-ins' forms that take the column offsets and count their rounds
_COUNTED = {cheap_init: _cheap, karp_sipser_init: _karp_sipser}


def apply_warm_start(name: str, graph, cmatch, rmatch):
    """Run the registered initializer ``name`` on a ``DeviceCSR``:
    ``(cmatch, rmatch, rounds)``, ``rounds`` 0 for a custom initializer."""
    fn = get_warm_start(name)
    if fn in _COUNTED:
        return _COUNTED[fn](graph.ecol, graph.cadj, cmatch, rmatch,
                            graph.cxadj)
    return (*fn(graph.ecol, graph.cadj, cmatch, rmatch), jnp.int32(0))


def register_warm_start(name: str, fn: InitFn) -> None:
    """Add a custom initializer to the registry (pure device fn required).

    Re-registering a name bumps its version so compiled programs built from
    the previous initializer are not reused.
    """
    if not callable(fn):
        raise TypeError(f"warm start {name!r} must be callable")
    WARM_STARTS[name] = fn
    _VERSIONS[name] = _VERSIONS.get(name, -1) + 1


def warm_start_version(name: str) -> int:
    """Monotonic per-name counter; part of the compile-cache key."""
    return _VERSIONS.get(name, 0)


def warm_start_names() -> tuple:
    return tuple(WARM_STARTS)


def get_warm_start(name: str) -> InitFn:
    try:
        return WARM_STARTS[name]
    except KeyError:
        raise KeyError(
            f"unknown warm start {name!r}; registered: {warm_start_names()}"
        ) from None
