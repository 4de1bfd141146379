"""Bipartite graph container in CSR form, the paper's data layout.

The paper stores the graph as column-major CSR (``cxadj``/``cadj``): for
column ``c`` the adjacent rows are ``cadj[cxadj[c]:cxadj[c+1]]``.  The TPU
adaptation additionally materializes the *edge-parallel* view ``ecol`` (the
column endpoint of every edge) so a BFS level is one dense vector op over all
edges instead of a per-thread walk over a ragged adjacency list.

All arrays are int32 and padded to fixed sizes so the whole matcher jits once
per size bucket:

* padded edges point at a sentinel column ``nc`` and sentinel row ``nr``;
* state vectors (``cmatch``/``bfs_array``/``root``) carry one extra sentinel
  slot which is never active.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

INT = np.int32

# Sentinel values shared with the matcher kernels.
UNMATCHED = -1          # vertex not matched
ENDPOINT = -2           # row discovered as an augmenting-path endpoint (paper's -2)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class BipartiteCSR:
    """Column-major CSR bipartite graph with an edge-parallel view.

    Attributes
    ----------
    nc, nr    : true number of columns / rows.
    nnz       : true number of edges.
    cxadj     : (nc+1,) CSR offsets.
    cadj      : (nnz_pad,) row endpoint per edge (sentinel ``nr`` in padding).
    ecol      : (nnz_pad,) column endpoint per edge (sentinel ``nc`` in padding).
    """

    nc: int
    nr: int
    nnz: int
    cxadj: np.ndarray
    cadj: np.ndarray
    ecol: np.ndarray

    @property
    def nnz_pad(self) -> int:
        return int(self.cadj.shape[0])

    @staticmethod
    def from_csr(cxadj: np.ndarray, cadj: np.ndarray, nc: int, nr: int,
                 pad_to: Optional[int] = None, lane: int = 128) -> "BipartiteCSR":
        cxadj = np.asarray(cxadj, dtype=INT)
        cadj = np.asarray(cadj, dtype=INT)
        nnz = int(cadj.shape[0])
        assert cxadj.shape == (nc + 1,)
        assert cxadj[-1] == nnz
        npad = pad_to if pad_to is not None else max(lane, _round_up(nnz, lane))
        assert npad >= nnz
        degrees = np.diff(cxadj)
        ecol = np.repeat(np.arange(nc, dtype=INT), degrees)
        cadj_p = np.full(npad, nr, dtype=INT)
        ecol_p = np.full(npad, nc, dtype=INT)
        cadj_p[:nnz] = cadj
        ecol_p[:nnz] = ecol
        return BipartiteCSR(nc=nc, nr=nr, nnz=nnz, cxadj=cxadj, cadj=cadj_p, ecol=ecol_p)

    @staticmethod
    def from_edges(cols: np.ndarray, rows: np.ndarray, nc: int, nr: int,
                   pad_to: Optional[int] = None) -> "BipartiteCSR":
        """Build from an unsorted edge list, deduplicating."""
        cols = np.asarray(cols, dtype=np.int64)
        rows = np.asarray(rows, dtype=np.int64)
        assert cols.shape == rows.shape
        keys = cols * np.int64(nr) + rows
        keys = np.unique(keys)
        cols = (keys // nr).astype(INT)
        rows = (keys % nr).astype(INT)
        order = np.argsort(cols, kind="stable")
        cols, rows = cols[order], rows[order]
        counts = np.bincount(cols, minlength=nc).astype(INT)
        cxadj = np.zeros(nc + 1, dtype=INT)
        np.cumsum(counts, out=cxadj[1:])
        return BipartiteCSR.from_csr(cxadj, rows, nc, nr, pad_to=pad_to)

    def to_scipy(self):
        import scipy.sparse as sp
        data = np.ones(self.nnz, dtype=np.int8)
        return sp.csr_matrix(
            (data, self.cadj[: self.nnz], self.cxadj), shape=(self.nc, self.nr)
        )

    def permuted(self, seed: int = 0) -> "BipartiteCSR":
        """Random row/column permutation — the paper's RCP instance transform."""
        rng = np.random.default_rng(seed)
        cperm = rng.permutation(self.nc).astype(INT)   # new id of old column
        rperm = rng.permutation(self.nr).astype(INT)
        cols = cperm[self.ecol[: self.nnz]]
        rows = rperm[self.cadj[: self.nnz]]
        return BipartiteCSR.from_edges(cols, rows, self.nc, self.nr,
                                       pad_to=self.nnz_pad)

    def transpose(self) -> "BipartiteCSR":
        """Row-major view (rxadj/radj) as a BipartiteCSR with roles swapped."""
        return BipartiteCSR.from_edges(self.cadj[: self.nnz], self.ecol[: self.nnz],
                                       self.nr, self.nc, pad_to=self.nnz_pad)


def validate_matching(g: BipartiteCSR, cmatch: np.ndarray, rmatch: np.ndarray) -> int:
    """Check matching validity; return its cardinality. Raises on violation."""
    cmatch = np.asarray(cmatch)[: g.nc].astype(np.int64)
    rmatch = np.asarray(rmatch)[: g.nr].astype(np.int64)
    cols = np.flatnonzero(cmatch != UNMATCHED)
    rows = cmatch[cols]
    in_range = (rows >= 0) & (rows < g.nr)
    safe = np.where(in_range, rows, 0)
    symmetric = in_range & (rmatch[safe] == cols)
    # (c, r) is an edge iff its key is among the sorted real-edge keys; the
    # int64-max tail keeps every searchsorted position a valid index
    keys = np.append(np.sort(g.ecol[: g.nnz].astype(np.int64) * g.nr
                             + g.cadj[: g.nnz]), np.iinfo(np.int64).max)
    want = cols * g.nr + safe
    is_edge = symmetric & (keys[np.searchsorted(keys, want)] == want)
    if not is_edge.all():
        i = int(np.argmin(is_edge))                  # first offending column
        c, r = int(cols[i]), int(rows[i])
        assert in_range[i], f"cmatch[{c}]={r} out of range"
        assert symmetric[i], \
            f"asymmetric match c={c} r={r} rmatch[r]={rmatch[r]}"
        raise AssertionError(f"matched non-edge ({c},{r})")
    mrows = np.flatnonzero(rmatch != UNMATCHED)
    mcols = rmatch[mrows]
    ok = (mcols >= 0) & (mcols < g.nc)
    ok &= cmatch[np.where(ok, mcols, 0)] == mrows
    if not ok.all():
        i = int(np.argmin(ok))
        raise AssertionError(
            f"asymmetric match r={int(mrows[i])} c={int(mcols[i])}")
    return len(cols)


def is_maximal(g: BipartiteCSR, cmatch: np.ndarray, rmatch: np.ndarray
               ) -> bool:
    """True iff no edge joins a free column to a free row.

    The weaker-than-maximum guarantee a phase-budget-truncated solve keeps
    (``MatcherConfig(max_phases=k, degrade_maximal=True)``): a maximal
    matching is at least half the maximum, so it is the principled
    degradation target under deadline pressure (Birn et al.).
    """
    cmatch = np.asarray(cmatch)[: g.nc]
    rmatch = np.asarray(rmatch)[: g.nr]
    cols, rows = g.ecol[: g.nnz], g.cadj[: g.nnz]
    return not bool(np.any((cmatch[cols] == UNMATCHED)
                           & (rmatch[rows] == UNMATCHED)))
