"""The plain reference and the comparison that decides ``correct``.

Independent of the program: numpy and scipy only, on the benchmark's own
copy of each graph (:class:`traffic.generate.HostGraph`).  A maximum
cardinality matching is not unique, but its size is, so a result passes when
it is a valid matching of its graph (every matched pair an edge, ``cmatch``
and ``rmatch`` agreeing, every entry ``-1`` or in range) and as large as
scipy's Hopcroft–Karp matching of the same graph.

Three numbers are compared, each with the limit 0 (an exact comparison):

* ``invalid_entries``: matching entries that break validity, summed over
  every result checked;
* ``card_gap``: the largest ``|reference size - result size|`` over them;
* ``unanswered``: results due that never came or came as an error.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

LIMITS = {"invalid_entries": 0, "card_gap": 0, "unanswered": 0}


def _scipy_csr(g):
    import scipy.sparse as sp
    return sp.csr_matrix((np.ones(g.nnz, np.int8), g.cadj, g.cxadj),
                         shape=(g.nc, g.nr))


def reference_matching(g) -> np.ndarray:
    """scipy's maximum matching: ``match[c]`` is column ``c``'s row or -1."""
    from scipy.sparse.csgraph import maximum_bipartite_matching
    return np.asarray(maximum_bipartite_matching(_scipy_csr(g),
                                                 perm_type="column"))


def maximum_size(g) -> int:
    return int((reference_matching(g) >= 0).sum())


def control_matching(g) -> Tuple[np.ndarray, np.ndarray]:
    """The control: the reference's matching with one matched pair taken
    out.  Valid, and one short of maximum: the guarantee it breaks is the
    configuration's "maximum cardinality", by the least amount possible."""
    cm = reference_matching(g).astype(np.int64)
    rm = np.full(g.nr, -1, np.int64)
    cols = np.flatnonzero(cm >= 0)
    rm[cm[cols]] = cols
    if cols.size:
        rm[cm[cols[0]]] = -1
        cm[cols[0]] = -1
    return cm, rm


def edge_keys(g) -> np.ndarray:
    """Sorted ``column * nr + row`` of every edge (the CSR order)."""
    cols = np.repeat(np.arange(g.nc, dtype=np.int64), np.diff(g.cxadj))
    return cols * g.nr + g.cadj.astype(np.int64)


def violations(g, cmatch, rmatch, keys: Optional[np.ndarray] = None
               ) -> Tuple[int, int]:
    """(entries that break validity, matched columns) of one result."""
    cm = np.asarray(cmatch).astype(np.int64)
    rm = np.asarray(rmatch).astype(np.int64)
    if cm.shape != (g.nc,) or rm.shape != (g.nr,):
        return g.nc + g.nr, 0
    bad = int(np.sum((cm < -1) | (cm >= g.nr)))
    bad += int(np.sum((rm < -1) | (rm >= g.nc)))
    cols = np.flatnonzero((cm >= 0) & (cm < g.nr))
    rows = cm[cols]
    bad += int(np.sum(rm[rows] != cols))              # asymmetric
    mrows = np.flatnonzero((rm >= 0) & (rm < g.nc))
    bad += int(np.sum(cm[rm[mrows]] != mrows))
    if keys is None:
        keys = edge_keys(g)
    want = cols * g.nr + rows
    if keys.size:
        pos = np.minimum(np.searchsorted(keys, want), keys.size - 1)
        bad += int(np.sum(keys[pos] != want))          # not an edge
    else:
        bad += int(want.size)
    return bad, int(cols.size)


class Comparison:
    """Accumulates the compared numbers over every result of a run."""

    def __init__(self):
        self.values: Dict[str, int] = {k: 0 for k in LIMITS}
        self.checked = 0

    def add(self, g, cmatch, rmatch, max_size: int,
            keys: Optional[np.ndarray] = None) -> bool:
        """Compare one result; True when it passes on its own."""
        bad, size = violations(g, cmatch, rmatch, keys)
        gap = abs(max_size - size)
        self.values["invalid_entries"] += bad
        self.values["card_gap"] = max(self.values["card_gap"], gap)
        self.checked += 1
        return bad <= LIMITS["invalid_entries"] and gap <= LIMITS["card_gap"]

    def add_unanswered(self, n: int = 1) -> None:
        self.values["unanswered"] += n

    @property
    def correct(self) -> bool:
        return all(self.values[k] <= LIMITS[k] for k in LIMITS)

    def report(self) -> Dict[str, dict]:
        return {k: {"value": self.values[k], "limit": LIMITS[k]}
                for k in LIMITS}
