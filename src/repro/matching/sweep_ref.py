"""Pure-jnp oracles for one BFS level's sweep: the reference the solver's
sweeps (:func:`repro.matching.solve._winner_xla` and the pull sweeps) are
held bit-identical to in the tests.

Semantics (one BFS level of the paper's Alg. 2 / Alg. 4, proposal half):
for every edge e = (c, r):
  active  = bfs[c] == level            (and, WR: bfs[root[c]] >= L0-1)
  propose = active and ( (rmatch[r] >= 0 and bfs[rmatch[r]] == L0-1)
                         or rmatch[r] == -1 )
  out[e]  = c if propose else IINF

:func:`frontier_expand_ref` is that per-edge proposal sweep alone;
:func:`frontier_expand_fused_ref` composes it with the deterministic per-row
min-merge ("first writer wins" = lowest proposing column): a ``(nr+1,)``
winner vector with IINF in every unreached row and in the trailing sentinel
slot.  Written straight from the formula, independent of the solver's
factored sweep.
"""
from __future__ import annotations

import jax.numpy as jnp

UNVISITED = jnp.int32(1)
IINF = jnp.int32(2**30)


def frontier_expand_ref(ecol, cadj, bfs, root, rmatch, level):
    nc = bfs.shape[0] - 1
    active = bfs[ecol] == level
    if root is not None:
        active &= bfs[root[ecol]] >= UNVISITED
    cm = rmatch[cadj]
    col_unvis = bfs[jnp.clip(cm, 0, nc)] == UNVISITED
    target = active & ((cm >= 0) & col_unvis | (cm == -1))
    return jnp.where(target, ecol, IINF)


def frontier_expand_fused_ref(ecol, cadj, bfs, root, rmatch, level):
    """Proposals + per-row min-merge: the per-row winner oracle."""
    nr = rmatch.shape[0] - 1
    prop = frontier_expand_ref(ecol, cadj, bfs, root, rmatch, level)
    rows = jnp.where(prop < IINF, cadj, jnp.int32(nr))
    win = jnp.full(nr + 1, IINF, jnp.int32).at[rows].min(prop)
    return win.at[nr].set(IINF)


def frontier_expand_pull_ref(radj, erow, bfs, root, rmatch, level):
    """Pull oracle: the same min-merge over the row-sorted (CSC) edge view —
    the proposal predicate is per-edge and min is the merge, so this is
    definitionally the winner oracle on permuted arrays."""
    return frontier_expand_fused_ref(radj, erow, bfs, root, rmatch, level)
