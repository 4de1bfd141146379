"""Paper Figure 2: per-phase BFS level counts for APsB vs APFB.

Instrumented re-execution of the phase loop (python outer loop over the same
jitted level-expansion) on a grid instance (long paths, Hamrle3-like regime)
and a random instance (short paths, Delaunay-like regime is the converse).
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from repro.core import cheap_matching_jax
from repro.core.matcher import (FOUND, L0, NEG, UNVISITED, _alternate,
                                _cardinality, _expand_level, _fix_matching)
from repro.graphs import grid_graph, random_bipartite


def instrumented_phases(g, algo: str, max_phases: int = 10_000):
    """Returns list of per-phase BFS level counts (the y-axis of Fig. 2)."""
    nc, nr = g.nc, g.nr
    cm0, rm0 = cheap_matching_jax(g)
    cmatch = jnp.concatenate([jnp.asarray(cm0), jnp.array([-3], jnp.int32)])
    rmatch = jnp.concatenate([jnp.asarray(rm0), jnp.array([-3], jnp.int32)])
    ecol, cadj = jnp.asarray(g.ecol), jnp.asarray(g.cadj)
    cols = jnp.arange(nc + 1, dtype=jnp.int32)
    levels_per_phase: List[int] = []
    for _ in range(max_phases):
        bfs = jnp.where(cmatch >= 0, UNVISITED, L0).at[nc].set(NEG)
        root = jnp.where(cmatch >= 0, jnp.int32(nc), cols)
        pred = jnp.full(nr + 1, jnp.int32(nc), jnp.int32)
        level = L0
        aug = False
        nlev = 0
        while True:
            bfs, root, pred, rmatch, ins, aug_l = _expand_level(
                ecol, cadj, bfs, root, pred, rmatch, level, wr=True,
                wr_exact=False)
            nlev += 1
            aug = aug or bool(aug_l)
            level = level + 1
            if algo == "apsb" and aug:
                break
            if not bool(ins):
                break
        levels_per_phase.append(nlev)
        if not aug:
            break
        card0 = _cardinality(cmatch)
        mask = rmatch == -2
        cm1, rm1, _ = _alternate(cmatch, rmatch, pred,
                                 mask, jnp.int32(2 * (min(nc, nr) + 2)))
        cm1, rm1 = _fix_matching(cm1, rm1)
        if int(_cardinality(cm1)) <= int(card0):
            first = jnp.argmax(mask)
            one = jnp.zeros(nr + 1, bool).at[first].set(jnp.any(mask))
            cm1, rm1, _ = _alternate(cmatch, jnp.where(mask, -1, rmatch),
                                     pred, one,
                                     jnp.int32(2 * (min(nc, nr) + 2)))
            cm1, rm1 = _fix_matching(cm1, rm1)
        cmatch, rmatch = cm1, rm1
    return levels_per_phase


def run(scale: str = "tiny") -> List[str]:
    side = {"tiny": 24, "small": 64, "large": 128}[scale]
    n = {"tiny": 1024, "small": 16384, "large": 1 << 18}[scale]
    graphs = {
        "grid(road-like)": grid_graph(side),
        "rand(delaunay-like)": random_bipartite(n, n, 4.0, seed=2),
    }
    rows = ["fig2.graph,algo,phases,total_levels,levels_per_phase"]
    for gname, g in graphs.items():
        for algo in ("apfb", "apsb"):
            lv = instrumented_phases(g, algo)
            prof = ";".join(str(x) for x in lv[:40])
            rows.append(f"{gname},{algo},{len(lv)},{sum(lv)},{prof}")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
