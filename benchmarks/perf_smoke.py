"""§Perf-smoke: the level-sweep microbench + solve bench behind the repo's
committed perf baseline (``BENCH_PR7.json``).

Every row carries a machine-portable ``rel`` ratio (path time over the jnp
path's time on the same input) so the CI regression gate compares relative
numbers rather than absolute wall-clock across hosts; the gate reads the
``solve`` set — per-graph sub-millisecond sweep rows are for humans, too
noisy to gate on.  Row sets:

* ``perf_smoke.sweep`` — ONE BFS level of frontier expansion (the O(nnz) hot
  loop of Figs. 2-5) through the jnp per-row winner oracle
  (:func:`repro.matching.sweep_ref.frontier_expand_fused_ref`), jitted.
* ``perf_smoke.solve`` — full ``Matcher.run`` geomeans per sweep config
  (the push sweep and the beyond-paper ``dirop`` dispatch).

Run directly, or through the harness + regression gate (refresh the
committed baseline with ``--update-baseline``, never by hand):

    python -m benchmarks.run --only perf_smoke --scale tiny \
        --json bench_new.json --baseline BENCH_PR7.json
    python -m benchmarks.run --only perf_smoke,corpus --scale tiny \
        --update-baseline BENCH_PR7.json --runs 3
"""
from __future__ import annotations

import sys
from typing import List

import jax
import jax.numpy as jnp

from repro.core import MatcherConfig, cheap_matching_jax
from repro.graphs import random_bipartite, scaled_free
from repro.matching.solve import level0_state
from repro.matching.sweep_ref import frontier_expand_fused_ref
from .common import geomean, time_call, time_matcher

_SCALES = {
    "tiny": [("rand", lambda: random_bipartite(512, 512, 3.0, seed=1)),
             ("free", lambda: scaled_free(512, 512, 4.0, seed=2))],
    "small": [("rand", lambda: random_bipartite(4096, 4096, 4.0, seed=1)),
              ("free", lambda: scaled_free(4096, 4096, 6.0, seed=2))],
    "large": [("rand", lambda: random_bipartite(20000, 20000, 4.0, seed=1)),
              ("free", lambda: scaled_free(20000, 20000, 6.0, seed=2))],
}


def _sweep_state(g):
    """Level-L0 BFS state from the cheap matching — built by the solver's
    own ``level0_state`` init so the probe cannot drift from what the
    solver actually sweeps."""
    cm, rm = cheap_matching_jax(g)
    cmj = jnp.concatenate([jnp.asarray(cm), jnp.array([-3], jnp.int32)])
    rmj = jnp.concatenate([jnp.asarray(rm), jnp.array([-3], jnp.int32)])
    bfs, root = level0_state(cmj)
    return jnp.asarray(g.ecol), jnp.asarray(g.cadj), bfs, root, rmj


# the same proposals + per-row min-merge oracle the solver's sweeps are
# tested against, jitted
_jnp_winner = jax.jit(frontier_expand_fused_ref)


def run(scale: str = "tiny") -> List[str]:
    backend = jax.default_backend()
    rows = ["perf_smoke.sweep,backend,mode,graph,path,block_edges,ms,rel"]
    reps = 20                       # sweeps per timed call: sub-ms sweeps
    for gname, build in _SCALES[scale]:
        g = build()
        ecol, cadj, bfs, root, rmj = _sweep_state(g)

        def timed(fn):
            fn()                    # compile (not timed)
            def many():
                for _ in range(reps):
                    out = fn()
                jax.block_until_ready(out)
            return time_call(many, repeat=5) / reps

        base = timed(lambda: _jnp_winner(ecol, cadj, bfs, root, rmj,
                                         jnp.int32(2)))
        rows.append(f"perf_smoke.sweep,{backend},xla,{gname},jnp,-,"
                    f"{base*1e3:.3f},1.000")

    rows.append("perf_smoke.solve,backend,mode,config,geomean_ms,rel")
    solve_cases = [
        ("jnp", MatcherConfig(algo="apfb", kernel="gpubfs_wr")),
        ("dirop", MatcherConfig(algo="apfb", kernel="gpubfs_wr",
                                dirop=True)),
    ]
    insts = [(n, b()) for n, b in _SCALES[scale]]
    prepared = [(n, g, *cheap_matching_jax(g)) for n, g in insts]
    base_ms = None
    for cname, cfg in solve_cases:
        times = [time_matcher(g, cfg, cm0, rm0, repeat=3)[0]
                 for _, g, cm0, rm0 in prepared]
        ms = geomean(times) * 1e3
        if base_ms is None:
            base_ms = ms
        rows.append(f"perf_smoke.solve,{backend},xla,{cname},{ms:.2f},"
                    f"{ms/base_ms:.3f}")
    return rows


if __name__ == "__main__":
    print("\n".join(run(sys.argv[1] if len(sys.argv) > 1 else "tiny")))
