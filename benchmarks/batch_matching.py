"""Batched serving throughput: ``match_many`` vs a per-graph loop.

The vmap path solves a whole bucket of independent graphs in one compiled
dispatch — the first step toward serving many concurrent matching requests
(ROADMAP north star).  Reports per-graph latency for both paths and the
resulting speedup, per batch size.

Caveat: under vmap the batched while_loops run in lock-step (every graph
pays for the slowest), so on a single CPU device the ratio can dip below 1;
the dispatch-count win shows on wide accelerators and in serving loops where
per-call overhead dominates.
"""
from __future__ import annotations

import time
from typing import List

import jax
import numpy as np

from repro.graphs import random_bipartite
from repro.matching import DeviceCSR, Matcher, MatcherConfig

BEST = MatcherConfig(algo="apfb", kernel="gpubfs_wr")


def run(scale: str = "tiny") -> List[str]:
    n = {"tiny": 256, "small": 2048, "large": 16384}[scale]
    pad = {"tiny": 1024, "small": 8192, "large": 65536}[scale]
    rows = ["batch.batch_size,loop_ms_per_graph,vmap_ms_per_graph,speedup"]
    matcher = Matcher(BEST, warm_start="cheap")
    for bs in (2, 8, 32):
        graphs = [DeviceCSR.from_host(
            random_bipartite(n, n, 4.0, seed=s, pad_to=pad))
            for s in range(bs)]
        batch = DeviceCSR.stack(graphs)
        # warmup both paths (compile)
        loop_out = [matcher.run(g) for g in graphs]
        jax.block_until_ready([s.cmatch for s in loop_out])
        many = matcher.run_many(batch)
        jax.block_until_ready(many.cmatch)
        assert (np.asarray(many.cardinality).tolist()
                == [int(s.cardinality) for s in loop_out])

        t0 = time.perf_counter()
        jax.block_until_ready([matcher.run(g).cmatch for g in graphs])
        t_loop = (time.perf_counter() - t0) / bs
        t0 = time.perf_counter()
        jax.block_until_ready(matcher.run_many(batch).cmatch)
        t_vmap = (time.perf_counter() - t0) / bs
        rows.append(f"{bs},{t_loop*1e3:.2f},{t_vmap*1e3:.2f},"
                    f"{t_loop/max(t_vmap, 1e-9):.2f}")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
