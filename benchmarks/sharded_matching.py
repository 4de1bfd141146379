"""Sharded vs single-device matching sweep (``ShardedMatcher`` scale-out).

Times the edge-partitioned ``ShardedMatcher`` (one pmin per BFS level)
against the single-device ``Matcher`` on the same instances, asserting equal
cardinality.  On a real multi-chip mesh the sharded column shows the scale-out
curve; on a forced-host CPU mesh it mostly prices the collective overhead
(docs/architecture.md, "ShardedMatcher").

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.sharded_matching
"""
from __future__ import annotations

import os
import sys
from typing import List

if __name__ == "__main__":                 # forced mesh only when standalone:
    os.environ.setdefault(                 # under benchmarks.run JAX is
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")  # already up

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.graphs import random_bipartite  # noqa: E402
from repro.matching import (DeviceCSR, Matcher, MatcherConfig,  # noqa: E402
                            ShardedMatcher)

from .common import time_call  # noqa: E402

BEST = MatcherConfig(algo="apfb", kernel="gpubfs_wr")


def run(scale: str = "tiny") -> List[str]:
    sizes = {"tiny": (512, 2048), "small": (2048, 8192),
             "large": (8192, 32768)}[scale]
    ndev = jax.device_count()
    mesh = jax.make_mesh((ndev,), ("data",))
    single = Matcher(BEST, warm_start="cheap")
    sharded = ShardedMatcher(mesh, config=BEST, warm_start="cheap")
    rows = [f"sharded.n,devices,single_ms,sharded_ms,ratio,edges_per_dev"]
    for n in sizes:
        g = random_bipartite(n, n, 4.0, seed=7)
        graph = DeviceCSR.from_host(g)
        sharded_g = graph.shard(mesh, "data")
        s1 = single.run(graph)                       # warmup (compile)
        s2 = sharded.run(sharded_g)
        assert int(s1.cardinality) == int(s2.cardinality), \
            (n, int(s1.cardinality), int(s2.cardinality))
        t1 = time_call(
            lambda: jax.block_until_ready(single.run(graph).cmatch))
        t2 = time_call(
            lambda: jax.block_until_ready(sharded.run(sharded_g).cmatch))
        rows.append(f"{n},{ndev},{t1*1e3:.2f},{t2*1e3:.2f},"
                    f"{t1/max(t2, 1e-9):.2f},{sharded_g.nnz_pad // ndev}")
    return rows


if __name__ == "__main__":
    print("\n".join(run(sys.argv[1] if len(sys.argv) > 1 else "tiny")))
