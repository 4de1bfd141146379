"""The paper's stated future work: distributed-memory matching.

``ShardedMatcher`` — edge-partitioned APFB over a device mesh, one ``pmin``
collective per BFS level, same solve loop as the single-device ``Matcher``
(see docs/architecture.md).  Runs on every device JAX sees: the chips of a
TPU host, or 8 simulated devices on a CPU-only machine:

    PYTHONPATH=src python examples/distributed_matching.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

from repro.core import maximum_cardinality, validate_matching               # noqa: E402
from repro.graphs import random_bipartite                                  # noqa: E402
from repro.matching import (DeviceCSR, Matcher, MatcherConfig,             # noqa: E402
                            ShardedMatcher)


def main():
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    g = random_bipartite(4096, 4096, 6.0, seed=0)
    graph = DeviceCSR.from_host(g).shard(mesh, "data")
    print(f"graph: {g.nc}x{g.nr}, {g.nnz} edges, "
          f"sharded over {mesh.size} devices "
          f"({graph.nnz_pad // mesh.size} edges/device)")
    cfg = MatcherConfig(algo="apfb", kernel="gpubfs_wr")
    sharded = ShardedMatcher(mesh, config=cfg, warm_start="cheap")
    state = sharded.run(graph)            # warm start + solve, one program
    cmatch, rmatch = state.to_host()
    card = validate_matching(g, cmatch, rmatch)
    opt = maximum_cardinality(g)
    stats = sharded.stats(state).as_dict()
    print(f"distributed {stats['variant']}: |M| = {card} "
          f"(optimal {opt}) in {stats['phases']} phases")
    assert card == opt
    single = Matcher(cfg, warm_start="cheap").run(DeviceCSR.from_host(g))
    assert int(single.cardinality) == card
    print("OK — one pmin collective per BFS level, state replicated, "
          "edges sharded; cardinality matches the single-device Matcher")


if __name__ == "__main__":
    main()
