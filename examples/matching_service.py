"""Minimal end-to-end matching service demo: warmup, a mixed burst, metrics.

Builds a :class:`repro.serving.MatchingService` over one declared size
bucket, AOT-compiles its (bucket x config x warm-start x batch) grid, fires
a burst of mixed-family graphs at it, and prints per-request stats plus the
service counters.  Runs on 4 simulated host devices so the oversize ->
ShardedMatcher admission route is exercised too:

    PYTHONPATH=src python examples/matching_service.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402

from repro.core import validate_matching                                    # noqa: E402
from repro.graphs import (grid_graph, kron_graph, random_bipartite,        # noqa: E402
                          scaled_free)
from repro.matching import DeviceCSR, Matcher, MatcherConfig               # noqa: E402
from repro.serving import Bucketizer, MatchingService, SizeBucket          # noqa: E402


def main():
    cfg = MatcherConfig(algo="apfb", kernel="gpubfs_wr")
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    service = MatchingService(
        bucketizer=Bucketizer((SizeBucket(256, 256, 2048),),
                              oversize="shard"),
        config=cfg, warm_start="cheap",
        max_batch=4, max_delay_ms=2.0, mesh=mesh)

    print(service.warm_up())                 # AOT: traffic never compiles

    burst = {
        "random": random_bipartite(200, 180, 3.0, seed=1),
        "kron": kron_graph(7, 6, seed=2),
        "grid": grid_graph(12),
        "free": scaled_free(150, 160, 4.0, seed=3),
        "oversize": random_bipartite(400, 400, 4.0, seed=4),   # -> sharded
    }
    futures = {name: service.submit(g) for name, g in burst.items()}

    for name, fut in futures.items():
        res = fut.result(timeout=300)
        g = burst[name]
        cm, rm = res.matching()
        assert validate_matching(g, cm, rm) == res.cardinality
        direct = Matcher(cfg, warm_start="cheap").run(
            DeviceCSR.from_host(g).bucketed())
        assert res.cardinality == int(direct.cardinality), name
        print(f"{name:>9}: route={res.route:<7} |M|={res.cardinality:4d} "
              f"batch={res.batch_size} wait={res.queue_wait_s * 1e3:6.1f} ms "
              f"latency={res.latency_s * 1e3:6.1f} ms")

    snap = service.metrics.snapshot()
    service.close()
    print(f"service: {snap['submitted']} submitted, "
          f"{snap['dispatches']} dispatches, "
          f"occupancy {snap['occupancy']:.2f}, "
          f"pad-waste {snap['pad_edge_waste']:.2f}, "
          f"compile {snap['compile_hits']}h/{snap['compile_misses']}m")
    print("OK — every request matched the direct Matcher, one dispatch "
          "per flushed bucket")


if __name__ == "__main__":
    main()
