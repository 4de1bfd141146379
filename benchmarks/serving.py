"""Open-loop serving sweep: latency percentiles + occupancy vs offered load.

Replays a Poisson arrival trace of same-family graphs at increasing offered
rates against a warmed :class:`repro.serving.MatchingService` and reports,
per load level: p50/p99 end-to-end latency, batch occupancy, device
dispatches vs the naive 1-dispatch-per-request loop, and the flush-reason
mix.  The dispatch column is the acceptance check for the scheduler: the
batched path issues exactly ONE device dispatch per flushed bucket, so
``dispatches`` must be <= ``requests`` (and shrinks as load grows and
batches fill).

A second, past-saturation section drives offered load well beyond the
sweep's top rate against a *bounded* service (``max_queue`` + per-request
``deadline_s``) and reports the overload posture: shed rate, deadline-miss
rate, and p99 latency of the requests that were actually served.  Its
``serving.overload_summary`` row carries the loss rate as the portable
``rel`` column so overload behaviour is regression-gated by
``benchmarks/run.py --baseline`` exactly like perf.

    PYTHONPATH=src python -m benchmarks.serving [--scale tiny]
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.graphs import random_bipartite
from repro.matching import MatcherConfig
from repro.matching.device_csr import bucket_nnz
from repro.serving import (Bucketizer, MatchingService, QueueFullError,
                           SizeBucket, percentile)

BEST = MatcherConfig(algo="apfb", kernel="gpubfs_wr")


def run(scale: str = "tiny") -> List[str]:
    n, deg, requests = {"tiny": (192, 3.0, 48),
                        "small": (1024, 4.0, 128),
                        "large": (4096, 4.0, 256)}[scale]
    rates = {"tiny": (100.0, 500.0, 2500.0),
             "small": (50.0, 250.0, 1000.0),
             "large": (25.0, 100.0, 400.0)}[scale]
    v = 1 << max(8, int(np.ceil(np.log2(n))))
    bucket = SizeBucket(v, v, bucket_nnz(int(v * deg * 2)))
    pool = [random_bipartite(n, n, deg, seed=s) for s in range(16)]
    rng = np.random.default_rng(7)

    rows = ["serving.rate_rps,requests,p50_ms,p99_ms,occupancy,dispatches,"
            "req_per_dispatch,naive_dispatches,full,deadline,drain,"
            "compile_misses"]
    for rate in rates:
        service = MatchingService(bucketizer=Bucketizer((bucket,)),
                                  config=BEST, warm_start="cheap",
                                  max_batch=8, max_delay_ms=2.0)
        service.warm_up()                      # AOT: traffic never compiles
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=requests))
        t0 = time.perf_counter()
        futures = []
        for i in range(requests):
            lag = t0 + arrivals[i] - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            futures.append(service.submit(pool[i % len(pool)]))
        results = [f.result(timeout=300) for f in futures]
        service.drain()
        snap = service.metrics.snapshot()
        service.close()
        lat = [r.latency_s for r in results]
        p50 = percentile(lat, 50) * 1e3
        p99 = percentile(lat, 99) * 1e3
        dispatches = snap["dispatches"]
        assert dispatches <= requests, (dispatches, requests)
        rows.append(
            f"{rate:g},{requests},{p50:.2f},{p99:.2f},"
            f"{snap['occupancy']:.2f},{dispatches},"
            f"{requests / max(1, dispatches):.2f},{requests},"
            f"{snap['flushes_full']},{snap['flushes_deadline']},"
            f"{snap['flushes_drain']},{snap['compile_misses']}")

    rows += overload_rows(bucket, pool, requests, rates[-1] * 4, rng)
    return rows


def overload_rows(bucket, pool, requests: int, rate: float, rng) -> List[str]:
    """Past-saturation posture: offered load ~4x the sweep's top rate at a
    *bounded* service (``max_queue`` backpressure + per-request deadline).

    The detail row reports shed rate, deadline-miss rate, and p99 latency of
    the requests actually served; the ``serving.overload_summary`` row
    carries the total loss rate (shed + deadline misses, over offered) as
    the machine-portable ``rel`` the regression gate watches — measured
    values stay out of the summary's identity columns so baseline rows keep
    matching across runs.
    """
    service = MatchingService(bucketizer=Bucketizer((bucket,)),
                              config=BEST, warm_start="cheap",
                              max_batch=8, max_delay_ms=2.0,
                              max_queue=2 * 8, shed_policy="reject-newest")
    service.warm_up()
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=requests))
    t0 = time.perf_counter()
    futures = []
    shed = 0
    for i in range(requests):
        lag = t0 + arrivals[i] - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        try:
            futures.append(service.submit(pool[i % len(pool)],
                                          deadline_s=0.5))
        except QueueFullError:
            shed += 1
    service.drain()
    served = [f.result() for f in futures if f.exception(timeout=300) is None]
    snap = service.metrics.snapshot()
    service.close()
    misses = snap["deadline_misses"]
    loss = (shed + misses) / requests
    p99 = (percentile([r.latency_s for r in served], 99) * 1e3
           if served else float("nan"))
    return [
        "serving.overload,requests,served,shed_rate,deadline_miss_rate,"
        "p99_served_ms",
        f"{rate:g},{requests},{len(served)},{shed / requests:.3f},"
        f"{misses / requests:.3f},{p99:.2f}",
        "serving.overload_summary,requests,rel",
        f"{rate:g},{requests},{loss:.3f}",
    ]


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="tiny",
                    choices=["tiny", "small", "large"])
    print("\n".join(run(ap.parse_args().scale)))
