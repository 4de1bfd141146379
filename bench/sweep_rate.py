#!/usr/bin/env python3
"""Find a service's knee: the highest offered rate with no growing backlog.

    python3 bench/sweep_rate.py --config assign-service --traffic mixed-steady \
        --rates 2 4 6 8 --seconds 20 --seed 1

One process, one chip.  For each rate, in the order given, the traffic mix
is offered open loop at that rate for ``--seconds`` through a fresh service
built by the configuration's entry (``bench/entries/<entry>.py``), every
answer is awaited and checked against the reference, and one JSON line
reports what the window saw: answers per second, latency percentiles from
each request's due time, and the median latency of the first and last
quarter of the requests.  A rate is sustained when the window answers at
least 95% of what it offers and the last quarter waits no more than twice
as long as the first and half a second more; the knee is the highest rate
below the first one that is not.  The last line is ``{"knee": <rate>}``.
Exits 1 on a host without a TPU, and 3 when an answer is wrong.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def quarter_p50(lat, first: bool) -> float:
    q = max(1, len(lat) // 4)
    part = sorted(lat[:q] if first else lat[-q:])
    return part[len(part) // 2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(run.ROOT,
                                                           ".jax_cache")
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != run.PLATFORM:
        print(f"sweep_rate: needs a {run.PLATFORM} chip; JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import reference
    from traffic import generate
    config = run.load_json(os.path.join(run.BENCH, "configs",
                                        f"{args.config}.json"))
    entry = run.load_module(os.path.join(run.BENCH, "entries",
                                         f"{config['entry']}.py"),
                            "bench_entry")
    base = generate.load(args.traffic)
    knee, wrong = None, False
    for rate in args.rates:
        mix = dict(base, rate_per_s=rate)
        cell = entry.Cell(config, mix, args.seed, args.seconds, run.log)
        cell.window(args.seconds)
        cell.settle()
        cmp = reference.Comparison()
        cell.compare(cmp)
        wrong |= not cmp.correct
        lat = list(cell.latencies())
        answered = cell.outcomes()["answered_s"]
        in_window = sum(1 for t in answered
                        if t is not None and t <= args.seconds)
        s = sorted(lat)
        first, last = quarter_p50(lat, True), quarter_p50(lat, False)
        growing = bool(last > 2 * first + 0.5
                       or in_window < 0.95 * rate * args.seconds)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "answered_per_s": in_window / args.seconds,
            "p50_ms": 1e3 * s[len(s) // 2],
            "p95_ms": 1e3 * s[max(0, -(-95 * len(s) // 100) - 1)],
            "first_quarter_p50_ms": 1e3 * first,
            "last_quarter_p50_ms": 1e3 * last,
            "growing_backlog": growing, "correct": bool(cmp.correct)}),
            flush=True)
        if growing:
            break
        knee = rate
    print(json.dumps({"knee": knee, "device": devices[0].device_kind}))
    return 3 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
