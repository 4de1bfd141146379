#!/usr/bin/env python3
"""The control of a cell's comparison: it has to come out as not correct.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

The control is the reference put in the program's place with one of the
configuration's guarantees broken: each answer is scipy's maximum matching
with one matched pair taken out (:func:`reference.control_matching`), a
valid matching one short of maximum.  It runs on the cell's own inputs, at
the cell's own size, made from each seed exactly as a run makes them, and
goes through the same comparison as a run's answers, as many of them as a
run of ``--seconds`` compares.  Prints each seed's compared numbers beside
their limits; exits 0 when the control fails the comparison on every seed.

It imports nothing of the program, so it also runs in a checkout without
``src/``; graph mixes are made on JAX's default device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_readings(mix: dict, seed: int, seconds: float) -> dict:
    """The compared numbers of the control on one seed's inputs."""
    from traffic import generate
    import reference
    cmp = reference.Comparison()
    if mix["kind"] == "graph":
        for host in generate.graphs(mix, seed):
            cmp.add(host, *reference.control_matching(host),
                    reference.maximum_size(host))
    else:
        for req in generate.requests(mix, seconds, seed):
            cmp.add(req.graph, *reference.control_matching(req.graph),
                    reference.maximum_size(req.graph))
    return {"seed": seed, "checked": cmp.checked, "correct": cmp.correct,
            "checks": cmp.report()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length whose requests are compared "
                         "(default: the benchmark's run_seconds)")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    seconds = args.seconds or spec["run_seconds"]
    sys.path.insert(0, BENCH)
    from traffic import generate
    mix = generate.load(cell["traffic"])
    failed_everywhere = True
    for seed in args.seeds:
        r = control_readings(mix, seed, seconds)
        print(json.dumps(r), flush=True)
        failed_everywhere &= not r["correct"]
    print(f"control {'fails' if failed_everywhere else 'PASSES'} the "
          f"comparison of {args.workload} on seeds {args.seeds}")
    return 0 if failed_everywhere else 1


if __name__ == "__main__":
    sys.exit(main())
