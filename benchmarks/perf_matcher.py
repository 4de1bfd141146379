"""§Perf iteration log for the matcher itself (hypothesis -> change ->
measure).  Run at --scale small for meaningful times:

    PYTHONPATH=src python -m benchmarks.perf_matcher [small|large]

Covers: (1) paper-faithful variant baselines, (2) the beyond-paper
bounded-tail APFB sweep (interpolating APsB <-> APFB), (3) level/phase work
accounting that explains the wins (the per-level microbench lives in
benchmarks/perf_smoke.py).
"""
from __future__ import annotations

import sys
from typing import List

from repro.core import MatcherConfig
from .common import geomean, prepared_instances, time_matcher


def run(scale: str = "small") -> List[str]:
    rows = ["perf_matcher.set,config,geomean_ms,phases_total"]
    for rcp in (False, True):
        label = "RCP" if rcp else "orig"
        insts = prepared_instances(scale, rcp)
        cases = [
            ("apsb-wr (paper)", MatcherConfig(algo="apsb",
                                              kernel="gpubfs_wr",
                                              wr_exact=True)),
            ("apfb-wr (paper best)", MatcherConfig(algo="apfb",
                                                   kernel="gpubfs_wr")),
            ("apfb-plain tail=0", MatcherConfig(algo="apfb",
                                                kernel="gpubfs")),
            ("apfb-wr tail=2", MatcherConfig(algo="apfb", kernel="gpubfs_wr",
                                             tail_levels=2)),
            ("apfb-wr tail=4", MatcherConfig(algo="apfb", kernel="gpubfs_wr",
                                             tail_levels=4)),
            ("apfb-plain tail=2", MatcherConfig(algo="apfb", kernel="gpubfs",
                                                tail_levels=2)),
            ("apfb-plain tail=4", MatcherConfig(algo="apfb", kernel="gpubfs",
                                                tail_levels=4)),
        ]
        for cname, cfg in cases:
            times, phases = [], 0
            for name, (g, cm0, rm0) in insts.items():
                t, st = time_matcher(g, cfg, cm0, rm0, repeat=2)
                times.append(t)
                phases += st["phases"]
            rows.append(f"{label},{cname},{geomean(times)*1e3:.2f},{phases}")
    return rows


if __name__ == "__main__":
    print("\n".join(run(sys.argv[1] if len(sys.argv) > 1 else "small")))
