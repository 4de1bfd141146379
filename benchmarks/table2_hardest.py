"""Paper Table 2: per-instance runtimes of the best variant
(APFB-GPUBFS-WR-CT, as in the paper) vs sequential HK / PFP / HK-C,
original + permuted instances."""
from __future__ import annotations

from typing import List

from repro.core import MatcherConfig
from .common import prepared_instances, time_matcher, time_sequential

BEST = MatcherConfig(algo="apfb", kernel="gpubfs_wr")


def run(scale: str = "tiny") -> List[str]:
    rows = ["table2.set,instance,ours_ms,HK_ms,PFP_ms,PR_ms,HKC_ms,"
            "speedup_vs_best_seq"]
    for rcp in (False, True):
        label = "RCP" if rcp else "orig"
        for name, (g, cm0, rm0) in prepared_instances(scale, rcp).items():
            t, st = time_matcher(g, BEST, cm0, rm0, repeat=2)
            seq = time_sequential(g, cm0.copy(), rm0.copy())
            best_seq = min(seq.values())
            rows.append(
                f"{label},{name},{t*1e3:.2f},{seq['HK']*1e3:.2f},"
                f"{seq['PFP']*1e3:.2f},{seq['PR']*1e3:.2f},"
                f"{seq['HK-C']*1e3:.2f},{best_seq/t:.2f}")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
