"""Paper Figures 3-5: speedup profiles and performance profiles of the best
variant vs the sequential algorithms, original + RCP sets."""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core import MatcherConfig
from .common import geomean, prepared_instances, time_matcher, time_sequential

BEST = MatcherConfig(algo="apfb", kernel="gpubfs_wr")


def run(scale: str = "tiny") -> List[str]:
    rows = ["fig35.set,instance,speedup_vs_HK,speedup_vs_PFP,speedup_vs_HKC"]
    summary = []
    for rcp in (False, True):
        label = "RCP" if rcp else "orig"
        speeds = {"HK": [], "PFP": [], "HK-C": []}
        for name, (g, cm0, rm0) in prepared_instances(scale, rcp).items():
            t, _ = time_matcher(g, BEST, cm0, rm0, repeat=2)
            seq = time_sequential(g, cm0.copy(), rm0.copy())
            for k in speeds:
                speeds[k].append(seq[k] / t)
            rows.append(f"{label},{name},{seq['HK']/t:.2f},"
                        f"{seq['PFP']/t:.2f},{seq['HK-C']/t:.2f}")
        # profile: fraction of instances with speedup >= 1 (paper's fig3 y@x=0)
        frac = {k: float(np.mean(np.asarray(v) >= 1.0))
                for k, v in speeds.items()}
        summary.append(
            f"{label},GEOMEAN,{geomean(speeds['HK']):.2f},"
            f"{geomean(speeds['PFP']):.2f},{geomean(speeds['HK-C']):.2f}")
        summary.append(
            f"{label},FRAC_FASTER,{frac['HK']:.2f},{frac['PFP']:.2f},"
            f"{frac['HK-C']:.2f}")
    return rows + summary


if __name__ == "__main__":
    print("\n".join(run()))
