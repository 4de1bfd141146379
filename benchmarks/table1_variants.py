"""Paper Table 1: geometric-mean runtime of the matcher variants
(APFB/APsB x GPUBFS/GPUBFS-WR; the paper's MT/CT thread-grid axis has no
counterpart in the XLA sweep) on the original and RCP sets."""
from __future__ import annotations

from typing import List

from repro.core import VARIANTS
from .common import geomean, prepared_instances, time_matcher


def run(scale: str = "tiny") -> List[str]:
    rows = ["table1.set,variant,geomean_ms,total_phases"]
    for rcp in (False, True):
        label = "RCP_S1" if rcp else "O_S1"
        insts = prepared_instances(scale, rcp)
        for cfg in VARIANTS:
            times, phases = [], 0
            for name, (g, cm0, rm0) in insts.items():
                t, st = time_matcher(g, cfg, cm0, rm0, repeat=2)
                times.append(t)
                phases += st["phases"]
            rows.append(f"{label},{cfg.name},{geomean(times)*1e3:.2f},{phases}")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
