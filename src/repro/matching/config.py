"""Matcher variant configuration (the paper's four-variant matrix)."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class MatcherConfig:
    """One of the paper's four variants (2 algos x 2 BFS kernels), plus the
    solve-loop and frontier-sweep knobs.

    The sweep knobs (``dirop`` .. ``pull_dmax``) select *how* the per-level
    frontier expansion runs; they never change the matching the solver
    returns — every path is bit-identical to the deterministic min-merge
    semantics (asserted in tests/test_frontier_paths.py).  All of them are
    fields of this frozen dataclass, so every one lands in the compile-cache
    key by construction — there are no untracked execution knobs hiding in
    kwarg defaults.
    """

    algo: str = "apfb"          # "apfb" (HKDW-like) | "apsb" (HK-like)
    kernel: str = "gpubfs_wr"   # "gpubfs" | "gpubfs_wr"
    wr_exact: bool = False      # the APsB-GPUBFS-WR refinement (negative-row encoding)
    max_phases: int = 0         # 0 = until maximum (bounded internally)
    # When a positive max_phases budget exhausts before the solver certifies
    # the matching maximum, run one extra greedy augmentation round
    # (the `cheap` warm start's speculative pass, Birn-et-al maximal
    # matching) over the truncated result so the degraded answer is at
    # least MAXIMAL — no free column shares an edge with a free row.  The
    # serving degradation ladder turns this on for deadline-bounded solves;
    # it stays off by default because the corpus heuristic replay
    # (corpus/heuristic.py) steps the solver with max_phases=1 and its
    # CI-gated trajectories must not change under it.
    degrade_maximal: bool = False
    # beyond-paper: bound the BFS tail after the first augmenting level.
    # 0 = paper-faithful (APsB stops immediately, APFB exhausts the
    # frontier); k>0 on APFB = expand at most k more levels — interpolates
    # between the paper's two drivers (benchmarks/perf_matcher.py).
    tail_levels: int = 0
    # -- beyond-paper: direction-optimizing frontier engine (default off) ---
    # Beamer-style push/pull switching per BFS level, in-jit: estimate the
    # frontier's outgoing edges (push work actually useful) against the
    # unreached rows' incoming edges (pull work) and `lax.cond`-dispatch a
    # pull sweep over the CSC mirror (`DeviceCSR.with_csc`) when
    #     frontier_edges * dirop_alpha > pull_edges,
    # staying in pull — hysteresis — while
    #     frontier_edges * dirop_beta  > pull_edges   (beta > alpha).
    # On one device the pull sweep is a compact row-gather of
    # O(pull_cap * pull_dmax) (0 = auto, see resolve_cap/resolve_dmax) and
    # additionally requires the unreached rows to fit that geometry; in
    # ShardedMatcher it is the XLA sweep streamed over the CSC shard (the
    # one per-level pmin unchanged).  Either way the winners are
    # bit-identical to the push sweep, so the dispatch never changes the
    # matching.
    # The alpha/beta defaults come from the committed corpus sweep
    # (BENCH_PR7.json, ``corpus.alpha_sweep`` / ``_summary`` rows, tiny
    # scale; regenerate via benchmarks/run.py --update-baseline): 8/32 ties
    # the best geomean across the 10-family corpus (0.997 vs push-only) and
    # is the clear winner on the long-diameter families (grid 0.699).  That
    # sweep prices a pull that skips the edge tiles holding no unreached
    # row (corpus/heuristic.py's work model), which neither pull sweep here
    # does; the per-family rows are gated in CI (``corpus.heuristic``), so
    # changing these defaults without refreshing the baseline fails the
    # bench gate.
    dirop: bool = False
    dirop_alpha: float = 8.0
    dirop_beta: float = 32.0
    pull_cap: int = 0
    pull_dmax: int = 0

    def __post_init__(self):
        assert self.algo in ("apfb", "apsb")
        assert self.kernel in ("gpubfs", "gpubfs_wr")
        if self.wr_exact:
            assert self.kernel == "gpubfs_wr"
        assert self.pull_cap >= 0 and self.pull_dmax >= 0, \
            (self.pull_cap, self.pull_dmax)
        assert self.dirop_alpha > 0 and self.dirop_beta >= self.dirop_alpha, \
            ("hysteresis needs 0 < dirop_alpha <= dirop_beta",
             self.dirop_alpha, self.dirop_beta)

    @staticmethod
    def resolve_cap(auto_or_value: int, n: int) -> int:
        """The 0 = auto row capacity of the compact pull sweep: n/8 clamped
        to [64, 1024] (well under any dense O(nnz) sweep), ``n`` = nr."""
        return auto_or_value or max(64, min(1024, n // 8))

    @staticmethod
    def resolve_dmax(auto_or_value: int) -> int:
        """The 0 = auto per-row degree bound of the compact pull sweep."""
        return auto_or_value or 8

    @property
    def name(self) -> str:
        return f"{self.algo}-{self.kernel}" + ("-exact" if self.wr_exact
                                               else "")


VARIANTS = tuple(
    MatcherConfig(algo=a, kernel=k,
                  wr_exact=(a == "apsb" and k == "gpubfs_wr"))
    for a in ("apfb", "apsb")
    for k in ("gpubfs", "gpubfs_wr")
)
