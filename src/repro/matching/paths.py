"""Solve-path registry: every end-to-end way this package computes a matching.

The same APFB/APsB solve loop reaches the device through three execution
paths — the XLA push sweep (``jnp``), the direction-optimizing engine
(``dirop``: push or a compact pull over the CSC mirror, per level), and the
edge-sharded ``shard_map`` program.  All must produce a maximum matching on
every instance; the single-device ones must be *bit-identical*.  This
registry gives that family one enumerable surface so the differential fuzz
harness (:mod:`repro.corpus.verify`), the parity tests and the benchmarks
stop hand-rolling their own config lists that drift apart.

Each :class:`SolvePath` is a named set of :class:`MatcherConfig` overrides
plus how to build its matcher; :meth:`SolvePath.run_host` is the
host-graph-in, host-matching-out closure the harness calls.  Tests can
:func:`register_solve_path` throwaway paths (e.g. a deliberately broken
runner to exercise the mismatch artifact machinery) and must unregister
them again.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, Optional, Tuple

import numpy as np

from repro.core.csr import BipartiteCSR

from .api import Matcher
from .config import MatcherConfig
from .device_csr import DeviceCSR
from .sharded import ShardedMatcher


@dataclasses.dataclass(frozen=True)
class SolvePath:
    """One registered end-to-end solve configuration.

    ``overrides`` are :func:`dataclasses.replace` fields applied on top of a
    caller's base :class:`MatcherConfig` (so a path composes with any paper
    variant); ``sharded`` selects :class:`ShardedMatcher` over the mesh;
    ``runner``, when set, replaces the standard device round-trip entirely —
    a test hook for injecting broken paths into the fuzz harness.
    """
    name: str
    overrides: Mapping[str, object]
    sharded: bool = False
    runner: Optional[Callable] = None

    def configure(self, base: MatcherConfig = MatcherConfig()
                  ) -> MatcherConfig:
        return dataclasses.replace(base, **dict(self.overrides))

    def matcher(self, base: MatcherConfig = MatcherConfig(),
                warm_start: str = "cheap", mesh=None) -> Matcher:
        cfg = self.configure(base)
        if self.sharded:
            import jax
            if mesh is None:
                mesh = jax.make_mesh((jax.device_count(),), ("data",))
            return ShardedMatcher(mesh, "data", cfg, warm_start)
        return Matcher(cfg, warm_start)

    def run_host(self, g: BipartiteCSR,
                 base: MatcherConfig = MatcherConfig(),
                 warm_start: str = "cheap", mesh=None,
                 pad: Optional[Tuple[int, int, int]] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Host graph in, host ``(cmatch, rmatch)`` out (padding stripped).

        ``pad=(nc, nr, nnz_cap)`` places the instance in a declared size
        bucket so many instances share one compiled program — the fuzz
        harness's compile budget depends on it.  Padded vertices are
        isolated, so the returned true-size matching is unaffected.
        """
        if self.runner is not None:
            return self.runner(g, base=base, warm_start=warm_start)
        graph = DeviceCSR.from_host(g)
        if pad is not None:
            nc, nr, cap = pad
            graph = graph.pad_vertices(nc, nr).pad_to(cap)
        if self.configure(base).dirop:
            graph = graph.with_csc()       # sharded dirop: mirror pre-shard
        state = self.matcher(base, warm_start, mesh).run(graph)
        cm, rm = state.to_host()
        return cm[: g.nc], rm[: g.nr]


SOLVE_PATHS: Dict[str, SolvePath] = {}


def register_solve_path(name: str, overrides: Optional[Mapping] = None, *,
                        sharded: bool = False,
                        runner: Optional[Callable] = None) -> SolvePath:
    path = SolvePath(name, dict(overrides or {}), sharded, runner)
    SOLVE_PATHS[name] = path
    return path


def unregister_solve_path(name: str) -> None:
    SOLVE_PATHS.pop(name, None)


def solve_path_names() -> Tuple[str, ...]:
    return tuple(SOLVE_PATHS)


# the built-in paths — one per frontier-sweep execution strategy.  The pull
# geometry (pull_cap / pull_dmax) stays on auto: its resolution is part of
# what the differential harness must cover.
register_solve_path("jnp", {})
register_solve_path("dirop", dict(dirop=True))
register_solve_path("sharded", {}, sharded=True)
