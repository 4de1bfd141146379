"""The composable matcher facade and vmap-batched ``match_many``.

:class:`Matcher` binds a :class:`MatcherConfig` variant plus a named warm
start and exposes a pure, jit-closed ``run(graph, state) -> MatchState``.
When no state is passed, warm-start initialization and the APFB/APsB solve
trace into ONE compiled program — there is no host transfer between init and
solve (the property the paper's whole design argues for).  Compiled programs
live in the explicit compile cache keyed on (bucket shape, config, warm
start), so repeated calls on the same size bucket dispatch immediately.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from .cache import compile_cache_key, get_compiled
from .config import MatcherConfig
from .device_csr import DeviceCSR
from .solve import make_solver
from .state import MatchState, MatchStats, empty_like_graph
from .warmstart import apply_warm_start, get_warm_start, warm_start_version


class Matcher:
    """A paper variant + warm start, compiled per size bucket.

    >>> m = Matcher(MatcherConfig(algo="apfb"), warm_start="karp_sipser")
    >>> state = m.run(graph)            # init + solve, one device program
    >>> int(state.cardinality)          # first (and only) host sync
    """

    def __init__(self, config: MatcherConfig = MatcherConfig(),
                 warm_start: str = "none"):
        self.config = config
        self.warm_start = warm_start
        get_warm_start(warm_start)      # fail fast on unknown names

    @staticmethod
    def _check_state(graph: DeviceCSR, state: MatchState) -> None:
        """A state sized for a different graph would silently corrupt the
        BFS (clamped gathers); fail loudly at trace time instead."""
        assert (state.cmatch.shape[-1] == graph.nc + 1
                and state.rmatch.shape[-1] == graph.nr + 1), (
            f"MatchState sized {(state.cmatch.shape[-1] - 1,)} x "
            f"{(state.rmatch.shape[-1] - 1,)} does not fit graph bucket "
            f"({graph.nc}, {graph.nr})")

    # -- pure pytree functions (safe to jit/vmap/compose) --------------------
    def init(self, graph: DeviceCSR, state: Optional[MatchState] = None
             ) -> MatchState:
        """Warm-start-initialized state (no solve).

        Pure in its pytree arguments; the eager path dispatches through the
        compile cache, and under an outer ``jit`` it simply inlines.
        """
        if state is None:
            state = empty_like_graph(graph)
        key = compile_cache_key(graph.bucket_key, None,
                                self._cache_tag(True), "init")
        return get_compiled(key, lambda: self._init_pure)(graph, state)

    def _init_pure(self, graph: DeviceCSR, state: MatchState) -> MatchState:
        self._check_state(graph, state)
        with jax.named_scope("warm_start"):
            cm, rm, rounds = apply_warm_start(self.warm_start, graph,
                                              state.cmatch, state.rmatch)
        return dataclasses.replace(state, cmatch=cm, rmatch=rm,
                                   warm_rounds=state.warm_rounds + rounds)

    def solve(self, graph: DeviceCSR, state: MatchState) -> MatchState:
        """Run the solver from ``state`` (pure; no warm start applied)."""
        self._check_state(graph, state)
        kw = {}
        if self.config.dirop:
            kw["cxadj"] = graph.cxadj
            if not graph.has_csc:
                raise ValueError(
                    "MatcherConfig(dirop=True) needs the CSC mirror; build "
                    "it once with graph.with_csc() (serving admission does "
                    "this automatically for dirop configs)")
            kw.update(rxadj=graph.rxadj, radj=graph.radj, erow=graph.erow)
        cm, rm, phases, fb, cert, levels = make_solver(self.config)(
            graph.ecol, graph.cadj, state.cmatch, state.rmatch, **kw)
        return MatchState(cmatch=cm, rmatch=rm,
                          phases=state.phases + phases,
                          fallbacks=state.fallbacks + fb,
                          certified=cert, levels=state.levels + levels,
                          warm_rounds=state.warm_rounds)

    def _cache_tag(self, cold: bool):
        """Warm-start identity for the compile cache; versioned so that
        re-registering a name invalidates programs built from the old fn."""
        if not cold:
            return "<resume>"
        return (self.warm_start, warm_start_version(self.warm_start))

    # -- compiled entry points ------------------------------------------------
    def program(self, graph: DeviceCSR, cold: bool = True):
        """The jitted ``(graph, state) -> state`` program that :meth:`run`
        (or :meth:`run_many`, for a stacked graph) dispatches for
        ``graph``'s bucket; ``cold`` fuses the warm start in front of the
        solve.  ``graph`` may hold ``jax.ShapeDtypeStruct`` leaves, so
        ``program(g).lower(g, state).compile()`` compiles ahead of time.
        """
        batched = bool(graph.batch_shape)
        key = compile_cache_key(graph.bucket_key, self.config,
                                self._cache_tag(cold),
                                "run_many" if batched else "run")

        def build():
            one = self.solve
            if cold:
                # _init_pure, not init: going through the public entry inside
                # this build would register a second ("init") cache entry at
                # trace time (AOT warmup counts on one program per entry).
                one = lambda g, s: self.solve(g, self._init_pure(g, s))  # noqa: E731
            return jax.vmap(one) if batched else one

        return get_compiled(key, build)

    def run(self, graph: DeviceCSR, state: Optional[MatchState] = None
            ) -> MatchState:
        """Maximum matching on device.

        ``state=None``: warm start + solve fused in one program.  With an
        explicit ``state`` (e.g. resuming after graph updates) the warm start
        is skipped and the solver continues from it.  Pure in its pytree
        arguments — calling it under an outer ``jax.jit`` inlines the whole
        matcher into the caller's program.
        """
        assert not graph.batch_shape, \
            "run() takes a single graph; use run_many for a stacked DeviceCSR"
        cold = state is None
        if cold:
            state = empty_like_graph(graph)
        return self.program(graph, cold)(graph, state)

    def run_many(self, graphs: DeviceCSR,
                 states: Optional[MatchState] = None) -> MatchState:
        """Batched matching over a stacked same-bucket ``DeviceCSR``.

        One ``vmap``-compiled program solves the whole batch per dispatch —
        the serving path for many concurrent matching requests.
        """
        assert graphs.batch_shape, "run_many expects a stacked DeviceCSR"
        cold = states is None
        if cold:
            states = empty_like_graph(graphs)
        return self.program(graphs, cold)(graphs, states)

    def stats(self, state: MatchState) -> MatchStats:
        """Device-scalar stats labelled with this matcher's variant name."""
        return MatchStats.of(state, self.config.name)


def match_many(graphs: DeviceCSR, config: MatcherConfig = MatcherConfig(),
               warm_start: str = "cheap",
               states: Optional[MatchState] = None) -> MatchState:
    """Functional alias: ``Matcher(config, warm_start).run_many(graphs)``."""
    return Matcher(config, warm_start).run_many(graphs, states)


def maximum_matching_device(graph: DeviceCSR,
                            config: MatcherConfig = MatcherConfig(),
                            warm_start: str = "none") -> MatchState:
    """Single-graph device-resident matching (state in, state out)."""
    return Matcher(config, warm_start).run(graph)
