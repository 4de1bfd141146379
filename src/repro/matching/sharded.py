"""Distributed-memory matching: the edge-partitioned matcher over a mesh.

The paper closes with: "an out-of-core or distributed-memory type algorithm is
amenable when the graph does not fit into the device ... We plan to
investigate the techniques to obtain good matching performance for
extreme-scale bipartite graphs."  :class:`ShardedMatcher` is that algorithm,
and it is the *same* solver as the single-device :class:`~repro.matching.api.
Matcher` — :func:`repro.matching.solve.make_solver` with a mesh axis bound:

* the edge list is 1-D sharded across one mesh axis
  (:meth:`DeviceCSR.shard`); each device owns ``nnz/D`` edges — the natural
  scale-out of the paper's CT strided edge ownership;
* the O(n) BFS state (``bfs``/``root``/``pred``/``cmatch``/``rmatch``) is
  replicated; every level each device sweeps its own edge shard into a local
  per-row winner vector (the fused Pallas ``frontier_expand_fused`` kernel
  when ``config.use_pallas`` — each shard's min-merge happens inside its
  kernel — the jnp sweep + scatter otherwise) and the shard winners merge
  with one ``jax.lax.pmin`` — a single all-reduce per BFS level, the
  minimal coordination any level-synchronous distributed BFS needs;
* ``ALTERNATE``/``FIXMATCHING`` act on replicated O(n) state and therefore
  run redundantly-but-identically on every device (cheaper than sharding
  them: their cost is O(n) per phase vs O(nnz/D) for expansion).

Communication per level = one pmin over an (nr+1) int32 vector; a ring
all-reduce moves ``2*(D-1)/D * 4*(nr+1)`` bytes per link
(``benchmarks/collective_report.py --matcher`` prices this, and
``docs/architecture.md`` walks through the whole design).

The warm start runs *outside* the ``shard_map`` region, as plain jnp inside
the same jitted program: GSPMD partitions its scatter/gather rounds over the
sharded edge arrays automatically, so every registry entry
(``none``/``cheap``/``karp_sipser``/custom) works unmodified.  GSPMD may only
do that over ``Auto`` mesh axes, while ``jax.make_mesh`` types its axes
``Explicit`` by default; the matcher therefore places and compiles on the
:func:`~repro.matching.device_csr.auto_mesh` view of the caller's mesh.
Compiled programs live in the shared compile cache, keyed additionally on the
mesh fingerprint and axis name.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from .api import Matcher
from .cache import compile_cache_key, get_compiled
from .config import MatcherConfig
from .device_csr import DeviceCSR, auto_mesh
from .solve import make_solver
from .state import MatchState, MatchStats, empty_like_graph
from .warmstart import apply_warm_start, cheap_init


def mesh_cache_key(mesh: Mesh, axis: str):
    """Hashable mesh identity for the compile cache.

    Two meshes force distinct programs iff they differ in axis layout or
    member devices; both are captured here (device ids, not object ids, so a
    re-built but identical mesh still hits).
    """
    return (tuple(mesh.shape.items()),
            tuple(int(d.id) for d in mesh.devices.flat), axis)


class ShardedMatcher(Matcher):
    """A paper variant + warm start, compiled per (size bucket, mesh, axis).

    >>> mesh = jax.make_mesh((4,), ("data",))
    >>> m = ShardedMatcher(mesh, config=MatcherConfig(algo="apfb"),
    ...                    warm_start="cheap")
    >>> state = m.run(DeviceCSR.from_host(g).shard(mesh, "data"))
    >>> int(state.cardinality)          # == single-device Matcher.run

    Inherits the single-device facade: ``init``/``solve``/``stats`` and the
    state checks are shared; only ``run`` is replaced with the
    ``shard_map``-wrapped program (``run_many`` is not supported — batching
    and edge-sharding compose via one graph per mesh instead).
    """

    def __init__(self, mesh: Mesh, axis: str = "data",
                 config: MatcherConfig = MatcherConfig(),
                 warm_start: str = "none"):
        super().__init__(config, warm_start)
        if self.config.adaptive_frontier:
            raise ValueError(
                "adaptive_frontier is single-device only; ShardedMatcher "
                "keeps the dense per-shard sweep + one pmin per level "
                "(use MatcherConfig(dirop=True) for a direction heuristic "
                "that composes with sharding)")
        assert axis in mesh.axis_names, (axis, mesh.axis_names)
        self.mesh = auto_mesh(mesh)
        self.axis = axis

    def run(self, graph: DeviceCSR, state: Optional[MatchState] = None
            ) -> MatchState:
        """Maximum matching with edges sharded over the mesh axis.

        ``graph`` is re-sharded if needed (:meth:`DeviceCSR.shard` is a no-op
        on an already edge-partitioned graph of the right capacity).  As with
        the single-device path, ``state=None`` fuses warm start + solve into
        one compiled program; an explicit state resumes the solver from it.
        """
        assert not graph.batch_shape, \
            "ShardedMatcher.run takes a single (edge-sharded) graph"
        if self.config.dirop and not graph.has_csc:
            raise ValueError(
                "MatcherConfig(dirop=True) needs the CSC mirror; call "
                "graph.with_csc() before .shard() — the mirror shards with "
                "the graph")
        graph = graph.shard(self.mesh, self.axis)
        cold = state is None
        if cold:
            state = empty_like_graph(graph)
        return self.program(graph, cold)(graph, state)

    def program(self, graph: DeviceCSR, cold: bool = True):
        """The jitted program :meth:`run` dispatches for ``graph``'s bucket
        on this mesh (see :meth:`Matcher.program`); ``graph`` is taken as
        already edge-sharded, as :meth:`run` leaves it."""
        key = compile_cache_key(
            graph.bucket_key, self.config, self._cache_tag(cold),
            ("sharded_run",) + mesh_cache_key(self.mesh, self.axis))
        dirop = self.config.dirop

        def build():
            solve = make_solver(self.config, axis=self.axis)
            # dirop extends the solver args with the column offsets and the
            # CSC mirror: O(n) offsets replicated, the row-sorted edge
            # arrays 1-D sharded exactly like the CSR ones
            in_specs = (P(self.axis), P(self.axis), P(), P())
            if dirop:
                in_specs += (P(), P(), P(self.axis), P(self.axis))
            # check_vma off: the level loop is a while_loop over replicated
            # state that the varying-manual-axes check cannot follow
            smap = jax.shard_map(
                solve, mesh=self.mesh, in_specs=in_specs,
                out_specs=(P(),) * 6, check_vma=False)
            cfg = self.config

            def fn(g: DeviceCSR, s: MatchState) -> MatchState:
                self._check_state(g, s)
                cm, rm, rounds = s.cmatch, s.rmatch, s.warm_rounds
                if cold:
                    with jax.named_scope("warm_start"):
                        cm, rm, ran = apply_warm_start(self.warm_start, g,
                                                       cm, rm)
                    rounds = rounds + ran
                extra = ((g.cxadj, g.rxadj, g.radj, g.erow) if dirop else ())
                cm, rm, phases, fb, cert, levels = smap(g.ecol, g.cadj, cm,
                                                        rm, *extra)
                if cfg.degrade_maximal and cfg.max_phases > 0:
                    # Same budget-exhausted maximality repair as the
                    # single-device solver, applied OUTSIDE the shard_map
                    # region: cheap_init's scatter rounds need the whole
                    # edge list, and like the warm start GSPMD partitions
                    # them over the sharded arrays automatically.
                    cm, rm = jax.lax.cond(
                        cert, lambda cr: cr,
                        lambda cr: cheap_init(g.ecol, g.cadj, *cr,
                                              cxadj=g.cxadj),
                        (cm, rm))
                return MatchState(cmatch=cm, rmatch=rm,
                                  phases=s.phases + phases,
                                  fallbacks=s.fallbacks + fb,
                                  certified=cert, levels=s.levels + levels,
                                  warm_rounds=rounds)

            return fn

        return get_compiled(key, build)

    def run_many(self, graphs, states=None):
        raise NotImplementedError(
            "ShardedMatcher shards edges over the mesh; batch with "
            "Matcher.run_many or one ShardedMatcher call per graph")

    def stats(self, state: MatchState) -> MatchStats:
        ndev = int(self.mesh.shape[self.axis])
        return MatchStats.of(state, f"sharded-{self.config.name}@{ndev}")


def match_sharded(graph: DeviceCSR, mesh: Mesh, axis: str = "data",
                  config: MatcherConfig = MatcherConfig(),
                  warm_start: str = "cheap",
                  state: Optional[MatchState] = None) -> MatchState:
    """Functional alias: ``ShardedMatcher(mesh, axis, config, ws).run(...)``."""
    return ShardedMatcher(mesh, axis, config, warm_start).run(graph, state)
