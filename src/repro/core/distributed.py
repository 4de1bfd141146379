"""Numpy-compat wrapper over :class:`repro.matching.ShardedMatcher`.

The distributed edge-partitioned matcher (the paper's stated future work)
lives in :mod:`repro.matching.sharded` and shares the APFB/APsB solve loop,
warm-start registry, compile cache and XLA frontier sweep with the
single-device :class:`repro.matching.Matcher` — see ``docs/architecture.md``
(design + per-level collective cost) and ``docs/paper_map.md``.  This module
keeps only the original host-centric entry point (numpy in / numpy out,
stats as a dict) for existing callers.

New code should use :class:`repro.matching.ShardedMatcher` directly::

    graph = DeviceCSR.from_host(g).shard(mesh, "data")
    state = ShardedMatcher(mesh, config=cfg, warm_start="cheap").run(graph)
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
from jax.sharding import Mesh

from repro.matching import (DeviceCSR, MatcherConfig, MatchState,
                            ShardedMatcher)


def maximum_matching_distributed(
    g,
    mesh: Mesh,
    cfg: MatcherConfig = MatcherConfig(),
    axis: str = "data",
    cmatch0: Optional[np.ndarray] = None,
    rmatch0: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Edge-partitioned distributed matcher. State replicated, edges sharded.

    Thin host wrapper: uploads + shards once, runs
    :meth:`ShardedMatcher.run`, downloads once.  ``g`` is a host
    :class:`repro.core.csr.BipartiteCSR`.
    """
    graph = DeviceCSR.from_host(g).shard(mesh, axis)
    state = None
    if cmatch0 is not None:
        state = MatchState.from_host(np.asarray(cmatch0, np.int32),
                                     np.asarray(rmatch0, np.int32))
    out = ShardedMatcher(mesh, axis, cfg).run(graph, state)
    cmatch, rmatch = out.to_host()
    return cmatch, rmatch, {
        "phases": int(out.phases), "fallbacks": int(out.fallbacks),
        "cardinality": int((cmatch >= 0).sum()),
        "devices": int(mesh.shape[axis]),
        "variant": f"dist-{cfg.name}",
    }
