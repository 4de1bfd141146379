"""Numpy-compat wrapper over the device-resident ``repro.matching`` API.

The solver itself (the paper's APFB/APsB drivers, GPUBFS/GPUBFS-WR expansion,
ALTERNATE + FIXMATCHING) lives in :mod:`repro.matching.solve` as pure
shape-polymorphic JAX; this module keeps the original host-centric entry
point :func:`maximum_matching` (numpy in / numpy out, stats as a dict) and
re-exports the solver internals for the instrumented benchmarks and the
distributed matcher.

New code should use :class:`repro.matching.Matcher` directly — it keeps
graphs and matcher state on device and composes under ``jit``/``vmap``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.matching.config import MatcherConfig, VARIANTS           # noqa: F401
from repro.matching.solve import (FOUND, IINF, L0, NEG, UNVISITED,   # noqa: F401
                                  _alternate, _cardinality,
                                  _expand_level, _fix_matching,
                                  make_solver, scatter_min)
from repro.matching.api import Matcher
from repro.matching.device_csr import DeviceCSR
from repro.matching.state import MatchState

from .csr import BipartiteCSR


def maximum_matching(
    g: BipartiteCSR,
    cfg: MatcherConfig = MatcherConfig(),
    cmatch0: Optional[np.ndarray] = None,
    rmatch0: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Run one of the paper's matcher variants to a maximum matching.

    Returns (cmatch, rmatch, stats) as numpy arrays of true (unpadded) size.
    Thin host wrapper: uploads once, runs :meth:`Matcher.run`, downloads once.
    """
    graph = DeviceCSR.from_host(g)
    if cfg.dirop:
        graph = graph.with_csc()    # the pull sweep gathers the CSC mirror
    state = None
    if cmatch0 is not None:
        state = MatchState.from_host(np.asarray(cmatch0, np.int32),
                                     np.asarray(rmatch0, np.int32))
    out = Matcher(cfg).run(graph, state)
    cmatch, rmatch = out.to_host()
    stats = {"phases": int(out.phases), "fallbacks": int(out.fallbacks),
             "cardinality": int((cmatch >= 0).sum()),
             "certified": bool(out.certified), "variant": cfg.name}
    return cmatch, rmatch, stats
