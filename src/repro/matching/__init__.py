"""Device-resident matching API for the paper's GPU algorithms.

The public surface of the reproduction:

* :class:`DeviceCSR` — pytree bipartite graph (column-major CSR + the
  edge-parallel view) that passes straight through ``jax.jit`` / ``jax.vmap``;
* :class:`MatcherConfig` — one of the paper's eight variants;
* :class:`Matcher` — facade whose :meth:`Matcher.run` composes a registered
  warm start (``"none" | "cheap" | "karp_sipser"``) with the APFB/APsB solver
  in ONE compiled program (no host hop between init and solve);
* :class:`MatchState` / :class:`MatchStats` — pytree results that stay on
  device until the caller asks;
* :func:`match_many` — vmap-batched matching over a stacked ``DeviceCSR``
  bucket (many concurrent matching requests, one dispatch);
* :class:`ShardedMatcher` / :func:`match_sharded` — the same solve loop with
  edges partitioned over a device mesh (:meth:`DeviceCSR.shard`), one
  ``pmin`` collective per BFS level (the paper's stated future work);
* an explicit compile cache keyed on (bucket shape, config, warm start, and
  for the sharded path mesh/axis), replacing the scattered per-module
  ``functools.lru_cache`` jits, and :func:`enable_persistent_compile_cache`,
  which entry points call to keep compiled programs on disk across runs.

``repro.core.maximum_matching`` / ``cheap_matching_jax`` /
``repro.core.distributed`` remain as thin numpy-compat wrappers over this
package.  ``docs/architecture.md`` documents the design; ``docs/paper_map.md``
maps every paper algorithm to its implementation here.
"""
from .config import MatcherConfig, PallasUnsupportedError, VARIANTS
from .device_csr import DeviceCSR, GraphValidationError, validate_structure
from .state import MatchState, MatchStats
from .warmstart import WARM_STARTS, register_warm_start, warm_start_names
from .api import Matcher, match_many, maximum_matching_device
from .sharded import ShardedMatcher, match_sharded, mesh_cache_key
from .paths import (SOLVE_PATHS, SolvePath, register_solve_path,
                    solve_path_names, unregister_solve_path)
from .cache import (compile_cache_clear, compile_cache_info,
                    compile_cache_key, enable_persistent_compile_cache,
                    get_compiled)

__all__ = [
    "MatcherConfig", "PallasUnsupportedError", "VARIANTS",
    "DeviceCSR", "GraphValidationError", "validate_structure",
    "MatchState", "MatchStats",
    "Matcher", "match_many", "maximum_matching_device",
    "ShardedMatcher", "match_sharded", "mesh_cache_key",
    "SOLVE_PATHS", "SolvePath", "register_solve_path",
    "solve_path_names", "unregister_solve_path",
    "WARM_STARTS", "register_warm_start", "warm_start_names",
    "compile_cache_clear", "compile_cache_info", "compile_cache_key",
    "enable_persistent_compile_cache", "get_compiled",
]
