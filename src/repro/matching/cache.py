"""Explicit compile cache for matcher programs.

One process-wide table keyed on ``(bucket shape, MatcherConfig, warm start,
entry point)`` replaces the ``functools.lru_cache``-wrapped jits that used to
be scattered across ``core/matcher.py`` and ``core/cheap.py``.  Centralizing
it makes compilation observable (:func:`compile_cache_info`), evictable
(:func:`compile_cache_clear`) and keyed on exactly the things that force a
recompile: the padded bucket shape and the variant configuration.

The table is guarded by a reentrant lock: the serving layer
(``repro.serving``) hits it concurrently from its flush thread, AOT warmup,
and whatever thread calls ``submit``.  Capacity is ``MAX_ENTRIES``,
overridable with :func:`set_max_entries`; evictions are counted and exposed
in :func:`compile_cache_info` so a serving deployment can see when its
declared warmup grid no longer fits the cache.

That table lives as long as the process.  :func:`enable_persistent_compile_cache`
adds JAX's on-disk cache under it, so a new process finds the executables an
earlier one compiled; entry points call it first thing in ``main``.
"""
from __future__ import annotations

import os
import threading
from typing import Callable, Dict, Hashable, Tuple

import jax

MAX_ENTRIES = 256   # parity with the lru_cache maxsize this table replaced

_CACHE: Dict[Hashable, Callable] = {}
_HITS = 0
_MISSES = 0
_EVICTIONS = 0
_LOCK = threading.RLock()
_TLS = threading.local()      # per-thread hit/miss tallies (see below)


def enable_persistent_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``.jax_cache`` at the root
    of the checkout this file belongs to — a fixed path, because the path
    is part of what a later run must find again.  Call it before the first
    compile; importing the package never calls it, so tests write no cache.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    from jax.experimental.compilation_cache import compilation_cache
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    path = os.path.join(root, ".jax_cache")
    compilation_cache.set_cache_dir(path)
    compilation_cache.reset_cache()   # a compile before this call fixed "none"
    return path


def _thread_counts() -> dict:
    counts = getattr(_TLS, "counts", None)
    if counts is None:
        counts = _TLS.counts = {"hits": 0, "misses": 0}
    return counts


def set_max_entries(n: int) -> int:
    """Override the cache capacity; returns the previous value.

    Shrinking below the current population evicts LRU entries immediately
    (counted as evictions).  A serving deployment sizes this to its warmup
    grid so warmed programs are never evicted by stray compiles.
    """
    global MAX_ENTRIES, _EVICTIONS
    assert n >= 1, f"cache capacity must be positive, got {n}"
    with _LOCK:
        old, MAX_ENTRIES = MAX_ENTRIES, int(n)
        while len(_CACHE) > MAX_ENTRIES:
            del _CACHE[next(iter(_CACHE))]
            _EVICTIONS += 1
    return old


def compile_cache_key(bucket_key: Tuple[int, ...], cfg, warm_start: str,
                      entry: str) -> Hashable:
    """Canonical key: (bucket shape, config, warm start, entry point).

    ``cfg`` is the :class:`~repro.matching.MatcherConfig` itself: every
    execution-path knob (``dirop`` + its heuristic/geometry fields, ...)
    lands in the key by being part of the frozen dataclass.  ``bucket_key``
    additionally carries the CSC-mirror marker (``DeviceCSR.bucket_key``
    appends ``"csc"``), so a mirrored graph — different pytree leaves,
    different traced program — never shares an entry with a bare one.
    """
    return (bucket_key, cfg, warm_start, entry)


def get_compiled(key: Hashable, build: Callable[[], Callable],
                 static_argnums=()) -> Callable:
    """Jitted program for ``key``, building (and jitting) it on first use."""
    global _HITS, _MISSES, _EVICTIONS
    counts = _thread_counts()
    with _LOCK:
        fn = _CACHE.get(key)
        if fn is None:
            _MISSES += 1
            counts["misses"] += 1
            fn = jax.jit(build(), static_argnums=static_argnums)
            while len(_CACHE) >= MAX_ENTRIES:        # LRU eviction
                del _CACHE[next(iter(_CACHE))]
                _EVICTIONS += 1
            _CACHE[key] = fn
        else:
            _HITS += 1
            counts["hits"] += 1
            _CACHE[key] = _CACHE.pop(key)            # move to MRU position
    return fn


def compile_cache_thread_info() -> dict:
    """Hits/misses made by the *calling thread* (since it first touched the
    cache).  The serving dispatcher reads deltas of this around each flush so
    concurrent compiles on other threads (warmup, direct Matcher users) are
    never misattributed to a dispatch."""
    return dict(_thread_counts())


def compile_cache_info() -> dict:
    with _LOCK:
        return {"entries": len(_CACHE), "hits": _HITS, "misses": _MISSES,
                "evictions": _EVICTIONS, "max_entries": MAX_ENTRIES,
                "keys": tuple(_CACHE)}


def compile_cache_clear() -> None:
    global _HITS, _MISSES, _EVICTIONS
    with _LOCK:
        _CACHE.clear()
        _HITS = 0
        _MISSES = 0
        _EVICTIONS = 0
