"""Matching state & stats as pytree dataclasses (device-resident results).

These replace the ad-hoc ``(cmatch, rmatch, stats-dict)`` tuple of the old
host-centric API: phases/fallbacks/cardinality stay as device scalars until
the caller explicitly asks (:meth:`MatchStats.as_dict`,
:meth:`MatchState.to_host`), so a matcher run composes under ``jit``/``vmap``
with zero forced syncs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SENTINEL = jnp.int32(-3)  # value of the trailing sentinel slot


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MatchState:
    """Matching vectors with the solver's sentinel slot still attached.

    ``cmatch`` (nc+1,) / ``rmatch`` (nr+1,): matched partner or -1; the last
    slot is the kernels' scratch sentinel.  ``phases``/``fallbacks`` count the
    solver's outer iterations and ``levels`` the BFS levels it expanded over
    all phases (all 0 for a fresh or a warm-started state); ``warm_rounds``
    counts the rounds the warm start ran (Karp–Sipser degree rounds plus
    cheap rounds; 0 for ``"none"`` and for a state not warm-started).
    ``certified`` is the solver's Berge certificate: True iff the last BFS
    phase proved no augmenting path remains, i.e. the matching is maximum —
    a ``MatcherConfig.max_phases``-truncated solve leaves it False (fresh
    and warm-started-only states are likewise uncertified).
    """

    cmatch: jax.Array
    rmatch: jax.Array
    phases: jax.Array
    fallbacks: jax.Array
    certified: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.bool_(False))
    # None: zeros shaped like ``phases``, for a state rebuilt from a solve's
    # outputs by a caller that predates the counter
    levels: Optional[jax.Array] = None
    warm_rounds: Optional[jax.Array] = None

    def __post_init__(self):
        for name in ("levels", "warm_rounds"):
            if getattr(self, name) is None and self.phases is not None:
                object.__setattr__(self, name, jnp.zeros_like(self.phases))

    @classmethod
    def fresh(cls, nc: int, nr: int, batch_shape: Tuple[int, ...] = ()
              ) -> "MatchState":
        """All-unmatched state for an (nc, nr) bucket (device arrays)."""
        cm = jnp.full(batch_shape + (nc + 1,), jnp.int32(-1))
        rm = jnp.full(batch_shape + (nr + 1,), jnp.int32(-1))
        cm = cm.at[..., nc].set(SENTINEL)
        rm = rm.at[..., nr].set(SENTINEL)
        zero = jnp.zeros(batch_shape, jnp.int32)
        return cls(cmatch=cm, rmatch=rm, phases=zero, fallbacks=zero,
                   certified=jnp.zeros(batch_shape, bool), levels=zero,
                   warm_rounds=zero)

    @classmethod
    def from_host(cls, cmatch: np.ndarray, rmatch: np.ndarray) -> "MatchState":
        """Wrap true-size host vectors (appends the sentinel slot)."""
        cm = jnp.concatenate([jnp.asarray(cmatch, jnp.int32),
                              jnp.full((1,), SENTINEL)])
        rm = jnp.concatenate([jnp.asarray(rmatch, jnp.int32),
                              jnp.full((1,), SENTINEL)])
        zero = jnp.int32(0)
        return cls(cmatch=cm, rmatch=rm, phases=zero, fallbacks=zero,
                   certified=jnp.bool_(False), levels=zero, warm_rounds=zero)

    @property
    def cardinality(self) -> jax.Array:
        """Matched-pair count as a device scalar (no host sync)."""
        return jnp.sum((self.cmatch[..., :-1] >= 0).astype(jnp.int32),
                       axis=-1)

    def to_host(self) -> Tuple[np.ndarray, np.ndarray]:
        """(cmatch, rmatch) as true-size numpy arrays — the only host hop."""
        return (np.asarray(self.cmatch)[..., :-1],
                np.asarray(self.rmatch)[..., :-1])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MatchStats:
    """Run statistics; scalars stay on device until :meth:`as_dict`."""

    cardinality: jax.Array
    phases: jax.Array
    fallbacks: jax.Array
    levels: jax.Array
    certified: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.bool_(False))
    variant: str = dataclasses.field(default="", metadata=dict(static=True))

    @classmethod
    def of(cls, state: MatchState, variant: str = "") -> "MatchStats":
        return cls(cardinality=state.cardinality, phases=state.phases,
                   fallbacks=state.fallbacks, certified=state.certified,
                   levels=state.levels, variant=variant)

    def as_dict(self) -> dict:
        """Host-side stats dict (the old API's ``stats`` payload)."""
        out = {k: np.asarray(getattr(self, k))
               for k in ("phases", "fallbacks", "levels", "cardinality")}
        out = {k: int(v) if v.ndim == 0 else v.astype(int)
               for k, v in out.items()}
        cert = np.asarray(self.certified)
        out["certified"] = bool(cert) if cert.ndim == 0 else cert.astype(bool)
        out["variant"] = self.variant
        return out


def empty_like_graph(graph, batch_shape: Optional[Tuple[int, ...]] = None
                     ) -> MatchState:
    """Fresh all-unmatched state shaped for ``graph`` (a DeviceCSR)."""
    bs = graph.batch_shape if batch_shape is None else batch_shape
    return MatchState.fresh(graph.nc, graph.nr, bs)
