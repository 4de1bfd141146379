"""Device-resident bipartite CSR graph as a registered JAX pytree.

``DeviceCSR`` mirrors :class:`repro.core.csr.BipartiteCSR` but its arrays are
``jax.Array`` leaves, so a graph passes straight through ``jax.jit`` /
``jax.vmap`` boundaries with no host transfer.  The true sizes ``nc``/``nr``
are static pytree metadata (they define the array shapes and therefore the
compiled program); the true edge count ``nnz`` stays a device scalar leaf so a
stacked batch of graphs may differ in it (padding edges carry sentinel
endpoints and are inert in every kernel).

Size-bucket helpers (:meth:`DeviceCSR.pad_to`, :func:`bucket_nnz`) round the
edge capacity up to a small set of shapes so the compile cache stays bounded,
and :meth:`DeviceCSR.stack` builds the batched bucket consumed by
:func:`repro.matching.match_many`.

A graph may additionally carry a **CSC mirror** (:meth:`DeviceCSR.with_csc`):
the row-major twin ``rxadj``/``radj`` plus the edge-parallel ``erow`` view and
the permutation ``eperm`` mapping each row-sorted edge back to its CSR slot.
The mirror is what the direction-optimizing pull sweep
(``MatcherConfig(dirop=True)``) gathers over; it is lazily built, stays
``None`` by default (zero cost for push-only workloads), and is threaded
through every shape operation (``pad_to``/``pad_vertices``/``bucketed``/
``stack``/``shard``) so an admitted serving graph keeps it.  Presence is part
of :attr:`bucket_key` — a mirrored graph compiles a different program than a
bare one, and the cache must see that.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # avoid a circular import; only needed for annotations
    from repro.core.csr import BipartiteCSR

LANE = 128  # TPU lane width; every edge capacity is a multiple of this


class GraphValidationError(ValueError):
    """A graph's CSR arrays violate the structural invariants every kernel
    assumes (monotone offsets, in-range endpoints, sentinel tail discipline).

    Raised by :meth:`DeviceCSR.validate` and by serving admission
    (``Bucketizer(validate=True)``) so a malformed or adversarial graph is
    rejected before it can poison a batched dispatch.  ``problems`` keeps
    the full finding list; ``str()`` shows them all.
    """

    def __init__(self, problems: Sequence[str]):
        self.problems = tuple(problems)
        super().__init__("invalid bipartite CSR: " + "; ".join(self.problems))


def validate_structure(cxadj: np.ndarray, cadj: np.ndarray, ecol: np.ndarray,
                       nnz: int, nc: int, nr: int) -> Tuple[str, ...]:
    """Structural findings for one graph's host-side CSR arrays (empty tuple
    = valid).  The checks mirror what the kernels silently assume:

    * ``cxadj`` is (nc+1,), starts at 0, is monotone nondecreasing and ends
      at the true edge count ``nnz`` (<= the padded capacity);
    * real edge slots carry in-range endpoints (``cadj`` row ids < nr,
      ``ecol`` column ids < nc) and ``ecol`` agrees with the offsets (edge
      slot ``e`` of column ``c`` has ``ecol[e] == c``);
    * padding slots carry the inert sentinels ``cadj = nr`` / ``ecol = nc``
      — a padding edge with a real endpoint would propose phantom matches.

    Out-of-range ids would otherwise be CLAMPED by the solver's guarded
    gathers into silently-wrong matchings, which is exactly why admission
    runs this before upload.
    """
    problems = []
    cxadj = np.asarray(cxadj)
    cadj = np.asarray(cadj)
    ecol = np.asarray(ecol)
    nnz_pad = int(cadj.shape[-1])
    if cxadj.shape != (nc + 1,):
        return (f"cxadj shape {cxadj.shape} != ({nc + 1},)",)
    if ecol.shape != cadj.shape:
        return (f"ecol shape {ecol.shape} != cadj shape {cadj.shape}",)
    if not (0 <= nnz <= nnz_pad):
        return (f"nnz {nnz} outside [0, nnz_pad={nnz_pad}]",)
    if cxadj[0] != 0:
        problems.append(f"cxadj[0] = {int(cxadj[0])} != 0")
    if np.any(np.diff(cxadj) < 0):
        bad = int(np.argmax(np.diff(cxadj) < 0))
        problems.append(f"cxadj not monotone at column {bad}")
    elif cxadj[-1] != nnz:
        problems.append(f"cxadj[-1] = {int(cxadj[-1])} != nnz {nnz}")
    real_r, real_c = cadj[:nnz], ecol[:nnz]
    if np.any((real_r < 0) | (real_r >= nr)):
        bad = int(np.argmax((real_r < 0) | (real_r >= nr)))
        problems.append(
            f"cadj[{bad}] = {int(real_r[bad])} outside rows [0, {nr})")
    if np.any((real_c < 0) | (real_c >= nc)):
        bad = int(np.argmax((real_c < 0) | (real_c >= nc)))
        problems.append(
            f"ecol[{bad}] = {int(real_c[bad])} outside columns [0, {nc})")
    elif not problems and cxadj[-1] == nnz:
        want = np.repeat(np.arange(nc, dtype=ecol.dtype), np.diff(cxadj))
        if not np.array_equal(real_c, want):
            bad = int(np.argmax(real_c != want))
            problems.append(
                f"ecol[{bad}] = {int(real_c[bad])} disagrees with cxadj "
                f"(expected column {int(want[bad])})")
    if np.any(cadj[nnz:] != nr):
        bad = nnz + int(np.argmax(cadj[nnz:] != nr))
        problems.append(
            f"padding cadj[{bad}] = {int(cadj[bad])} != sentinel {nr}")
    if np.any(ecol[nnz:] != nc):
        bad = nnz + int(np.argmax(ecol[nnz:] != nc))
        problems.append(
            f"padding ecol[{bad}] = {int(ecol[bad])} != sentinel {nc}")
    return tuple(problems)


def bucket_nnz(nnz: int, lane: int = LANE) -> int:
    """Smallest power-of-two multiple of ``lane`` holding ``nnz`` edges."""
    cap = lane
    while cap < nnz:
        cap *= 2
    return cap


def auto_mesh(mesh):
    """``mesh`` with every axis ``Auto``-typed (same devices, same names).

    ``jax.make_mesh`` types its axes ``Explicit`` by default.  The sharded
    matcher leaves the warm start outside ``shard_map`` for GSPMD to
    partition, which only ``Auto`` axes allow, so :meth:`DeviceCSR.shard`
    and :class:`~repro.matching.ShardedMatcher` place and compile on this
    view of whatever mesh the caller built.
    """
    from jax.sharding import AxisType
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def per_shard_nnz(nnz_pad: int, ndev: int, lane: int = LANE) -> int:
    """Per-device edge capacity when sharding ``nnz_pad`` edges over ``ndev``
    devices: each shard is itself a canonical bucket.  Shared by
    :meth:`DeviceCSR.shard` and the collective cost model
    (``benchmarks/collective_report.py --matcher``)."""
    return bucket_nnz(-(-nnz_pad // ndev), lane)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceCSR:
    """Column-major CSR bipartite graph living on the accelerator.

    Data leaves (batchable): ``cxadj`` (nc+1,), ``cadj``/``ecol``
    (nnz_pad,), ``nnz`` scalar int32.  Static metadata: ``nc``, ``nr``.

    Optional CSC mirror leaves (all present or all ``None``, see
    :meth:`with_csc`): ``rxadj`` (nr+1,) row offsets into the row-sorted edge
    list, ``radj``/``erow`` (nnz_pad,) column/row endpoints in row-sorted
    order, ``eperm`` (nnz_pad,) the CSR position of each row-sorted edge.
    Sentinel conventions match the CSR side (``radj = nc``, ``erow = nr``).
    """

    cxadj: jax.Array
    cadj: jax.Array
    ecol: jax.Array
    nnz: jax.Array
    nc: int = dataclasses.field(metadata=dict(static=True))
    nr: int = dataclasses.field(metadata=dict(static=True))
    rxadj: Optional[jax.Array] = None
    radj: Optional[jax.Array] = None
    erow: Optional[jax.Array] = None
    eperm: Optional[jax.Array] = None

    # -- shape/bucket introspection ------------------------------------------
    @property
    def nnz_pad(self) -> int:
        return int(self.cadj.shape[-1])

    @property
    def batch_shape(self) -> Tuple[int, ...]:
        return tuple(self.cadj.shape[:-1])

    @property
    def has_csc(self) -> bool:
        return self.rxadj is not None

    @property
    def bucket_key(self) -> Tuple:
        """The compile-relevant shape: (*batch, nc, nr, nnz_pad[, "csc"]).

        The mirror marker matters: a mirrored graph has extra pytree leaves,
        so the traced program differs and the compile cache (and the serving
        warmup grid) must key on its presence.
        """
        key = self.batch_shape + (self.nc, self.nr, self.nnz_pad)
        return key + ("csc",) if self.has_csc else key

    # -- host <-> device ------------------------------------------------------
    @classmethod
    def from_host(cls, g: "BipartiteCSR", pad_to: Optional[int] = None,
                  device=None) -> "DeviceCSR":
        """Upload a host graph, optionally repadding the edge capacity."""
        cadj, ecol = g.cadj, g.ecol
        if pad_to is not None and pad_to != g.nnz_pad:
            assert pad_to >= g.nnz, (pad_to, g.nnz)
            cadj = np.full(pad_to, g.nr, np.int32)
            ecol = np.full(pad_to, g.nc, np.int32)
            cadj[: g.nnz] = g.cadj[: g.nnz]
            ecol[: g.nnz] = g.ecol[: g.nnz]
        put = (lambda x: jax.device_put(x, device)) if device else jnp.asarray
        return cls(cxadj=put(np.asarray(g.cxadj, np.int32)),
                   cadj=put(np.asarray(cadj, np.int32)),
                   ecol=put(np.asarray(ecol, np.int32)),
                   nnz=put(np.int32(g.nnz)), nc=g.nc, nr=g.nr)

    def validate(self) -> "DeviceCSR":
        """Check the structural invariants (one host sync); returns ``self``
        so it chains, raises :class:`GraphValidationError` otherwise.

        Serving admission calls this via ``Bucketizer(validate=True)``; the
        corpus harness and tests call it directly on suspect graphs.
        """
        assert not self.batch_shape, "validate() takes a single graph"
        problems = validate_structure(self.cxadj, self.cadj, self.ecol,
                                      int(self.nnz), self.nc, self.nr)
        if problems:
            raise GraphValidationError(problems)
        return self

    def to_host(self) -> "BipartiteCSR":
        """Materialize back to the numpy container (one sync, for interop)."""
        from repro.core.csr import BipartiteCSR
        assert not self.batch_shape, "unstack a batched DeviceCSR first"
        return BipartiteCSR(nc=self.nc, nr=self.nr, nnz=int(self.nnz),
                            cxadj=np.asarray(self.cxadj),
                            cadj=np.asarray(self.cadj),
                            ecol=np.asarray(self.ecol))

    # -- the CSC mirror -------------------------------------------------------
    def with_csc(self) -> "DeviceCSR":
        """Attach the row-major mirror (no-op if already present).

        One stable ``argsort`` over the edge list: padding edges carry
        ``cadj = nr`` so they sort to the tail and stay inert sentinels in
        the mirror too (``radj = nc``, ``erow = nr``).  ``rxadj[r]`` is the
        first row-sorted slot of row ``r`` and ``rxadj[nr]`` the true edge
        count; ``eperm`` maps each row-sorted slot back to its CSR position
        (identity on the sentinel tail).  Build it *before* ``stack`` or
        ``shard`` — the mirror then rides every later shape operation.
        """
        if self.has_csc:
            return self
        assert not self.batch_shape, \
            "with_csc() takes a single graph; build the mirror before stack()"
        order = jnp.argsort(self.cadj, stable=True).astype(jnp.int32)
        erow = self.cadj[order]
        rxadj = jnp.searchsorted(
            erow, jnp.arange(self.nr + 1, dtype=jnp.int32)).astype(jnp.int32)
        return dataclasses.replace(self, rxadj=rxadj, radj=self.ecol[order],
                                   erow=erow, eperm=order)

    def drop_csc(self) -> "DeviceCSR":
        """Return the bare graph (the mirror leaves removed)."""
        return dataclasses.replace(self, rxadj=None, radj=None, erow=None,
                                   eperm=None)

    # -- bucketing ------------------------------------------------------------
    def pad_to(self, nnz_pad: int) -> "DeviceCSR":
        """Grow the edge capacity on device (sentinel-fill the new slots)."""
        cur = self.nnz_pad
        if nnz_pad == cur:
            return self
        assert nnz_pad > cur, f"cannot shrink edge capacity {cur} -> {nnz_pad}"
        extra = nnz_pad - cur
        pad_shape = self.batch_shape + (extra,)
        cadj = jnp.concatenate(
            [self.cadj, jnp.full(pad_shape, self.nr, jnp.int32)], axis=-1)
        ecol = jnp.concatenate(
            [self.ecol, jnp.full(pad_shape, self.nc, jnp.int32)], axis=-1)
        g = dataclasses.replace(self, cadj=cadj, ecol=ecol)
        if self.has_csc:
            # mirror sentinels live at the tail too; new slots map to the new
            # CSR tail slots (identity), keeping eperm a true permutation
            tail = cur + jnp.arange(extra, dtype=jnp.int32)
            g = dataclasses.replace(
                g,
                radj=jnp.concatenate(
                    [self.radj, jnp.full(pad_shape, self.nc, jnp.int32)],
                    axis=-1),
                erow=jnp.concatenate(
                    [self.erow, jnp.full(pad_shape, self.nr, jnp.int32)],
                    axis=-1),
                eperm=jnp.concatenate(
                    [self.eperm,
                     jnp.broadcast_to(tail, pad_shape)], axis=-1))
        return g

    def bucketed(self, lane: int = LANE) -> "DeviceCSR":
        """Round the edge capacity up to the canonical power-of-two bucket."""
        return self.pad_to(bucket_nnz(self.nnz_pad, lane))

    def pad_vertices(self, nc: int, nr: int) -> "DeviceCSR":
        """Grow the vertex counts on device (serving-bucketizer path).

        The extra columns/rows are isolated (no incident edges), so the
        maximum matching — and every solver trajectory on the real vertices —
        is unchanged.  Padding edges are re-sentineled (they encoded the old
        ``nc``/``nr``) and ``cxadj`` is extended with the terminal offset.
        Changes the static bucket shape, which is the point: the bucketizer
        maps many true sizes onto one declared compiled bucket.
        """
        if (nc, nr) == (self.nc, self.nr):
            return self
        assert not self.batch_shape, "pad_vertices() takes a single graph"
        assert nc >= self.nc and nr >= self.nr, \
            f"cannot shrink vertex counts {(self.nc, self.nr)} -> {(nc, nr)}"
        cxadj = self.cxadj
        if nc > self.nc:
            cxadj = jnp.concatenate(
                [cxadj, jnp.broadcast_to(cxadj[-1:], (nc - self.nc,))])
        cadj = jnp.where(self.cadj == self.nr, jnp.int32(nr), self.cadj)
        ecol = jnp.where(self.ecol == self.nc, jnp.int32(nc), self.ecol)
        g = dataclasses.replace(self, cxadj=cxadj, cadj=cadj, ecol=ecol,
                                nc=nc, nr=nr)
        if self.has_csc:
            rxadj = self.rxadj
            if nr > self.nr:
                # new rows are edgeless: offsets repeat the true edge count
                rxadj = jnp.concatenate(
                    [rxadj, jnp.broadcast_to(rxadj[-1:], (nr - self.nr,))])
            g = dataclasses.replace(
                g, rxadj=rxadj,
                radj=jnp.where(self.radj == self.nc, jnp.int32(nc),
                               self.radj),
                erow=jnp.where(self.erow == self.nr, jnp.int32(nr),
                               self.erow))
        return g

    # -- multi-device sharding ------------------------------------------------
    def shard(self, mesh, axis: str = "data") -> "DeviceCSR":
        """Edge-partition the graph over one mesh axis (for ShardedMatcher).

        The edge arrays (``ecol``/``cadj``) are 1-D sharded across the
        ``axis`` devices — each owns an equal contiguous slice — while the
        O(n) arrays (``cxadj``, ``nnz``) are replicated.  The edge capacity is
        padded so every shard is itself a canonical power-of-two bucket
        (:func:`bucket_nnz`): the result stays an ordinary ``DeviceCSR``
        pytree whose :attr:`bucket_key` is cacheable, and each per-device
        slice keeps the lane alignment the Pallas kernel tiles over.
        Padding edges carry sentinel endpoints and are inert, as everywhere;
        they accumulate at the tail, but the per-level sweep is a dense
        vector op over every lane of a shard, so work per device is exactly
        the shard capacity no matter how the real edges distribute.

        Arrays are placed on the :func:`auto_mesh` view of ``mesh``, so a
        graph already placed on the caller's ``Explicit`` mesh is re-placed,
        not passed through.
        """
        assert not self.batch_shape, "shard() takes a single graph"
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = auto_mesh(mesh)
        ndev = int(mesh.shape[axis])
        per_shard = per_shard_nnz(self.nnz_pad, ndev)
        g = self if ndev * per_shard == self.nnz_pad \
            else self.pad_to(ndev * per_shard)
        edges = NamedSharding(mesh, P(axis))
        rep = NamedSharding(mesh, P())
        g = dataclasses.replace(
            g,
            ecol=jax.device_put(g.ecol, edges),
            cadj=jax.device_put(g.cadj, edges),
            cxadj=jax.device_put(g.cxadj, rep),
            nnz=jax.device_put(g.nnz, rep))
        if g.has_csc:
            # the row-sorted edge list shards 1-D like the CSR one: each
            # device owns a contiguous *row range* of the mirror (rows are
            # sorted), which is exactly what the per-shard pull sweep wants;
            # the O(n) offsets stay replicated.  Shard boundaries need not
            # align with the CSR shards — any edge partition min-merged with
            # the same per-level pmin yields the same winners.
            g = dataclasses.replace(
                g,
                radj=jax.device_put(g.radj, edges),
                erow=jax.device_put(g.erow, edges),
                eperm=jax.device_put(g.eperm, edges),
                rxadj=jax.device_put(g.rxadj, rep))
        return g

    # -- batching -------------------------------------------------------------
    @staticmethod
    def stack(graphs: Sequence["DeviceCSR"]) -> "DeviceCSR":
        """Stack same-bucket graphs into one batched DeviceCSR (for vmap)."""
        assert graphs, "empty graph batch"
        g0 = graphs[0]
        assert len({g.has_csc for g in graphs}) == 1, \
            "cannot stack mirrored and bare graphs; with_csc() all or none"
        cap = max(g.nnz_pad for g in graphs)
        graphs = [g.pad_to(cap) for g in graphs]
        for g in graphs:
            assert (g.nc, g.nr) == (g0.nc, g0.nr), \
                f"bucket mismatch: {(g.nc, g.nr)} vs {(g0.nc, g0.nr)}"
        return jax.tree.map(lambda *xs: jnp.stack(xs), *graphs)

    def unstack(self) -> Tuple["DeviceCSR", ...]:
        assert self.batch_shape, "not a batched DeviceCSR"
        n = self.batch_shape[0]
        return tuple(jax.tree.map(lambda x: x[i], self) for i in range(n))
