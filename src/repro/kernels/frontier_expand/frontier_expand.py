"""Pallas kernels: edge-tiled BFS frontier expansion (paper Alg. 2/4).

Written for the TPU, run in interpret mode: the TPU compiler rejects them
(see "What the TPU compiler does with them" below).

TPU adaptation of the paper's GPUBFS / GPUBFS-WR CUDA kernels
--------------------------------------------------------------
The CUDA kernel assigns columns to threads (MT: one column per thread,
CT: strided batches per thread) and each thread walks its CSR row segment
through global memory, relying on coalescing across the warp.

On TPU the analogous structure is:

* the *edge list* (``ecol``, ``cadj``) is tiled into VMEM blocks of
  ``block_edges`` lanes — the regular, streaming traffic (HBM -> VMEM), which
  is what the GPU coalesced accesses become;
* the BFS state vectors (``bfs``, ``root``, ``rmatch``) stay VMEM-resident
  across the whole grid (they are O(n) and reused by every tile) and are
  read with dynamic gathers by edge endpoint, where the GPU reads global
  memory at random — the gather the TPU compiler rejects (below);
* the paper's MT/CT knob becomes ``block_edges`` (tile granularity): CT's
  coarse-grained strided batches correspond to large tiles (4096 lanes),
  MT's fine-grained one-vertex-per-thread to small tiles (512).

Three kernel families share one proposal formula (:func:`_proposals`):

* :func:`frontier_expand` (legacy) emits the per-edge column proposals
  (IINF = no proposal) as an (nnz,) array; the deterministic per-row
  min-merge then runs as a separate XLA scatter outside the kernel.
* :func:`frontier_expand_fused` keeps a ``(nr+1,)`` winner accumulator
  resident in VMEM across the whole edge-tile grid (the output block maps to
  the same slot for every grid step, so sequential grid revision carries it)
  and min-merges each tile's proposals into it *inside* the kernel.  The
  (nnz,) proposal array and its HBM round-trip disappear: the kernel's only
  output is the per-row winner vector the solver actually needs, and it is
  bit-identical to ``scatter_min`` of the legacy proposals (min is the merge
  in both, so tile order cannot matter).

  By design the streamed traffic per level drops from ~3·nnz int32 plus
  the merge pass to 2·nnz in, (nr+1) out, at the price of a data-dependent
  scatter into VMEM; no compiled kernel has measured that trade.
* :func:`frontier_expand_pull` (``_kernel_pull`` / ``_kernel_pull_wr``) is
  the direction-optimizing *pull* sweep: the same accumulator contract as
  the fused family, but streaming the **CSC mirror** (``radj``/``erow``, the
  row-sorted edge list of ``DeviceCSR.with_csc``).  Because the edges are
  row-sorted, each tile is a contiguous *row range*; late in a BFS most
  rows are already reached, their tiles propose nothing, and the kernel
  skips the (sequential, VPU-hostile) scatter for the whole tile via
  ``pl.when(any(proposals))`` — the per-level scatter work becomes
  proportional to the tiles that still contain unreached rows instead of
  all of them.  The proposal predicate is symmetric in edge order, and min
  is the merge, so the pull winners are bit-identical to the push families
  on the same edge set (asserted in tests/test_frontier_paths.py).

Edge geometry: callers may pass any ``block_edges >= 1``; the wrappers pad
the edge arrays up to the next tile multiple with inert sentinel edges
(``ecol = nc`` points at the NEG bfs slot so the lane never proposes,
``cadj = nr`` lands in the winner slot that is reset to IINF), replacing the
old hard ``nnz % block_edges == 0`` requirement.

What the TPU compiler does with them
------------------------------------
Nothing here compiles for a TPU.  Lowering any of the six kernels (three
families x WR/plain) for a v5e fails in Mosaic with :data:`MOSAIC_REJECTION`:
:func:`_proposals` gathers from the VMEM-resident O(n) state vectors by edge
endpoint (``jnp.take``), a 1-D vector gather Mosaic does not lower, and the
fused and pull merges would next need a data-dependent scatter
(``.at[rows].min``) into the O(n) winner vector.  ``tests/test_tpu_compile.py``
compiles them for a described v5e and asserts the rejection, so the guard in
``MatcherConfig.canonical`` — compiled Pallas is refused when a ``Matcher`` is
built — comes out when a kernel first compiles.  The path that runs on the
chip is the XLA sweep (``MatcherConfig(use_pallas=False)``, the default).
The kernels run in interpret mode on the CPU, where they are held
bit-identical to that sweep (``tests/test_frontier_paths.py``).

``interpret=None`` auto-detects: interpret on the CPU, compile elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

UNVISITED = 1          # python ints: safe to close over in kernels
IINF = 2**30
LANE = 128             # TPU lane width; the floor for any edge tile

# Mosaic's error for every kernel family here, lowered for a TPU v5e under
# JAX 0.9 / libtpu 0.0.34 (see the module docstring).
MOSAIC_REJECTION = "NotImplementedError: Only 2D gather is supported"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` = auto: interpret on the CPU, compile anywhere else."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def check_edge_geometry(nnz: int, block_edges: int) -> None:
    """Trace-time validation of the edge-tile geometry.

    Raises a typed :class:`ValueError` naming the offending shapes (the old
    code bare-asserted ``nnz % block_edges == 0`` inside a jitted wrapper,
    which surfaced as an anonymous tuple).  Divisibility itself is no longer
    required — the wrappers pad — but the tile size must be positive.
    """
    if block_edges < 1:
        raise ValueError(
            "frontier_expand: block_edges must be a positive tile size, got "
            f"block_edges={block_edges} for nnz={nnz}")


def _pad_edges(ecol, cadj, block_edges: int, nc: int, nr: int):
    """Pad the edge arrays up to a multiple of ``block_edges`` with inert
    sentinel edges (``ecol=nc`` -> NEG bfs slot, never active; ``cadj=nr`` ->
    the winner slot that is reset to IINF)."""
    nnz = ecol.shape[0]
    pad = -(-nnz // block_edges) * block_edges
    if pad != nnz:
        ecol = jnp.concatenate(
            [ecol, jnp.full(pad - nnz, jnp.int32(nc))])
        cadj = jnp.concatenate(
            [cadj, jnp.full(pad - nnz, jnp.int32(nr))])
    return ecol, cadj


def _proposals(level, ecol, cadj, bfs, root, rmatch):
    """Per-edge proposal mask (paper Alg. 2 l.6-8 / Alg. 4 l.4-10).

    ``root=None`` selects the plain (non-WR) formula.  Shared by both kernel
    families and their jnp reference oracles.
    """
    nc = bfs.shape[0] - 1
    active = jnp.take(bfs, ecol, axis=0) == level
    if root is not None:
        # WR early-exit (Alg. 4 lines 4-7)
        myroot = jnp.take(root, ecol, axis=0)
        active &= jnp.take(bfs, myroot, axis=0) >= UNVISITED
    # row -> matched column lookup (Alg. 4 lines 9-10)
    cm = jnp.take(rmatch, cadj, axis=0)
    col_unvis = jnp.take(bfs, jnp.clip(cm, 0, nc), axis=0) == UNVISITED
    return active & ((cm >= 0) & col_unvis | (cm == -1))


# ---------------------------------------------------------------------------
# Legacy kernels: per-edge proposals, merge outside
# ---------------------------------------------------------------------------
def _kernel_wr(level_ref, ecol_ref, cadj_ref, bfs_ref, root_ref, rmatch_ref,
               out_ref):
    ecol = ecol_ref[...]
    target = _proposals(level_ref[0], ecol, cadj_ref[...], bfs_ref[...],
                        root_ref[...], rmatch_ref[...])
    out_ref[...] = jnp.where(target, ecol, jnp.int32(IINF))


def _kernel_plain(level_ref, ecol_ref, cadj_ref, bfs_ref, rmatch_ref, out_ref):
    ecol = ecol_ref[...]
    target = _proposals(level_ref[0], ecol, cadj_ref[...], bfs_ref[...],
                        None, rmatch_ref[...])
    out_ref[...] = jnp.where(target, ecol, jnp.int32(IINF))


# ---------------------------------------------------------------------------
# Fused kernels: per-row winner accumulator carried across the grid
# ---------------------------------------------------------------------------
def _merge_tile(target, ecol, cadj, win_ref):
    """Tile-local min-merge into the VMEM-resident winner accumulator.

    The accumulator block is revisited by every grid step (index map is
    constant), so it stays in VMEM for the whole sweep; the TPU grid is
    sequential, making read-modify-write across steps well defined.
    """
    nr = win_ref.shape[0] - 1

    @pl.when(pl.program_id(0) == 0)
    def _init():
        win_ref[...] = jnp.full(win_ref.shape, IINF, jnp.int32)

    prop = jnp.where(target, ecol, jnp.int32(IINF))
    rows = jnp.where(target, cadj, jnp.int32(nr))
    win_ref[...] = win_ref[...].at[rows].min(prop)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _seal():
        # the sentinel slot absorbed every non-proposal; never a winner
        win_ref[...] = win_ref[...].at[nr].set(jnp.int32(IINF))


def _kernel_fused_wr(level_ref, ecol_ref, cadj_ref, bfs_ref, root_ref,
                     rmatch_ref, win_ref):
    ecol, cadj = ecol_ref[...], cadj_ref[...]
    target = _proposals(level_ref[0], ecol, cadj, bfs_ref[...],
                        root_ref[...], rmatch_ref[...])
    _merge_tile(target, ecol, cadj, win_ref)


def _kernel_fused_plain(level_ref, ecol_ref, cadj_ref, bfs_ref, rmatch_ref,
                        win_ref):
    ecol, cadj = ecol_ref[...], cadj_ref[...]
    target = _proposals(level_ref[0], ecol, cadj, bfs_ref[...],
                        None, rmatch_ref[...])
    _merge_tile(target, ecol, cadj, win_ref)


# ---------------------------------------------------------------------------
# Pull kernels: CSC (row-sorted) edge stream, tile-skipping merge
# ---------------------------------------------------------------------------
def _merge_tile_pull(target, cols, rows, win_ref):
    """Like :func:`_merge_tile`, but the merge is predicated on the tile
    proposing anything at all.

    The pull stream is row-sorted, so a tile covers a contiguous row range;
    once those rows are reached the tile goes permanently quiet and the
    sequential in-VMEM scatter — the expensive part of the sweep — is
    skipped wholesale.  Init/seal stay unconditional (the accumulator
    contract does not depend on which tiles were quiet).
    """
    nr = win_ref.shape[0] - 1

    @pl.when(pl.program_id(0) == 0)
    def _init():
        win_ref[...] = jnp.full(win_ref.shape, IINF, jnp.int32)

    @pl.when(jnp.any(target))
    def _merge():
        prop = jnp.where(target, cols, jnp.int32(IINF))
        rows_ix = jnp.where(target, rows, jnp.int32(nr))
        win_ref[...] = win_ref[...].at[rows_ix].min(prop)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _seal():
        win_ref[...] = win_ref[...].at[nr].set(jnp.int32(IINF))


def _kernel_pull_wr(level_ref, radj_ref, erow_ref, bfs_ref, root_ref,
                    rmatch_ref, win_ref):
    cols, rows = radj_ref[...], erow_ref[...]
    target = _proposals(level_ref[0], cols, rows, bfs_ref[...],
                        root_ref[...], rmatch_ref[...])
    _merge_tile_pull(target, cols, rows, win_ref)


def _kernel_pull(level_ref, radj_ref, erow_ref, bfs_ref, rmatch_ref, win_ref):
    cols, rows = radj_ref[...], erow_ref[...]
    target = _proposals(level_ref[0], cols, rows, bfs_ref[...],
                        None, rmatch_ref[...])
    _merge_tile_pull(target, cols, rows, win_ref)


# ---------------------------------------------------------------------------
# Public wrappers
# ---------------------------------------------------------------------------
_KERNELS = {                       # family -> (wr kernel, plain kernel)
    "legacy": (_kernel_wr, _kernel_plain),
    "fused": (_kernel_fused_wr, _kernel_fused_plain),
    "pull": (_kernel_pull_wr, _kernel_pull),
}


@functools.partial(jax.jit,
                   static_argnames=("block_edges", "interpret", "family"))
def _sweep_impl(ecol, cadj, bfs, root, rmatch, level, *, block_edges: int,
                interpret: bool, family: str):
    """One pallas_call builder for all three kernel families.

    The edge padding, grid, and every input spec are identical; the
    families differ only in kernel body and output contract (edge-tiled
    (nnz,) proposals vs the carried (nr+1,) winner accumulator).  For the
    pull family ``ecol``/``cadj`` are the CSC mirror's ``radj``/``erow`` —
    same (column, row) endpoint roles, row-sorted order.
    """
    nnz = ecol.shape[0]
    nc = bfs.shape[0] - 1
    nr = rmatch.shape[0] - 1
    ecol_p, cadj_p = _pad_edges(ecol, cadj, block_edges, nc, nr)
    grid = (ecol_p.shape[0] // block_edges,)
    level_arr = jnp.asarray(level, jnp.int32).reshape(1)

    edge_spec = pl.BlockSpec((block_edges,), lambda i: (i,))
    def rep(arr):                       # replicated per tile (VMEM-resident)
        return pl.BlockSpec(arr.shape, lambda i: (0,))

    in_specs = [pl.BlockSpec((1,), lambda i: (0,)), edge_spec, edge_spec,
                rep(bfs)]
    args = [level_arr, ecol_p, cadj_p, bfs]
    if root is not None:
        in_specs.append(rep(root))
        args.append(root)
    in_specs.append(rep(rmatch))
    args.append(rmatch)

    kernel_wr, kernel_plain = _KERNELS[family]
    kernel = kernel_wr if root is not None else kernel_plain
    if family == "legacy":
        out_specs = edge_spec
        out_shape = jax.ShapeDtypeStruct(ecol_p.shape, jnp.int32)
    else:
        out_specs = pl.BlockSpec((nr + 1,), lambda i: (0,))  # carried acc
        out_shape = jax.ShapeDtypeStruct((nr + 1,), jnp.int32)
    out = pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                         out_specs=out_specs, out_shape=out_shape,
                         interpret=interpret)(*args)
    return out[:nnz] if family == "legacy" else out


def frontier_expand(ecol, cadj, bfs, root, rmatch, level, *,
                    block_edges: int = 4096,
                    interpret: Optional[bool] = None):
    """Per-edge frontier proposals (legacy two-step path).

    ``root=None`` selects the plain kernel; ``interpret=None`` auto-detects
    from the backend.  The per-row merge is the caller's scatter.
    """
    check_edge_geometry(int(ecol.shape[0]), block_edges)
    return _sweep_impl(ecol, cadj, bfs, root, rmatch, level,
                       block_edges=block_edges,
                       interpret=resolve_interpret(interpret),
                       family="legacy")


def frontier_expand_fused(ecol, cadj, bfs, root, rmatch, level, *,
                          block_edges: int = 4096,
                          interpret: Optional[bool] = None):
    """Fused frontier sweep: per-row winners, merged inside the kernel.

    Returns the ``(nr+1,)`` int32 winner vector (lowest proposing column per
    row, IINF = unreached; slot ``nr`` is the IINF sentinel) — bit-identical
    to ``scatter_min`` over :func:`frontier_expand` proposals, with no
    (nnz,) intermediate.  The carried accumulator relies on the grid
    executing *sequentially*, as the TPU's and the interpreter's do.
    """
    check_edge_geometry(int(ecol.shape[0]), block_edges)
    return _sweep_impl(ecol, cadj, bfs, root, rmatch, level,
                       block_edges=block_edges,
                       interpret=resolve_interpret(interpret),
                       family="fused")


def frontier_expand_pull(radj, erow, bfs, root, rmatch, level, *,
                         block_edges: int = 4096,
                         interpret: Optional[bool] = None):
    """Pull-direction frontier sweep over the CSC mirror's row-sorted edges.

    ``radj``/``erow`` are the column/row endpoints of ``DeviceCSR.with_csc``
    (sentinels ``nc``/``nr``, same conventions as ``ecol``/``cadj``).
    Returns the same ``(nr+1,)`` winner vector as
    :func:`frontier_expand_fused` — the proposal predicate is per-edge and
    min is the merge, so edge order cannot change the winners — but tiles
    whose row range no longer contains unreached rows skip their in-VMEM
    scatter entirely (see ``_merge_tile_pull``).  Like the fused family,
    the carried accumulator needs a sequential grid.
    """
    check_edge_geometry(int(radj.shape[0]), block_edges)
    return _sweep_impl(radj, erow, bfs, root, rmatch, level,
                       block_edges=block_edges,
                       interpret=resolve_interpret(interpret),
                       family="pull")
