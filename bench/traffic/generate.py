"""The benchmark's one traffic generator.

A traffic mix is a JSON file of parameters beside this module,
``bench/traffic/<name>.json``; a cell names it in ``BENCHMARK.json`` under
``traffic``.  Two kinds of mix exist:

* ``"graph"``: one large fixed instance under a fixed set of vertex
  numberings, solved back to back in an order drawn from the seed
  (``"family": "kron"`` or ``"uniform"``);
* ``"requests"``: an open-loop stream of small graphs of mixed families,
  made on the host from the seed, each due at a scheduled arrival time.

Every seed gets the same sizes and the same set of arrival gaps, in another
order: the seed changes which small instances are drawn, or the order of
a large one's numberings, not how much work a run holds.  The families are copies of the program's generators
(``repro.graphs.generators``, ``repro.launch.serve_matching.FAMILIES``), kept
here so that no change to the program can change the yardstick.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class HostGraph:
    """Column-major CSR of a bipartite graph: the rows of column ``c`` are
    ``cadj[cxadj[c]:cxadj[c + 1]]``.  No padding, no duplicate edges."""

    nc: int
    nr: int
    cxadj: np.ndarray
    cadj: np.ndarray

    @property
    def nnz(self) -> int:
        return int(self.cadj.shape[0])


@dataclasses.dataclass(frozen=True)
class Request:
    family: str
    graph: HostGraph
    due_s: float            # scheduled arrival, seconds after the window opens


def load(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def csr_from_edges(cols, rows, nc: int, nr: int) -> HostGraph:
    """Deduplicated CSR from an unsorted edge list."""
    keys = np.unique(np.asarray(cols, np.int64) * nr
                     + np.asarray(rows, np.int64))
    cols = (keys // nr).astype(np.int32)
    counts = np.bincount(cols, minlength=nc)
    cxadj = np.zeros(nc + 1, np.int32)
    np.cumsum(counts, out=cxadj[1:])
    return HostGraph(nc, nr, cxadj, (keys % nr).astype(np.int32))


# -- host families (copies of repro.graphs.generators) ------------------------
def random_bipartite(rng, nc: int, nr: int, avg_deg: float) -> HostGraph:
    nnz = int(nc * avg_deg)
    return csr_from_edges(rng.integers(0, nc, nnz), rng.integers(0, nr, nnz),
                          nc, nr)


def kron_graph(rng, scale: int, edge_factor: int) -> HostGraph:
    """RMAT/Kronecker, Graph500 parameters a, b, c = .57, .19, .19."""
    n = 1 << scale
    nnz = n * edge_factor
    a, b, c = 0.57, 0.19, 0.19
    cols = np.zeros(nnz, np.int64)
    rows = np.zeros(nnz, np.int64)
    for bit in range(scale):
        u = rng.random(nnz)
        cols |= (u >= a + b).astype(np.int64) << bit
        rows |= ((u >= a) & (u < a + b) | (u >= a + b + c)
                 ).astype(np.int64) << bit
    return csr_from_edges(cols, rows, n, n)


def grid_graph(side: int) -> HostGraph:
    """Bipartite double cover of a 2-D grid: long augmenting paths."""
    n = side * side
    idx = np.arange(n).reshape(side, side)
    right_c, right_r = idx[:, :-1].ravel(), idx[:, 1:].ravel()
    down_c, down_r = idx[:-1, :].ravel(), idx[1:, :].ravel()
    cols = np.concatenate([np.arange(n), right_c, right_r, down_c, down_r])
    rows = np.concatenate([np.arange(n), right_r, right_c, down_r, down_c])
    return csr_from_edges(cols, rows, n, n)


def scaled_free(rng, nc: int, nr: int, avg_deg: float,
                alpha: float = 1.8) -> HostGraph:
    """Power-law column degrees."""
    w = rng.zipf(alpha, size=nc).astype(np.float64)
    w = np.minimum(w, nr // 2)
    w *= (nc * avg_deg) / w.sum()
    degs = np.maximum(1, rng.poisson(w)).astype(np.int64)
    cols = np.repeat(np.arange(nc, dtype=np.int64), degs)
    return csr_from_edges(cols, rng.integers(0, nr, int(degs.sum())), nc, nr)


# name -> (rng, size hint n) -> graph; the rules of serve_matching.FAMILIES
FAMILIES: Dict[str, Callable[[np.random.Generator, int], HostGraph]] = {
    "random": lambda rng, n: random_bipartite(rng, n, n - n // 8, 3.0),
    "kron": lambda rng, n: kron_graph(rng, max(4, int(np.log2(max(n, 16)))),
                                      6),
    "grid": lambda rng, n: grid_graph(max(4, int(np.sqrt(n)))),
    "free": lambda rng, n: scaled_free(rng, n, n, 4.0),
}


def arrival_gaps(rate: float, count: int, seed: int) -> np.ndarray:
    """``count`` exponential inter-arrival gaps of mean ``1/rate``: the
    distribution's quantiles at (k + 1/2)/count, in an order drawn from the
    seed.  Every seed offers the same gaps, so the same load."""
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    return np.random.default_rng([seed, 1]).permutation(gaps)


def requests(mix: dict, seconds: float, seed: int) -> List[Request]:
    """The open-loop request stream of a ``"requests"`` mix: Poisson-like
    arrivals at ``rate`` per second over ``seconds``, families round-robin
    in the mix's order, instance seeds drawn from ``seed``."""
    rate = float(mix["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    due = np.cumsum(arrival_gaps(rate, count, seed))
    due *= seconds / due[-1] * (count - 0.5) / count   # last one inside
    names = mix["families"]
    out = []
    for i in range(count):
        fam = names[i % len(names)]
        rng = np.random.default_rng([seed, 2, i])
        out.append(Request(fam, FAMILIES[fam](rng, int(mix["size_hint"])),
                           float(due[i])))
    return out


# -- graph mixes: endpoints drawn on the device, deduplicated on the host ------
def graphs(mix: dict, seed: int) -> List[HostGraph]:
    """The graphs of a ``"graph"`` mix: one fixed instance under a fixed
    set of ``labelings`` numberings, in an order drawn from the seed.

    The instance's endpoints are drawn from the mix's ``structure_seed`` on
    the default device in one jitted call, as a published matrix is one
    fixed instance.  ``"kron"`` is the Graph500 graph: undirected, so each
    drawn edge is stored both ways and the matrix is symmetric, with
    self-loops and duplicates dropped as Graph500's kernel 1 does; a
    labeling is one random permutation of the vertex labels, applied to
    rows and columns alike.  ``"uniform"`` is a nonsymmetric matrix; a
    labeling permutes its columns and its rows each on their own.  How many
    phases a solve needs depends on the numbering, so every seed gets the
    same set of numberings and only their order changes.  The edges are
    deduplicated into the CSR on the host: a sort of 2^24 pairs on the
    device would compile for minutes, this compiles in seconds.
    """
    base = int(mix["structure_seed"])
    key = jax.random.fold_in(jax.random.key(base & 0xFFFFFFFF), base >> 32)
    fam = mix["family"]
    if fam == "kron":
        n = 1 << int(mix["scale"])
        cols, rows = _kron_edges(key, int(mix["scale"]),
                                 int(mix["edge_factor"]), tuple(mix["abc"]))
    elif fam == "uniform":
        n = 1 << int(mix["log2_n"])
        cols, rows = _uniform_edges(key, n, int(mix["per_column"]))
    else:
        raise ValueError(f"unknown graph family {fam!r}")
    cols, rows = np.asarray(cols), np.asarray(rows)
    if fam == "kron":
        loop = cols == rows
        cols, rows = (np.concatenate([cols[~loop], rows[~loop]]),
                      np.concatenate([rows[~loop], cols[~loop]]))
    out = []
    for k in range(int(mix["labelings"])):
        rng = np.random.default_rng([base, 3, k])
        cperm = rng.permutation(n).astype(np.int32)
        rperm = cperm if fam == "kron" else rng.permutation(n).astype(
            np.int32)
        out.append(csr_from_edges(cperm[cols], rperm[rows], n, n))
    order = np.random.default_rng([seed, 3]).permutation(len(out))
    return [out[i] for i in order]


def edge_slots(mix: dict) -> int:
    """Edge slots of a ``"graph"`` mix: the padded edge capacity the
    program sees, the same for every seed (a power of two): every drawn
    edge, twice for the undirected ``"kron"``."""
    if mix["family"] == "kron":
        return 2 * int(mix["edge_factor"]) << int(mix["scale"])
    return int(mix["per_column"]) << int(mix["log2_n"])


def padded(g: HostGraph, slots: int):
    """``(cxadj, cadj, ecol)`` in the program's padded edge-list layout:
    ``slots`` edge slots, the tail holding the sentinels ``cadj = nr`` and
    ``ecol = nc``."""
    cadj = np.full(slots, g.nr, np.int32)
    ecol = np.full(slots, g.nc, np.int32)
    cadj[: g.nnz] = g.cadj
    ecol[: g.nnz] = np.repeat(np.arange(g.nc, dtype=np.int32),
                              np.diff(g.cxadj))
    return g.cxadj, cadj, ecol


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _kron_edges(key, scale: int, edge_factor: int, abc):
    """Graph500 RMAT: each of ``scale`` bits of an edge's column and row
    picks a quadrant with probabilities a, b, c, 1 - a - b - c."""
    a, b, c = abc
    m = edge_factor << scale

    def bit(i, carry):
        cols, rows = carry
        u = jax.random.uniform(jax.random.fold_in(key, i), (m,))
        cb = (u >= a + b).astype(jnp.int32)
        rb = (((u >= a) & (u < a + b)) | (u >= a + b + c)).astype(jnp.int32)
        return cols | (cb << i), rows | (rb << i)

    zero = jnp.zeros(m, jnp.int32)
    return jax.lax.fori_loop(0, scale, bit, (zero, zero))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _uniform_edges(key, n: int, per_column: int):
    m = n * per_column
    kc, kr = jax.random.split(key)
    return (jax.random.randint(kc, (m,), 0, n, jnp.int32),
            jax.random.randint(kr, (m,), 0, n, jnp.int32))


if __name__ == "__main__":      # pragma: no cover - a quick look at a mix
    import sys
    mix = load(sys.argv[1])
    if mix["kind"] == "requests":
        reqs = requests(mix, float(sys.argv[2]) if len(sys.argv) > 2 else 10,
                        0)
        print(len(reqs), "requests;", {f: sum(r.family == f for r in reqs)
                                       for f in mix["families"]},
              "max nnz", max(r.graph.nnz for r in reqs),
              "last due", round(reqs[-1].due_s, 3), "mean gap",
              round(float(np.mean(np.diff([r.due_s for r in reqs]))), 4))
    else:
        print(mix)
