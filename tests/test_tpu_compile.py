"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The programs the chip runs compile at real size: the single-chip
``Matcher.run`` programs at 2^20 vertices per side and 2^23 edges, one
``match_many`` program at a serving bucket, and the ``ShardedMatcher``
program over the four chips of a v5e 2x2, whose edge arrays must be spread
a quarter per chip.  The Pallas frontier kernels must still be rejected by
the TPU compiler: that rejection is what ``MatcherConfig.canonical`` refuses
compiled Pallas for, so when a kernel compiles here this file fails and the
guard comes out.  The single-chip programs also pin the XLA sweep's shape:
one gather per edge slot in each BFS level.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers import every file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.frontier_expand import (frontier_expand,
                                           frontier_expand_fused,
                                           frontier_expand_pull)
from repro.kernels.frontier_expand.frontier_expand import MOSAIC_REJECTION
from repro.matching import DeviceCSR, Matcher, MatcherConfig, ShardedMatcher
from repro.matching.state import empty_like_graph
from repro.serving import ladder

N = 1 << 20                 # vertices per side
NNZ = 1 << 23               # edges
EDGE_BYTES = 2 * NNZ * 4    # ecol + cadj, int32
SHARDED_N = 1 << 21         # the four-chip cell: vertices per side
SHARDED_NNZ = 1 << 24       # and edge slots


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but not read back without the chip: keep it off meanwhile
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _graph(nc, nr, nnz, sharding, edge_sharding=None, batch=()):
    def leaf(shape, sh=sharding):
        return jax.ShapeDtypeStruct(batch + shape, jnp.int32, sharding=sh)
    edges = edge_sharding or sharding
    return DeviceCSR(cxadj=leaf((nc + 1,)), cadj=leaf((nnz,), edges),
                     ecol=leaf((nnz,), edges), nnz=leaf(()), nc=nc, nr=nr)


def _edge_gathers(hlo: str, scope: str) -> int:
    """Gathers with an edge-slot result (``s32[NNZ]``) under ``scope``, in
    the compiled text: by the gather's own ``op_name``, or by that of the
    fusion that calls the computation holding it."""
    comp, caller_op, gathers = None, {}, []
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head:
            comp = head.group(1)
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        op = op.group(1) if op else ""
        call = re.search(r"calls=%([\w.-]+)", line)
        if call:
            caller_op[call.group(1)] = op
        if re.search(rf"= s32\[{NNZ}\]\{{[^}}]*\}} gather\(", line):
            gathers.append((comp, op))
    return sum(scope in op or scope in caller_op.get(c, "")
               for c, op in gathers)


def _computations(hlo: str):
    """``{computation: [instruction lines]}`` and ``{instruction: type}``
    of compiled HLO text (instruction names are unique in a module)."""
    comps, types, comp = {}, {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) \(", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            comps[comp] = []
            continue
        inst = re.match(r"\s*(?:ROOT )?%([\w.-]+) = (\S+)", line)
        if inst and comp is not None:
            types[inst.group(1)] = inst.group(2)
            comps[comp].append(line)
    return comps, types


def _elements(type_: str) -> int:
    dims = re.match(r"\w+\[([\d,]*)\]", type_).group(1)
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def _round_streams(hlo: str, scope: str):
    """For each while loop under ``scope``: the element counts of the
    tables its body gathers edge-length results from, and of the operands
    it scatters edge-length updates into (nested computations included)."""
    comps, types = _computations(hlo)
    out = []
    for line in (l for lines in comps.values() for l in lines):
        if " while(" not in line or f"/{scope}/" not in line:
            continue
        todo, seen = [re.search(r"body=%([\w.-]+)", line).group(1)], set()
        while todo:
            c = todo.pop()
            if c in seen or c not in comps:
                continue
            seen.add(c)
            for l in comps[c]:
                todo += re.findall(r"(?:calls|body|condition|to_apply)=%"
                                   r"([\w.-]+)", l)
                branches = re.search(r"branch_computations=\{([^}]*)\}", l)
                if branches:
                    todo += re.findall(r"%([\w.-]+)", branches.group(1))
        gathers, scatters = [], []
        for l in (l for c in seen for l in comps[c]):
            m = re.match(r"\s*(?:ROOT )?%[\w.-]+ = (\S+) (gather|scatter)\("
                         r"([^)]*)\)", l)
            if not m:
                continue
            ops = re.findall(r"%([\w.-]+)", m.group(3))
            if m.group(2) == "gather" and _elements(m.group(1)) == NNZ:
                gathers.append(_elements(types[ops[0]]))
            if m.group(2) == "scatter" and _elements(types[ops[-1]]) == NNZ:
                scatters.append(_elements(types[ops[0]]))
        out.append((sorted(gathers), sorted(scatters)))
    return out


def _state(graph, sharding):
    shapes = jax.eval_shape(lambda: empty_like_graph(graph))
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        shapes)


@pytest.mark.parametrize("cfg", [
    MatcherConfig(),
    MatcherConfig(algo="apsb", kernel="gpubfs_wr", wr_exact=True),
], ids=lambda c: c.name)
def test_matcher_run_compiles_for_v5e(one_chip, cfg):
    g = _graph(N, N, NNZ, one_chip)
    st = _state(g, one_chip)
    matcher = Matcher(cfg, warm_start="karp_sipser")
    compiled = matcher.program(g).lower(g, st).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= EDGE_BYTES
    # the whole solve (warm start fused in) keeps well under 1 GiB of the
    # chip's 16 GB: the O(nnz) edges plus O(n) state, no (nnz,) blow-up
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 1 << 30, mem
    # the XLA sweep reads one column-side value per edge slot per level;
    # the row side of the proposal predicate waits for the winner vector
    assert _edge_gathers(compiled.as_text(), "bfs_level") == 1


def test_warm_start_rounds_index_the_edges_by_row_only(one_chip):
    """Each Karp-Sipser degree round and each cheap round of the one-chip
    program streams the edge list through one gather by ``cadj`` (a table
    of nr+1 rows) and at most one scatter by ``cadj``; the column side is
    segment scans, so nothing of edge length is indexed by ``ecol`` (a
    table of nc+1 columns).  nc != nr tells the two apart."""
    nc, nr = 1 << 18, 3 << 16
    g = _graph(nc, nr, NNZ, one_chip)
    hlo = (Matcher(MatcherConfig(), warm_start="karp_sipser").program(g)
           .lower(g, _state(g, one_chip)).compile().as_text())
    rounds = _round_streams(hlo, "warm_start")
    # the degree rounds' loop, then the cheap rounds' loop
    assert sorted(rounds) == [([nr + 1], []), ([nr + 1], [nr + 1])], rounds


def test_match_many_compiles_for_v5e_at_a_serving_bucket(one_chip):
    bucket = ladder()[-1]
    batch = 8
    g = _graph(bucket.nc, bucket.nr, bucket.nnz_pad, one_chip,
               batch=(batch,))
    st = _state(g, one_chip)
    mem = (Matcher(MatcherConfig(), warm_start="cheap").program(g)
           .lower(g, st).compile().memory_analysis())
    assert mem.argument_size_in_bytes >= batch * 2 * bucket.nnz_pad * 4


def test_sharded_matcher_spreads_edges_over_four_v5e_chips(topo):
    # the caller's plain make_mesh mesh (Explicit axes under JAX 0.9)
    mesh = jax.make_mesh((4,), ("data",), devices=topo.devices)
    sm = ShardedMatcher(mesh, config=MatcherConfig(), warm_start="cheap")
    rep = NamedSharding(sm.mesh, P())
    g = _graph(N, N, NNZ, rep, edge_sharding=NamedSharding(sm.mesh, P("data")))
    st = _state(g, rep)
    mem = sm.program(g).lower(g, st).compile().memory_analysis()
    # per device: a quarter of the edges plus the replicated O(n) vectors
    # (cxadj, cmatch, rmatch), never the whole edge list; the scalar
    # arguments (the state's counters, the edge count) take up to 4 KiB
    # each
    replicated = 3 * (N + 1) * 4
    scalars = 5 * 4096
    assert EDGE_BYTES // 4 <= mem.argument_size_in_bytes, mem
    assert mem.argument_size_in_bytes <= (EDGE_BYTES // 4 + replicated
                                          + scalars), mem


def _merge_operands(hlo: str):
    """The result type of every operand of each all-reduce whose ``op_name``
    lies under ``merge_shards``, by the operand's own definition."""
    types = {}
    merges = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.-]+) = (\S+) ([\w-]+)\(([^)]*)\)",
                     line)
        if not m:
            continue
        types[m.group(1)] = m.group(2)
        if (m.group(3).startswith("all-reduce")
                and "/merge_shards/" in line):
            merges.append(re.findall(r"%([\w.-]+)", m.group(4)))
    return [[types[op] for op in ops] for ops in merges]


@pytest.fixture(scope="module")
def sharded_cell_hlo(topo):
    """The four-chip benchmark cell's compiled program (Karp-Sipser, 2^21
    per side, 2^24 edge slots over a v5e 2x2), as HLO text."""
    mesh = jax.make_mesh((4,), ("data",), devices=topo.devices)
    sm = ShardedMatcher(mesh, config=MatcherConfig(), warm_start="karp_sipser")
    rep = NamedSharding(sm.mesh, P())
    g = _graph(SHARDED_N, SHARDED_N, SHARDED_NNZ, rep,
               edge_sharding=NamedSharding(sm.mesh, P("data")))
    return sm.program(g).lower(g, _state(g, rep)).compile().as_text()


def test_sharded_cell_merges_one_winner_vector_per_level(sharded_cell_hlo,
                                                         one_chip):
    """The merge of each BFS level is an all-reduce of the (nr+1) int32
    winner vector; the one-chip program has no merge at all."""
    n, hlo = SHARDED_N, sharded_cell_hlo
    operands = _merge_operands(hlo)
    assert operands, "no all-reduce under merge_shards"
    for types in operands:
        assert types and all(re.match(rf"s32\[{n + 1}\]\{{", t)
                             for t in types), types
    g1 = _graph(1 << 18, 1 << 18, 1 << 21, one_chip)
    one = (Matcher(MatcherConfig(), warm_start="karp_sipser").program(g1)
           .lower(g1, _state(g1, one_chip)).compile().as_text())
    assert "merge_shards" not in one


@pytest.mark.parametrize("wr", [True, False], ids=["wr", "plain"])
@pytest.mark.parametrize("kernel", [frontier_expand, frontier_expand_fused,
                                    frontier_expand_pull],
                         ids=lambda k: k.__name__)
def test_pallas_kernels_still_rejected_by_the_v5e_compiler(one_chip, kernel,
                                                           wr):
    n, nnz = 1 << 16, 1 << 19

    def leaf(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    state = leaf((n + 1,))
    sweep = jax.jit(lambda e, c, b, r, m, lvl: kernel(
        e, c, b, r, m, lvl, block_edges=4096, interpret=False))
    reason = MOSAIC_REJECTION.split(": ", 1)[1]
    with pytest.raises(NotImplementedError, match=re.escape(reason)):
        sweep.lower(leaf((nnz,)), leaf((nnz,)), state,
                    state if wr else None, state, leaf(())).compile()


def test_sharded_warm_start_moves_no_edge_array(sharded_cell_hlo):
    """GSPMD partitions the warm start's segment scans and its gather and
    scatter by row over the edge shards in place: no collective of the
    program, the warm start's among them, moves more than an O(n) vector
    (the all-reduces of the row and column tables, and the carries of a
    few elements between the shards' blocks), so none gathers an edge
    array."""
    moved = []
    for line in sharded_cell_hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.-]+ = (.*?) ((?:all-gather|all-reduce|"
                     r"collective-permute|all-to-all|reduce-scatter)"
                     r"(?:-start)?)\(", line)
        if m:
            moved.append((m.group(2), "/warm_start/" in line, max(
                [_elements(t) for t in re.findall(r"[su]32\[[\d,]*\]",
                                                  m.group(1))] or [0])))
    assert [op for op, ws, _ in moved if ws], "no collective under warm_start"
    assert max(size for _, _, size in moved) <= SHARDED_N + 2, moved
