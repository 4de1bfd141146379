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
    # (cxadj, cmatch, rmatch), never the whole edge list
    replicated = 3 * (N + 1) * 4
    assert EDGE_BYTES // 4 <= mem.argument_size_in_bytes, mem
    assert mem.argument_size_in_bytes <= EDGE_BYTES // 4 + replicated + 4096, mem


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


def test_sharded_cell_merges_one_winner_vector_per_level(topo, one_chip):
    """The four-chip benchmark cell's program (Karp-Sipser, 2^21 per side,
    2^24 edge slots over a v5e 2x2): the merge of each BFS level is an
    all-reduce of the (nr+1) int32 winner vector; the one-chip program
    has no merge at all."""
    n, nnz = 1 << 21, 1 << 24
    mesh = jax.make_mesh((4,), ("data",), devices=topo.devices)
    sm = ShardedMatcher(mesh, config=MatcherConfig(), warm_start="karp_sipser")
    rep = NamedSharding(sm.mesh, P())
    g = _graph(n, n, nnz, rep, edge_sharding=NamedSharding(sm.mesh, P("data")))
    hlo = sm.program(g).lower(g, _state(g, rep)).compile().as_text()
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
