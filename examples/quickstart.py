"""Quickstart: maximum cardinality bipartite matching with the paper's
GPU-style algorithms (APFB / APsB) on the device-resident API.

    PYTHONPATH=src python examples/quickstart.py   (or `pip install -e .`)
"""
import numpy as np

from repro.core import hopcroft_karp, validate_matching
from repro.graphs import kron_graph, random_bipartite
from repro.matching import (DeviceCSR, Matcher, MatcherConfig, VARIANTS,
                            compile_cache_info,
                            enable_persistent_compile_cache, match_many)


def main():
    enable_persistent_compile_cache()
    # a power-law bipartite graph (kron_g500-style, as in the paper's suite)
    g = kron_graph(scale=12, edge_factor=8, seed=1)
    print(f"graph: {g.nc} cols, {g.nr} rows, {g.nnz} edges")

    # upload once; the graph is a pytree and stays on device from here on
    graph = DeviceCSR.from_host(g)

    # the paper's winning variant: APFB + GPUBFS-WR + CT, warm-started with
    # cheap matching — init + solve fuse into ONE compiled program
    best = MatcherConfig(algo="apfb", kernel="gpubfs_wr")
    matcher = Matcher(best, warm_start="cheap")
    state = matcher.run(graph)
    stats = matcher.stats(state).as_dict()          # first host sync
    cmatch, rmatch = state.to_host()
    card = validate_matching(g, cmatch, rmatch)
    print(f"{best.name}: |M| = {card} in {stats['phases']} phases "
          f"({stats['fallbacks']} fallbacks)")

    # cross-check against sequential Hopcroft-Karp (the paper's baseline)
    cm_hk, _ = hopcroft_karp(g)
    assert card == int((cm_hk >= 0).sum())
    print("matches sequential Hopcroft-Karp cardinality: OK")

    # the four variants of Table 1 share the uploaded graph
    for cfg in VARIANTS:
        st = Matcher(cfg, warm_start="cheap").run(graph)
        print(f"  {cfg.name:28s} phases={int(st.phases):3d} "
              f"card={int(st.cardinality)}")

    # batched serving: 8 independent graphs, one vmap-compiled dispatch
    batch = DeviceCSR.stack([
        DeviceCSR.from_host(random_bipartite(512, 512, 3.0, seed=s,
                                             pad_to=2048))
        for s in range(8)])
    many = match_many(batch, best, warm_start="karp_sipser")
    print("match_many cardinalities:", np.asarray(many.cardinality).tolist())
    print("compiled programs cached:", compile_cache_info()["entries"])


if __name__ == "__main__":
    main()
