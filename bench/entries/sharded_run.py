"""Entry ``sharded_run``: large graphs through ``ShardedMatcher.run``, their
edges sharded over the chips of a mesh, back to back.

The configuration's ``mesh`` names the axis and the number of chips; the
entry builds that mesh from the first chips JAX finds and exits with an
error where there are fewer.  Each graph of the mix is placed with
``DeviceCSR.shard``: the edge list split in equal contiguous slices, one
per chip, the column offsets replicated.  Everything else is
``matcher_run``'s: the graphs, the warm-up on an edgeless graph of the same
shapes, the window in whole rounds, the check of every answer against the
reference.  Each solve also records its BFS levels (``MatchState.levels``),
one merge of the shards per level.

A traced run classes the compiled program's gathers and scatters, and its
merge (the instructions under the scope ``merge_shards``) as ``merge``,
and reads the merge's device time from the trace
(:mod:`collective`).  A program without the scope has no merge to read,
and its merge metrics report nothing.
"""
from __future__ import annotations

import glob
import importlib.util
import os
import time

import jax
import numpy as np

import collective
import reference
import trace_reduce
from traffic import generate

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_entry_{name}", os.path.join(HERE, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


matcher_run = _load("matcher_run")


class Cell(matcher_run.Cell):
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float,
                 log):
        from repro.matching import (DeviceCSR, MatcherConfig,
                                    ShardedMatcher)
        self.log = log
        axis, ndev = config["mesh"]["axis"], int(config["mesh"]["devices"])
        devices = jax.devices()
        if len(devices) < ndev:
            raise SystemExit(f"sharded_run: the mesh needs {ndev} devices; "
                             f"JAX found {len(devices)}")
        mesh = jax.make_mesh((ndev,), (axis,), devices=devices[:ndev])
        t = time.perf_counter()
        self.hosts = generate.graphs(mix, seed)
        slots = generate.edge_slots(mix)
        nc, nr = self.hosts[0].nc, self.hosts[0].nr

        def place(cxadj, cadj, ecol, nnz):
            return DeviceCSR(cxadj=cxadj, cadj=cadj, ecol=ecol,
                             nnz=np.int32(nnz), nc=nc, nr=nr
                             ).shard(mesh, axis)

        self.graphs = [place(*generate.padded(g, slots), g.nnz)
                       for g in self.hosts]
        jax.block_until_ready(self.graphs)
        log(f"{len(self.hosts)} graphs {mix['family']} {nc}x{nr} nnz="
            f"{self.hosts[0].nnz} nnz_pad={slots}, {slots // ndev} per "
            f"device over {ndev}, made in {time.perf_counter() - t:.3f} s")
        self.matcher = ShardedMatcher(
            mesh, axis, MatcherConfig(**config["matcher_config"]),
            warm_start=config["warm_start"])
        self.ndev = ndev
        t = time.perf_counter()
        empty = place(np.zeros(nc + 1, np.int32),
                      np.full(slots, nr, np.int32),
                      np.full(slots, nc, np.int32), 0)
        state = self.matcher.run(empty)
        state.to_host()
        int(state.levels)
        log(f"program sharded-{self.matcher.config.name}+"
            f"{self.matcher.warm_start}@{ndev} ready in "
            f"{time.perf_counter() - t:.3f} s")
        self.solves = []
        self.results = []

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            for i, graph in enumerate(self.graphs):
                ts = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.solve"):
                    state = self.matcher.run(graph)
                    cmatch, rmatch = state.to_host()
                te = time.perf_counter()
                self.solves.append({"graph": i, "start": ts - t0,
                                    "end": te - t0,
                                    "phases": int(state.phases),
                                    "levels": int(state.levels),
                                    "size": int((cmatch >= 0).sum())})
                self.results.append((i, cmatch, rmatch))

    def settle(self) -> None:
        super().settle()
        self.log(f"levels {[s['levels'] for s in self.solves]}")

    def counters(self, traced: bool) -> dict:
        """Program counters read after the window.  A traced run also
        classes the compiled program's instructions for the trace (the
        program ``run`` compiled, again, from the persistent cache), reads
        the merge's device time from the trace, and the bytes the window's
        merges had to move: one all-reduce of the winner vector a level."""
        out = {"solves": self.solves}
        if traced:
            from repro.matching.state import empty_like_graph
            g = self.graphs[0]
            hlo = self.matcher.program(g).lower(
                g, empty_like_graph(g)).compile().as_text()
            merges = collective.merge_instructions(hlo)
            out["op_kinds"] = {**trace_reduce.op_kinds(hlo),
                               **dict.fromkeys(merges, "merge")}
            if merges:
                out["merge_s"] = collective.merge_seconds(
                    trace_reduce.load(self._trace_dir()), merges)
            out["merge_bytes"] = (sum(s["levels"] for s in self.solves)
                                  * collective.allreduce_bytes(g.nr + 1,
                                                               self.ndev))
            out["device_kind"] = self.matcher.mesh.devices.flat[0].device_kind
        return out

    @staticmethod
    def _trace_dir() -> str:
        """The directory of the trace this process just wrote: the newest
        under the benchmark's output directory."""
        newest = max(glob.glob(os.path.join(BENCH, ".out", "trace-*", "**",
                                            "*.xplane.pb"), recursive=True),
                     key=os.path.getmtime)
        return os.path.dirname(newest)

    def compare(self, cmp: "reference.Comparison") -> None:
        """Every answer against its own graph; the maximum once, as every
        numbering of the instance has the same."""
        del self.graphs, self.matcher
        t = time.perf_counter()
        best = reference.maximum_size(self.hosts[0])
        keys = [reference.edge_keys(g) for g in self.hosts]
        self.ok = [cmp.add(self.hosts[i], cmatch, rmatch, best, keys[i])
                   for i, cmatch, rmatch in self.results]
        self.log(f"reference: maximum {best}, {len(self.results)} results "
                 f"checked in {time.perf_counter() - t:.3f} s")
