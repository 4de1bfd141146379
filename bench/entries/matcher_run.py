"""Entry ``matcher_run``: large graphs through ``Matcher.run``, back to back.

Set-up makes the mix's graphs (:func:`traffic.generate.graphs`: one fixed
instance under a fixed set of numberings, in the seed's order), puts each
on the device in the program's padded layout, and compiles (or loads) the
one program the window drives, by running it once on an edgeless graph of
the same shapes.  The window solves in whole rounds, each graph of the set
once per round, and starts a round while it is open, so every run does the
same work; it ends when the last solve it started has finished.  A solve
goes from the device-resident ``DeviceCSR`` to the matching on the host.
Every matching the window produced is checked against the reference.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from traffic import generate
import reference


class Cell:
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float,
                 log):
        from repro.matching import DeviceCSR, Matcher, MatcherConfig
        self.log = log
        t = time.perf_counter()
        self.hosts = generate.graphs(mix, seed)
        slots = generate.edge_slots(mix)
        self.graphs = []
        for g in self.hosts:
            cxadj, cadj, ecol = generate.padded(g, slots)
            self.graphs.append(DeviceCSR(
                cxadj=jnp.asarray(cxadj), cadj=jnp.asarray(cadj),
                ecol=jnp.asarray(ecol), nnz=jnp.int32(g.nnz),
                nc=g.nc, nr=g.nr))
        jax.block_until_ready(self.graphs)
        nc, nr = self.hosts[0].nc, self.hosts[0].nr
        log(f"{len(self.hosts)} graphs {mix['family']} {nc}x{nr} nnz="
            f"{self.hosts[0].nnz} nnz_pad={slots} made in "
            f"{time.perf_counter() - t:.3f} s")
        self.matcher = Matcher(MatcherConfig(**config["matcher_config"]),
                               warm_start=config["warm_start"])
        t = time.perf_counter()
        empty = DeviceCSR(cxadj=jnp.zeros(nc + 1, jnp.int32),
                          cadj=jnp.full(slots, nr, jnp.int32),
                          ecol=jnp.full(slots, nc, jnp.int32),
                          nnz=jnp.int32(0), nc=nc, nr=nr)
        state = self.matcher.run(empty)
        state.to_host()
        int(state.phases)
        log(f"program {self.matcher.config.name}+{self.matcher.warm_start} "
            f"ready in {time.perf_counter() - t:.3f} s")
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
                                    "size": int((cmatch >= 0).sum())})
                self.results.append((i, cmatch, rmatch))

    def settle(self) -> None:
        """Every solve the window started has finished inside it."""
        self.log("solves (s): " + ", ".join(
            f"{s['end'] - s['start']:.6f}" for s in self.solves)
            + f"; phases {[s['phases'] for s in self.solves]}")

    def counters(self, traced: bool) -> dict:
        """Program counters read after the window.  A traced run also runs
        the warm start's own program on each graph, for
        ``warm_matched_share``, and reads which fusions of the solve's
        compiled program are gathers and scatters, for the trace's op
        classes."""
        out = {"solves": self.solves}
        if traced:
            out["warm_sizes"] = [int(self.matcher.init(g).cardinality)
                                 for g in self.graphs]
            from repro.matching import MatchState
            import trace_reduce
            g = self.graphs[0]
            program = self.matcher.program(g).lower(
                g, MatchState.fresh(g.nc, g.nr))
            out["op_kinds"] = trace_reduce.op_kinds(
                program.compile().as_text())
        return out

    def compare(self, cmp: "reference.Comparison") -> None:
        del self.graphs, self.matcher          # free the device before the
        t = time.perf_counter()                # reference runs
        best = [reference.maximum_size(g) for g in self.hosts]
        keys = [reference.edge_keys(g) for g in self.hosts]
        self.ok = [cmp.add(self.hosts[i], cmatch, rmatch, best[i], keys[i])
                   for i, cmatch, rmatch in self.results]
        self.log(f"reference: maxima {best}, {len(self.results)} results "
                 f"checked in {time.perf_counter() - t:.3f} s")

    def outcomes(self) -> dict:
        return {"solves": self.solves}

    @property
    def attempted(self) -> int:
        return len(self.solves)

    @property
    def failed(self) -> int:
        return self.ok.count(False)
