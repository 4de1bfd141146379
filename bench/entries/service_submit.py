"""Entry ``service_submit``: an open-loop request stream through
``MatchingService.submit``.

Set-up makes every request's graph on the host from the seed, builds the
service with the configuration's settings, and compiles (or loads) only the
programs this traffic reaches: the buckets its graphs fall in, at every
batch size the service may pad a flush to, and the small programs that cut
one result out of a batch.  The window submits each request at its due
time on this thread and records when its future resolves; a request is
timed from its due time, so a late generator shows as latency.  After the
window every request is awaited (up to ``WAIT_S`` past the close) and
every answer is checked against the reference.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import wait

import jax
import numpy as np

from traffic import generate
import reference

WAIT_S = 120.0      # how long past the window an answer may still come
TRACE_WINDOW_S = 3.0    # a traced run's window: some 10^5 device ops/s


class Cell:
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float,
                 log):
        from repro.core.csr import BipartiteCSR
        from repro.matching import DeviceCSR, MatcherConfig, MatchStats
        from repro.serving import Bucketizer, MatchingService, ladder
        from repro.serving.scheduler import batch_ladder
        from repro.serving.warmup import WarmupGrid, synthetic_bucket_graph
        self.log = log
        t = time.perf_counter()
        self.requests = generate.requests(mix, seconds, seed)
        self.inputs = [BipartiteCSR.from_csr(r.graph.cxadj, r.graph.cadj,
                                             r.graph.nc, r.graph.nr)
                       for r in self.requests]
        log(f"{len(self.requests)} requests made in "
            f"{time.perf_counter() - t:.3f} s; largest nnz "
            f"{max(r.graph.nnz for r in self.requests)}")
        s = config["service"]
        cfg = MatcherConfig(**config["matcher_config"])
        self.service = MatchingService(
            bucketizer=Bucketizer(ladder(max_vertices=s["ladder_max_vertices"]),
                                  oversize=s["oversize"], validate=True),
            config=cfg, warm_start=config["warm_start"],
            max_batch=s["max_batch"], max_delay_ms=s["max_delay_ms"])
        buckets = {self.service.bucketizer.bucket_for(g.nc, g.nr, g.nnz)
                   for g in self.inputs}
        if None in buckets:
            raise ValueError("the traffic holds a graph that fits no bucket")
        buckets = tuple(sorted(buckets, key=lambda b: b.cost))
        sizes = batch_ladder(self.service.max_batch)
        t = time.perf_counter()
        report = self.service.warm_up(WarmupGrid(
            buckets=buckets, configs=(cfg,),
            warm_starts=(self.service.warm_start,), batch_sizes=sizes))
        # what the flush thread does with a batch's result, once per shape
        matcher = self.service.matcher()
        for b in buckets:
            for n in sizes:
                out = matcher.run_many(DeviceCSR.stack(
                    [synthetic_bucket_graph(b)] * n))
                one = jax.tree.map(lambda x: x[n - 1], out)
                int(MatchStats.of(one, cfg.name).cardinality)
                one.to_host()
        log(f"service ready in {time.perf_counter() - t:.3f} s: buckets "
            f"{[b.key for b in buckets]}, batch sizes {list(sizes)}; {report}")
        self.done = [None] * len(self.requests)
        self.futures = []
        self.lateness = []
        self.t0 = None
        self.given_up = None
        self._lock = threading.Lock()

    def _on_done(self, i: int):
        def record(_fut):
            now = time.perf_counter()
            with self._lock:
                self.done[i] = now
        return record

    def window(self, seconds: float) -> None:
        t0 = self.t0 = time.perf_counter()
        for i, (req, graph) in enumerate(zip(self.requests, self.inputs)):
            delay = t0 + req.due_s - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self.lateness.append(time.perf_counter() - t0 - req.due_s)
            with jax.profiler.TraceAnnotation("bench.submit"):
                try:
                    fut = self.service.submit(graph)
                except Exception as e:          # refused: never answered
                    self.log(f"request {i} refused: {e!r}")
                    fut = None
            if fut is not None:
                fut.add_done_callback(self._on_done(i))
            self.futures.append(fut)
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)

    def settle(self) -> None:
        """Wait for the answers still due after the window."""
        live = [f for f in self.futures if f is not None]
        with jax.profiler.TraceAnnotation("bench.drain"):
            wait(live, timeout=max(0.0, self.t0 + WAIT_S
                                   + self.requests[-1].due_s
                                   - time.perf_counter()))
        self.given_up = time.perf_counter()
        late = sorted(self.lateness)
        self.log(f"generator lateness: median {1e3 * late[len(late) // 2]:.3f}"
                 f" ms, max {1e3 * late[-1]:.3f} ms over {len(late)} submits")

    def counters(self, traced: bool) -> dict:
        return {"service": self.service.metrics.snapshot()}

    def outcomes(self) -> dict:
        """What the end-to-end readers take, once the answers are checked."""
        return {"answered_s": [(d - self.t0) if (d is not None and ok)
                               else None for d, ok in zip(self.done, self.ok)]}

    def compare(self, cmp: "reference.Comparison") -> None:
        answers = []
        for fut in self.futures:
            res = None
            if fut is not None and fut.done() and fut.exception() is None:
                res = fut.result().matching()
            answers.append(res)
        self.service.close()
        del self.futures
        t = time.perf_counter()
        self.ok = []
        for req, ans in zip(self.requests, answers):
            if ans is None:
                cmp.add_unanswered()
                self.ok.append(False)
                continue
            self.ok.append(cmp.add(req.graph, ans[0], ans[1],
                                   reference.maximum_size(req.graph)))
        self.log(f"reference: {len(answers)} answers checked in "
                 f"{time.perf_counter() - t:.3f} s")

    def latencies(self) -> np.ndarray:
        """Seconds from each request's due time to its answer; a request
        that failed, was refused or answered wrongly counts as answered when
        the wait gave up, later than any served one."""
        out = []
        for req, done, ok in zip(self.requests, self.done, self.ok):
            end = done if (done is not None and ok) else self.given_up
            out.append(end - self.t0 - req.due_s)
        return np.asarray(out)

    @property
    def attempted(self) -> int:
        return len(self.requests)

    @property
    def failed(self) -> int:
        return self.ok.count(False)
