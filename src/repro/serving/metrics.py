"""Service observability: queue wait, batch occupancy, pad waste, compile
hits, host time per serving stage, and lock-step BFS levels.

All counters live behind one lock (``submit`` threads, the flush thread, and
metric readers race them); latency-shaped series go into bounded reservoirs
so a long-running service reports percentiles at O(1) memory.  Occupancy and
pad waste are the two prices the bucketizer/scheduler pay for bounded
compilation — a deployment watches them to re-size its bucket ladder and
batch targets.

Stage seconds are cumulative host time in each ``repro.serve.<stage>``
profiler span of :mod:`repro.serving.service`, recorded where the span ends;
a stage still open counts up to the snapshot, so the difference of two
snapshots is the time spent in the stage between them (the flush thread
sits in ``wait`` from start-up on).  :data:`STAGE_COUNTERS` maps a stage to
its ``snapshot()`` key.
"""
from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Iterable, List, Sequence


# serving stage (the span ``repro.serve.<stage>``) -> its snapshot() key
STAGE_COUNTERS = {"admit": "admit_s", "wait": "wait_s", "stack": "stack_s",
                  "solve": "batch_solve_s", "resolve": "resolve_s"}


def percentile(xs: Iterable[float], p: float) -> float:
    """Nearest-rank percentile (p in [0, 100]); NaN on an empty series."""
    s: List[float] = sorted(xs)
    if not s:
        return math.nan
    k = max(0, min(len(s) - 1, round(p / 100.0 * (len(s) - 1))))
    return s[k]


class ServiceMetrics:
    """Thread-safe counters for one :class:`MatchingService`."""

    def __init__(self, reservoir: int = 4096):
        self._lock = threading.RLock()   # snapshot() reads the properties
        # request lifecycle
        self.submitted = 0
        self.completed = 0
        self.failed = 0
        self.rejected = 0           # typed admission rejections
        self.sharded = 0            # oversize requests routed to ShardedMatcher
        # fault-tolerance lifecycle (see docs/architecture.md, the
        # degradation ladder): with `pending` these make the flush mix sum
        # to submissions —
        #   submitted == completed + failed + cancelled + shed_oldest
        #                + deadline_misses + pending
        # (shed_newest requests were refused at submit and are NOT in
        # `submitted`, mirroring `rejected`)
        self.cancelled = 0          # futures cancelled before their flush
        self.shed_newest = 0        # submits refused by backpressure
        self.shed_oldest = 0        # queued requests evicted for new ones
        self.deadline_misses = 0    # expired before dispatch, shed at flush
        self.quarantined = 0        # poisoned requests isolated by bisection
        self.restarts = 0           # flush-thread supervisor restarts
        # dispatch accounting (one device dispatch per flush)
        self.dispatches = 0
        self.flushes = {"full": 0, "deadline": 0, "drain": 0}
        self.batch_real = 0         # real requests across all flushes
        self.batch_padded = 0       # padded batch lanes across all flushes
        # pad-waste accounting (admission time)
        self.edges_true = 0
        self.edges_padded = 0
        # compile-cache deltas attributed to dispatches
        self.compile_hits = 0
        self.compile_misses = 0
        # host seconds per serving stage, and batched flushes that resolved
        # (the divisor of the per-flush means; no sharded dispatches, no
        # failed bisection halves)
        self.stage_s = dict.fromkeys(STAGE_COUNTERS, 0.0)
        self._open = {}             # (thread id, stage) -> its start time
        self.batch_flushes = 0
        # lock-step BFS levels: sum over real lanes of the lane's levels,
        # and sum over flushes of real lanes x the deepest real lane's
        self.lane_levels = 0
        self.lane_level_slots = 0
        # latency reservoirs (seconds)
        self.queue_wait_s: deque = deque(maxlen=reservoir)
        self.latency_s: deque = deque(maxlen=reservoir)

    # -- recording ------------------------------------------------------------
    def record_submit(self, nnz: int, nnz_pad: int) -> None:
        with self._lock:
            self.submitted += 1
            self.edges_true += nnz
            self.edges_padded += nnz_pad

    def record_reject(self) -> None:
        with self._lock:
            self.rejected += 1

    def record_sharded(self) -> None:
        with self._lock:
            self.sharded += 1
            self.dispatches += 1

    def record_flush(self, reason: str, real: int, padded: int,
                     hits: int, misses: int, levels: Sequence[int]) -> None:
        """One resolved batched flush; ``levels`` are its real lanes' BFS
        levels."""
        with self._lock:
            self.dispatches += 1
            self.batch_flushes += 1
            self.flushes[reason] = self.flushes.get(reason, 0) + 1
            self.batch_real += real
            self.batch_padded += padded
            self.compile_hits += hits
            self.compile_misses += misses
            self.lane_levels += sum(levels)
            self.lane_level_slots += len(levels) * max(levels)

    def stage_begin(self, stage: str) -> float:
        t = time.perf_counter()
        with self._lock:
            self._open[threading.get_ident(), stage] = t
        return t

    def stage_end(self, stage: str, t0: float, counted: bool = True) -> None:
        """Close the calling thread's ``stage`` begun at ``t0``; an
        uncounted stage (one that raised) adds nothing."""
        t = time.perf_counter()
        with self._lock:
            del self._open[threading.get_ident(), stage]
            if counted:
                self.stage_s[stage] += t - t0

    def record_done(self, queue_wait_s: float, latency_s: float) -> None:
        with self._lock:
            self.completed += 1
            self.queue_wait_s.append(queue_wait_s)
            self.latency_s.append(latency_s)

    def record_failed(self, n: int = 1) -> None:
        with self._lock:
            self.failed += n

    def record_cancelled(self, n: int = 1) -> None:
        with self._lock:
            self.cancelled += n

    def record_shed(self, policy: str, n: int = 1) -> None:
        with self._lock:
            if policy == "reject-newest":
                self.shed_newest += n
            else:
                self.shed_oldest += n

    def record_deadline_miss(self, n: int = 1) -> None:
        with self._lock:
            self.deadline_misses += n

    def record_quarantined(self, n: int = 1) -> None:
        with self._lock:
            self.quarantined += n

    def record_restart(self) -> None:
        with self._lock:
            self.restarts += 1

    # -- reading --------------------------------------------------------------
    @property
    def occupancy(self) -> float:
        """Real requests per padded batch lane, over all flushes."""
        with self._lock:
            return self.batch_real / max(1, self.batch_padded)

    @property
    def pad_edge_waste(self) -> float:
        """Fraction of admitted edge slots that are padding."""
        with self._lock:
            return 1.0 - self.edges_true / max(1, self.edges_padded)

    def snapshot(self) -> dict:
        """One consistent host-side view of every counter."""
        with self._lock:
            qs, ls = list(self.queue_wait_s), list(self.latency_s)
            now = time.perf_counter()
            stage_s = dict(self.stage_s)
            for (_, stage), t0 in self._open.items():
                stage_s[stage] += now - t0
            return {
                "submitted": self.submitted,
                "completed": self.completed,
                "failed": self.failed,
                "rejected": self.rejected,
                "sharded": self.sharded,
                "cancelled": self.cancelled,
                "shed_newest": self.shed_newest,
                "shed_oldest": self.shed_oldest,
                "deadline_misses": self.deadline_misses,
                "quarantined": self.quarantined,
                "restarts": self.restarts,
                "dispatches": self.dispatches,
                "flushes_full": self.flushes.get("full", 0),
                "flushes_deadline": self.flushes.get("deadline", 0),
                "flushes_drain": self.flushes.get("drain", 0),
                "batch_real": self.batch_real,
                "batch_padded": self.batch_padded,
                "occupancy": self.occupancy,
                "pad_edge_waste": self.pad_edge_waste,
                "compile_hits": self.compile_hits,
                "compile_misses": self.compile_misses,
                "queue_wait_p50_ms": percentile(qs, 50) * 1e3,
                "latency_p50_ms": percentile(ls, 50) * 1e3,
                "latency_p99_ms": percentile(ls, 99) * 1e3,
                **{key: stage_s[stage]
                   for stage, key in STAGE_COUNTERS.items()},
                "batch_flushes": self.batch_flushes,
                "lane_levels": self.lane_levels,
                "lane_level_slots": self.lane_level_slots,
            }
