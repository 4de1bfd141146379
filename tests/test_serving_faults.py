"""Chaos matrix for the fault-tolerant serving stack.

Every rung of the failure model / degradation ladder documented in
``docs/architecture.md`` — validate, quarantine, shed, degrade, restart —
driven deterministically through :class:`repro.serving.FaultInjector`:

* poisoned-batch bisection isolates exactly the bad request (innocents
  succeed; the poison fails with the real error + a quarantine artifact);
* a transient dispatch fault is absorbed by the singleton retry;
* flush-thread death -> supervisor fails the in-flight futures with
  :class:`FlushThreadDiedError`, restarts the thread, later submits served;
* deadline-miss shedding at flush time; bounded-queue shed policies
  (reject-newest backpressure / reject-oldest eviction) under sustained
  overload, with the metrics sum invariant
  ``submitted == completed + failed + cancelled + shed_oldest +
  deadline_misses`` holding throughout;
* ``max_phases`` degradation returns a valid *maximal* matching with
  ``certified == False`` and a full-budget rerun matches the
  Hopcroft-Karp oracle — also sweepable over every registered solve path
  via the corpus harness's ``oracle="maximal"`` mode;
* ``close()`` never strands a future (pending requests fail with
  :class:`ServiceClosedError`).
"""
import dataclasses
import json
import os

import pytest

from repro.core import is_maximal, validate_matching
from repro.core.oracles import hopcroft_karp
from repro.graphs import random_bipartite
from repro.matching import GraphValidationError, MatcherConfig
from repro.serving import (Bucketizer, DeadlineExceededError, FaultInjector,
                           FlushThreadDiedError, MatchingService,
                           PoisonedGraphFault, QueueFullError,
                           ServiceClosedError, SheddedError, SizeBucket)

CFG = MatcherConfig(algo="apfb", kernel="gpubfs_wr", schedule="ct")
BUCKET = SizeBucket(256, 256, 2048)


def graphs(n, seed0=100):
    return [random_bipartite(180 + i, 170 + i, 3.0, seed=seed0 + i)
            for i in range(n)]


def make_service(**kw):
    kw.setdefault("bucketizer", Bucketizer((BUCKET,), validate=True))
    kw.setdefault("config", CFG)
    kw.setdefault("warm_start", "cheap")
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_delay_ms", 60.0)
    kw.setdefault("adaptive", False)
    kw.setdefault("supervisor_interval_s", 0.02)
    return MatchingService(**kw)


def check_sum_invariant(snap):
    """Every accepted request is accounted for exactly once."""
    assert snap["submitted"] == (snap["completed"] + snap["failed"]
                                 + snap["cancelled"] + snap["shed_oldest"]
                                 + snap["deadline_misses"]), snap


# ---------------------------------------------------------------------------
# validate: structural admission checks
# ---------------------------------------------------------------------------
def test_admission_rejects_structurally_corrupt_graph():
    g = graphs(1)[0]
    bad_cadj = g.cadj.copy()
    bad_cadj[0] = g.nr + 5                       # row endpoint out of range
    bad = dataclasses.replace(g, cadj=bad_cadj)
    with make_service() as svc:
        with pytest.raises(GraphValidationError) as ei:
            svc.submit(bad)
        assert any("cadj" in p for p in ei.value.problems)
        res = svc.submit(g).result(timeout=300)  # service unharmed
        snap = svc.metrics.snapshot()
    assert res.cardinality > 0
    assert snap["rejected"] == 1 and snap["submitted"] == 1


# ---------------------------------------------------------------------------
# quarantine: bisection isolates the poison, innocents succeed
# ---------------------------------------------------------------------------
def test_bisection_isolates_exactly_the_poisoned_request(tmp_path):
    faults = FaultInjector(seed=3)
    faults.poison("bad")
    gs = graphs(4)
    with make_service(faults=faults, quarantine_dir=str(tmp_path)) as svc:
        futs = [svc.submit(g, tag="bad" if i == 2 else f"ok{i}")
                for i, g in enumerate(gs)]
        svc.drain()
        snap = svc.metrics.snapshot()
    for i, fut in enumerate(futs):
        if i == 2:
            continue
        res = fut.result(timeout=300)            # innocents all served
        cm, rm = res.matching()
        assert validate_matching(gs[i], cm, rm) == res.cardinality
    exc = futs[2].exception(timeout=300)
    assert isinstance(exc, PoisonedGraphFault) and exc.tag == "bad"
    # the isolated request left a replayable artifact
    art = exc.quarantine_artifact
    assert art and os.path.exists(art)
    with open(art) as f:
        payload = json.load(f)
    assert payload["schema"] == "repro-serving-quarantine/1"
    assert payload["tag"] == "bad"
    assert payload["nnz"] == len(payload["edges"]) == gs[2].nnz
    assert snap["quarantined"] == 1 and snap["failed"] == 1
    assert snap["completed"] == 3
    check_sum_invariant(snap)


def test_singleton_retry_absorbs_transient_fault():
    faults = FaultInjector(seed=4)
    faults.script(RuntimeError("transient device hiccup"))
    g = graphs(1)[0]
    with make_service(faults=faults, max_delay_ms=5.0,
                      dispatch_retries=2) as svc:
        res = svc.submit(g).result(timeout=300)
        snap = svc.metrics.snapshot()
    assert res.cardinality > 0
    assert snap["quarantined"] == 0 and snap["failed"] == 0
    assert faults.injected == 1                  # the fault did fire


@pytest.mark.parametrize("drop", ["cancelled", "expired"])
def test_claim_cut_to_a_singleton_keeps_its_retry(drop):
    # a flush of two that the claim cuts to one request is a singleton:
    # its transient fault is retried, not quarantined
    faults = FaultInjector(seed=8)
    g1, g2 = graphs(2)
    with make_service(faults=faults) as svc:     # 60ms delay: one flush
        gone = svc.submit(g1, deadline_s=0.0 if drop == "expired" else None)
        if drop == "cancelled":
            assert gone.cancel()
        faults.script(RuntimeError("transient device hiccup"))
        res = svc.submit(g2).result(timeout=300)
        snap = svc.metrics.snapshot()
    assert res.cardinality > 0 and res.batch_size == 1
    assert snap["quarantined"] == 0 and snap["failed"] == 0
    assert faults.injected == 1
    check_sum_invariant(snap)


# ---------------------------------------------------------------------------
# restart: flush-thread death -> supervisor fail-over + restart
# ---------------------------------------------------------------------------
def test_thread_death_supervisor_restarts_and_serves():
    faults = FaultInjector(seed=5)
    gs = graphs(4)
    with make_service(faults=faults) as svc:
        faults.kill_thread_after(0)              # very next dispatch dies
        futs = [svc.submit(g) for g in gs]
        excs = [f.exception(timeout=300) for f in futs]
        died = [e for e in excs if isinstance(e, FlushThreadDiedError)]
        assert died, excs                        # in-flight failed over
        assert all(e is None or isinstance(e, FlushThreadDiedError)
                   for e in excs)
        res = svc.submit(gs[0]).result(timeout=300)   # post-restart service
        snap = svc.metrics.snapshot()
    assert res.cardinality > 0
    assert snap["restarts"] >= 1 and faults.kills == 1
    check_sum_invariant(snap)


def test_close_fails_pending_futures_when_thread_is_dead():
    faults = FaultInjector(seed=6)
    gs = graphs(2)
    svc = make_service(faults=faults, supervise=False)   # nobody restarts
    faults.kill_thread_after(0)
    futs = [svc.submit(g) for g in gs[:2]]
    svc.flush()
    svc._thread.join(timeout=60)                 # let the injected crash land
    assert not svc._thread.is_alive()
    svc.close()                                  # must not strand the futures
    excs = [f.exception(timeout=60) for f in futs]
    assert all(isinstance(e, ServiceClosedError) for e in excs), excs


# ---------------------------------------------------------------------------
# shed: deadlines and bounded-queue policies under overload
# ---------------------------------------------------------------------------
def test_deadline_miss_is_shed_at_flush_time():
    g1, g2 = graphs(2)
    with make_service(max_delay_ms=5.0) as svc:
        late = svc.submit(g1, deadline_s=0.0)    # expired before any flush
        ok = svc.submit(g2)
        res = ok.result(timeout=300)
        snap = svc.metrics.snapshot()
    assert isinstance(late.exception(timeout=300), DeadlineExceededError)
    assert res.cardinality > 0
    assert snap["deadline_misses"] == 1
    check_sum_invariant(snap)


@pytest.mark.parametrize("policy", ["reject-newest", "reject-oldest"])
def test_shed_policy_under_sustained_overload(policy):
    faults = FaultInjector(seed=7, latency_s=0.08)   # slow device
    gs = graphs(8)
    refused = 0
    futs = []
    with make_service(faults=faults, max_batch=1, max_delay_ms=1.0,
                      max_queue=2, shed_policy=policy) as svc:
        for g in gs:
            try:
                futs.append(svc.submit(g))
            except QueueFullError:
                refused += 1
        svc.drain()
        snap = svc.metrics.snapshot()
    excs = [f.exception(timeout=300) for f in futs]
    evicted = sum(isinstance(e, SheddedError) for e in excs)
    assert all(e is None or isinstance(e, SheddedError) for e in excs), excs
    if policy == "reject-newest":
        assert refused >= 1 and refused == snap["shed_newest"]
        assert evicted == 0 and snap["shed_oldest"] == 0
        assert snap["submitted"] == len(futs)
    else:
        assert refused == 0 and snap["shed_newest"] == 0
        assert evicted >= 1 and evicted == snap["shed_oldest"]
        assert snap["submitted"] == len(gs)
    check_sum_invariant(snap)


def test_cancelled_future_is_counted():
    g1, g2 = graphs(2)
    with make_service() as svc:                  # 60ms delay: stays queued
        f1 = svc.submit(g1)
        f2 = svc.submit(g2)
        assert f1.cancel()
        assert f2.result(timeout=300).cardinality > 0
        svc.drain()
        snap = svc.metrics.snapshot()
    assert snap["cancelled"] == 1 and snap["completed"] == 1
    check_sum_invariant(snap)


# ---------------------------------------------------------------------------
# degrade: phase budget -> valid maximal matching, certified=False
# ---------------------------------------------------------------------------
def test_phase_budget_degrades_to_certified_false_maximal():
    g = random_bipartite(220, 200, 3.0, seed=42)
    budget = dataclasses.replace(CFG, max_phases=1, degrade_maximal=True)
    with make_service(max_delay_ms=5.0) as svc:
        degraded = svc.submit(g, config=budget, warm_start="none"
                              ).result(timeout=300)
        full = svc.submit(g).result(timeout=300)
    assert not degraded.certified                # budget truncated the solve
    cm, rm = degraded.matching()
    card = validate_matching(g, cm, rm)          # still a valid matching...
    assert is_maximal(g, cm, rm)                 # ...and maximal (>= M*/2)
    assert card == degraded.cardinality
    # the full-budget rerun certifies and matches the host HK oracle
    assert full.certified
    hk_cm, hk_rm = hopcroft_karp(g)
    assert full.cardinality == validate_matching(g, hk_cm, hk_rm)
    assert card <= full.cardinality
    assert 2 * card >= full.cardinality          # the maximal-matching bound


def test_corpus_harness_maximal_oracle_under_phase_budget(tmp_path):
    """Every registered solve path stays valid + maximal at max_phases=1
    (the acceptance sweep; CI's chaos-smoke job runs a bigger budget)."""
    from repro.corpus.verify import verify_corpus
    base = MatcherConfig(max_phases=1, degrade_maximal=True)
    rep = verify_corpus(scale="mini", budget=6, rcp=False, minimize=False,
                        base=base, oracle="maximal",
                        artifact_dir=str(tmp_path))
    assert not rep.failures, rep.summary()
