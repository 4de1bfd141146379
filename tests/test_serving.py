"""repro.serving: bucketizer admission, scheduler policy, AOT warmup, and
service-level cardinality parity with the direct Matcher."""
import dataclasses
import threading

import numpy as np
import pytest

from repro.core import validate_matching
from repro.graphs import (banded, comb_chain, community_graph, grid_graph,
                          kron_graph, mtx_fixture, random_bipartite,
                          scaled_free)
from repro.matching import (DeviceCSR, Matcher, MatcherConfig,
                            compile_cache_clear, compile_cache_info)
from repro.matching.cache import get_compiled, set_max_entries
from repro.serving import (Bucketizer, MatchingService, MicroBatcher,
                           OversizeGraphError, SizeBucket, batch_bucket,
                           batch_ladder, synthetic_bucket_graph)

CFG = MatcherConfig(algo="apfb", kernel="gpubfs_wr")
BUCKET = SizeBucket(256, 256, 2048)


def families():
    """One instance of every corpus generator family standing in for the
    paper's UFL classes, all sized to share one declared bucket."""
    return {
        "random": random_bipartite(200, 180, 3.0, seed=1),
        "kron": kron_graph(7, 6, seed=2),
        "grid": grid_graph(12),
        "free": scaled_free(150, 160, 4.0, seed=3),
        "band": banded(200, band=3, density=0.5, seed=5),
        "community": community_graph(192, 192, blocks=6, avg_deg=3.0, seed=6),
        "comb": comb_chain(96, teeth=16, seed=7),
        "mtx": mtx_fixture(),
    }


def direct_cardinality(g):
    return int(Matcher(CFG, warm_start="cheap").run(
        DeviceCSR.from_host(g).bucketed()).cardinality)


# ---------------------------------------------------------------------------
# Bucketizer: placement, padding, typed rejection
# ---------------------------------------------------------------------------
def test_bucketizer_pads_onto_declared_bucket():
    g = random_bipartite(200, 180, 3.0, seed=1)
    adm = Bucketizer((BUCKET,)).admit(g)
    assert adm.route == "bucket" and adm.bucket == BUCKET
    assert (adm.graph.nc, adm.graph.nr) == (256, 256)
    assert adm.graph.nnz_pad == 2048
    assert (adm.nc, adm.nr, adm.nnz) == (200, 180, g.nnz)
    assert adm.pad_edges == 2048 - g.nnz
    assert adm.pad_vertex_slots == (256 - 200) + (256 - 180)
    # padding vertices are isolated: the maximum matching is unchanged
    st = Matcher(CFG, warm_start="cheap").run(adm.graph)
    assert int(st.cardinality) == direct_cardinality(g)


def test_bucketizer_accepts_device_graph():
    g = random_bipartite(100, 90, 3.0, seed=4)
    adm = Bucketizer((BUCKET,)).admit(DeviceCSR.from_host(g))
    assert adm.bucket == BUCKET and adm.graph.nnz_pad == 2048
    st = Matcher(CFG, warm_start="cheap").run(adm.graph)
    assert int(st.cardinality) == direct_cardinality(g)


def test_bucketizer_oversize_typed_rejection():
    big = random_bipartite(400, 400, 3.0, seed=5)
    with pytest.raises(OversizeGraphError) as ei:
        Bucketizer((BUCKET,)).admit(big)
    assert (ei.value.nc, ei.value.nr) == (400, 400)
    assert ei.value.largest == BUCKET


def test_bucketizer_picks_smallest_fitting_bucket():
    small, large = SizeBucket(128, 128, 1024), SizeBucket(512, 512, 4096)
    bz = Bucketizer((large, small))          # order must not matter
    assert bz.admit(random_bipartite(100, 100, 3.0, seed=6)).bucket == small
    assert bz.admit(random_bipartite(300, 300, 3.0, seed=6)).bucket == large


# ---------------------------------------------------------------------------
# Scheduler: full/deadline/drain policy, AIMD target, batch ladder
# ---------------------------------------------------------------------------
def test_batch_ladder_and_bucket():
    assert batch_ladder(8) == (1, 2, 4, 8)
    assert batch_ladder(6) == (1, 2, 4, 6)
    assert batch_ladder(1) == (1,)
    assert batch_bucket(3, 8) == 4
    assert batch_bucket(5, 6) == 6
    assert batch_bucket(1, 8) == 1
    assert batch_bucket(8, 8) == 8


def test_scheduler_fixed_target_flushes_on_full():
    mb = MicroBatcher(max_batch=4, max_delay_s=1.0, adaptive=False)
    for i in range(3):
        assert mb.add("k", i, now=0.0) is None
    flush = mb.add("k", 3, now=0.0)
    assert flush is not None and flush.reason == "full"
    assert len(flush.items) == 4 and mb.pending == 0


def test_scheduler_deadline_flush_with_fake_clock():
    mb = MicroBatcher(max_batch=4, max_delay_s=0.5, adaptive=False)
    mb.add("k", "a", now=10.0)
    assert mb.due(now=10.4) == []
    assert mb.next_deadline() == 10.5
    (flush,) = mb.due(now=10.5)
    assert flush.reason == "deadline" and len(flush.items) == 1
    assert mb.next_deadline() is None


def test_scheduler_adaptive_target():
    mb = MicroBatcher(max_batch=8, max_delay_s=0.5, adaptive=True)
    assert mb.target("k") == 1
    assert mb.add("k", 0, now=0.0).reason == "full"   # target 1 -> immediate
    assert mb.target("k") == 2                        # doubled
    assert mb.add("k", 1, now=0.0) is None
    assert mb.add("k", 2, now=0.0).reason == "full"
    assert mb.target("k") == 4
    # a deadline flush drops the target straight to the observed size, so
    # sparse traffic goes back to immediate singleton dispatch
    mb.add("k", 3, now=1.0)
    (flush,) = mb.due(now=2.0)
    assert flush.reason == "deadline"
    assert mb.target("k") == 1
    assert mb.add("k", 4, now=3.0).reason == "full"   # no deadline wait


def test_scheduler_drain_flushes_every_key():
    mb = MicroBatcher(max_batch=8, max_delay_s=9.0, adaptive=False)
    mb.add("a", 1, now=0.0)
    mb.add("b", 2, now=0.0)
    flushes = mb.drain()
    assert {f.key for f in flushes} == {"a", "b"}
    assert all(f.reason == "drain" for f in flushes)
    assert mb.pending == 0


# ---------------------------------------------------------------------------
# Service: parity, deadline flush, warmup, oversize routing
# ---------------------------------------------------------------------------
def test_service_parity_across_generator_families():
    fams = families()
    with MatchingService(bucketizer=Bucketizer((BUCKET,)), config=CFG,
                         warm_start="cheap", max_batch=4,
                         max_delay_ms=20.0) as svc:
        svc.warm_up()
        futs = {name: svc.submit(g) for name, g in fams.items()}
        for name, g in fams.items():
            res = futs[name].result(timeout=300)
            assert res.route == "bucket"
            assert res.cardinality == direct_cardinality(g), name
            cm, rm = res.matching()
            assert cm.shape == (g.nc,) and rm.shape == (g.nr,)
            assert validate_matching(g, cm, rm) == res.cardinality
        snap = svc.metrics.snapshot()
    assert snap["completed"] == len(fams)
    assert 1 <= snap["dispatches"] <= len(fams)


@pytest.mark.parametrize("family", sorted(families()))
def test_service_submit_matches_direct_matcher(family):
    """Per-corpus-family: one submit() through the full admission/batching
    path returns exactly the direct Matcher's cardinality and a valid
    matching on the ORIGINAL (unpadded) vertex ranges."""
    g = families()[family]
    with MatchingService(bucketizer=Bucketizer((BUCKET,)), config=CFG,
                         warm_start="cheap", max_batch=2,
                         max_delay_ms=5.0) as svc:
        res = svc.submit(g).result(timeout=300)
    assert res.cardinality == direct_cardinality(g)
    cm, rm = res.matching()
    assert validate_matching(g, cm, rm) == res.cardinality


def test_service_deadline_flush_resolves_single_request():
    g = random_bipartite(128, 128, 3.0, seed=9)
    with MatchingService(bucketizer=Bucketizer((BUCKET,)), config=CFG,
                         warm_start="cheap", max_batch=8, max_delay_ms=30.0,
                         adaptive=False) as svc:
        res = svc.submit(g).result(timeout=300)
        assert res.cardinality == direct_cardinality(g)
        snap = svc.metrics.snapshot()
    # one request against max_batch=8 (fixed target) can only flush via the
    # deadline path
    assert snap["flushes_deadline"] == 1 and snap["flushes_full"] == 0
    assert snap["dispatches"] == 1
    assert res.queue_wait_s >= 0.02                   # waited for the deadline


def test_warmup_makes_first_dispatch_compile_free():
    compile_cache_clear()
    g = random_bipartite(200, 180, 3.0, seed=1)
    with MatchingService(bucketizer=Bucketizer((BUCKET,)), config=CFG,
                         warm_start="cheap", max_batch=4,
                         max_delay_ms=5.0) as svc:
        report = svc.warm_up()
        assert report.cells == len(batch_ladder(4))   # 1 bucket x 1 cfg x 1 ws
        assert report.compiled == report.cells        # cold cache: all built
        misses0 = compile_cache_info()["misses"]
        res = svc.submit(g).result(timeout=300)
        svc.drain()
        snap = svc.metrics.snapshot()
    assert res.cardinality > 0
    # acceptance: a warmed bucket's first dispatch is a pure cache hit
    assert compile_cache_info()["misses"] == misses0
    assert snap["compile_misses"] == 0 and snap["compile_hits"] >= 1
    # warming again is a no-op
    with MatchingService(bucketizer=Bucketizer((BUCKET,)), config=CFG,
                         warm_start="cheap", max_batch=4) as svc2:
        report2 = svc2.warm_up()
    assert report2.compiled == 0 and report2.already == report2.cells


@pytest.mark.parametrize("kernel_cfg", [
    CFG,
    dataclasses.replace(CFG, dirop=True),
], ids=["jnp", "dirop"])
def test_warmup_zero_miss_across_kernel_paths(kernel_cfg):
    """Serving x kernel paths: a service running the push sweep or the
    direction-optimizing config gets a compile-free first dispatch after
    warmup — the warmup grid must cover the config axes, including the
    CSC-mirrored graph shape dirop admissions carry."""
    compile_cache_clear()
    g = random_bipartite(200, 180, 3.0, seed=1)
    with MatchingService(bucketizer=Bucketizer((BUCKET,)), config=kernel_cfg,
                         warm_start="cheap", max_batch=4,
                         max_delay_ms=5.0) as svc:
        report = svc.warm_up()
        assert report.compiled == report.cells      # cold cache: all built
        misses0 = compile_cache_info()["misses"]
        res = svc.submit(g).result(timeout=300)
        svc.drain()
        snap = svc.metrics.snapshot()
    # the zero-miss checks first: direct_cardinality below compiles its own
    # (non-serving) program and must not be counted against the dispatch
    assert compile_cache_info()["misses"] == misses0
    assert snap["compile_misses"] == 0 and snap["compile_hits"] >= 1
    assert res.cardinality == direct_cardinality(g)


def test_dirop_admission_attaches_csc_mirror():
    """A dirop request's admitted graph must carry the mirror (and only
    then), so the dispatched pytree matches the warmed one."""
    bz = Bucketizer((BUCKET,))
    g = random_bipartite(200, 180, 3.0, seed=1)
    assert not bz.admit(g).graph.has_csc
    assert bz.admit(g, csc=True).graph.has_csc
    mirrored = Bucketizer((BUCKET,), build_csc=True).admit(g).graph
    assert mirrored.has_csc and mirrored.bucket_key == BUCKET.key + ("csc",)


def test_service_routes_oversize_to_sharded_matcher():
    import jax
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    big = random_bipartite(320, 320, 3.0, seed=11)
    with MatchingService(
            bucketizer=Bucketizer((BUCKET,), oversize="shard"),
            config=CFG, warm_start="cheap", mesh=mesh) as svc:
        res = svc.submit(big).result(timeout=300)
        snap = svc.metrics.snapshot()
    assert res.route == "sharded" and res.bucket is None
    assert res.cardinality == direct_cardinality(big)
    cm, rm = res.matching()
    assert validate_matching(big, cm, rm) == res.cardinality
    assert snap["sharded"] == 1


def test_service_rejects_oversize_without_mesh():
    big = random_bipartite(320, 320, 3.0, seed=11)
    with MatchingService(bucketizer=Bucketizer((BUCKET,)), config=CFG,
                         warm_start="cheap") as svc:
        with pytest.raises(OversizeGraphError):
            svc.submit(big)
        snap = svc.metrics.snapshot()
    assert snap["rejected"] == 1 and snap["submitted"] == 0


def test_service_cancelled_future_does_not_poison_the_flush():
    """A request cancelled while queued drops out of its flush; the other
    requests in the same batch still resolve normally."""
    g1 = random_bipartite(128, 128, 3.0, seed=13)
    g2 = random_bipartite(130, 130, 3.0, seed=14)
    with MatchingService(bucketizer=Bucketizer((BUCKET,)), config=CFG,
                         warm_start="cheap", max_batch=4, max_delay_ms=60.0,
                         adaptive=False) as svc:
        f1 = svc.submit(g1)
        f2 = svc.submit(g2)
        assert f1.cancel()                     # still queued: cancel wins
        res2 = f2.result(timeout=300)          # deadline flush serves g2
        assert res2.cardinality == direct_cardinality(g2)
    assert f1.cancelled()


def test_service_survives_bad_per_request_warm_start():
    """An invalid override fails in the caller's thread; the flush thread
    stays alive and keeps serving."""
    g = random_bipartite(128, 128, 3.0, seed=12)
    with MatchingService(bucketizer=Bucketizer((BUCKET,)), config=CFG,
                         warm_start="cheap", max_batch=4,
                         max_delay_ms=5.0) as svc:
        with pytest.raises(KeyError):
            svc.submit(g, warm_start="not-a-warm-start")
        res = svc.submit(g).result(timeout=300)      # service still serves
        assert res.cardinality == direct_cardinality(g)


def test_synthetic_bucket_graph_shape():
    g = synthetic_bucket_graph(BUCKET)
    assert g.bucket_key == BUCKET.key and int(g.nnz) == 0


# ---------------------------------------------------------------------------
# Compile cache satellites: evictions, capacity override, thread safety
# ---------------------------------------------------------------------------
def test_cache_eviction_counter_and_capacity_override():
    old = set_max_entries(2)
    try:
        before = compile_cache_info()["evictions"]
        for i in range(4):
            get_compiled(("evict-test", i), lambda: (lambda x: x))
        info = compile_cache_info()
        assert info["entries"] <= 2
        assert info["max_entries"] == 2
        assert info["evictions"] >= before + 2
    finally:
        set_max_entries(old)
    assert compile_cache_info()["max_entries"] == old


def test_cache_concurrent_access_is_consistent():
    info0 = compile_cache_info()
    errors = []

    def worker(tid):
        try:
            for i in range(40):
                get_compiled(("thread-test", tid % 2, i),
                             lambda: (lambda x: x))
        except Exception as e:   # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    info1 = compile_cache_info()
    calls = 4 * 40
    assert (info1["hits"] - info0["hits"]
            + info1["misses"] - info0["misses"]) == calls
