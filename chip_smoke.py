#!/usr/bin/env python3
"""Bring-up check of the matching engine on a TPU, through its entry points.

    python chip_smoke.py              # one chip: Matcher, match_many, service
    python chip_smoke.py --chips 4    # ShardedMatcher over four chips

One chip runs three phases:

* ``single``  — ``Matcher.run`` on a 2^20 x 2^20 random graph (~8.4 M edges)
  and a scale-20 Kronecker graph (skewed degrees), the default APFB config
  with the Karp–Sipser warm start, plus APsB-exact on the random graph;
* ``batch``   — one ``match_many`` over a stacked bucket of 8 graphs of the
  four traffic families;
* ``service`` — a ``MatchingService`` on a bucket ladder, warmed up, fed 16
  open-loop requests from the ``repro.launch.serve_matching`` trace.

``--chips 4`` runs only the sharded path and what it is compared with:
``ShardedMatcher`` with each warm start on a 2^21 x 2^21 graph (~16.8 M
edges) against one single-chip ``Matcher`` run on device 0, each run once.

Every matching is checked with ``validate_matching`` and its cardinality
against the scipy Hopcroft–Karp oracle.  Lines before the last are
informational (sizes, compile and solve seconds, persistent-cache events).
The last line is one JSON object, ``{"ok": true, "device": {...}}``.  On any
failure, on a host without a TPU, or outside a checkout of this repository
the script exits non-zero and prints no such line.  It runs in one process
and starts no other.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# The platform the checks run on, and the sizes.  tests/test_chip_smoke.py
# sets these to rehearse the script on the CPU at a tiny size.
PLATFORM = "tpu"
LOG_N = 20              # single: 2^20 vertices per side
AVG_DEG = 8.0           # edges per column
BATCH_N = 1 << 15       # batch: vertices per side of the bucket
BATCH_SIZE = 8
SERVICE_REQUESTS = 16
SERVICE_SIZE = 1024     # traffic family size hint (vertices)
SHARDED_LOG_N = 21      # --chips 4: 2^21 vertices per side
SEED = 0

_EVENTS = {"requests": 0, "hits": 0, "writes": 0, "compile_s": 0.0}


def _on_event(event: str, **_) -> None:
    key = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
           "/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "writes"}.get(event)
    if key:
        _EVENTS[key] += 1


def _on_duration(event: str, secs: float, **_) -> None:
    if event.startswith("/jax/core/compile/"):
        _EVENTS["compile_s"] += secs


def log(msg: str) -> None:
    print(msg, flush=True)


def timed_run(fn, steady: bool = True):
    """First call (trace + compile + solve), then, with ``steady``, a second
    solve; each timed to ``block_until_ready``.  Returns the last result and
    an informational string."""
    import jax
    ev0 = dict(_EVENTS)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    ev = {k: _EVENTS[k] - ev0[k] for k in _EVENTS}
    info = (f"compile_s={ev['compile_s']:.3f} first_call_s={first:.3f} "
            f"persistent_cache(requests={ev['requests']} hits={ev['hits']} "
            f"writes={ev['writes']})")
    if steady:
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        info += f" solve_s={time.perf_counter() - t0:.3f}"
    return out, info


def check_matching(g, cm, rm, opt: int, what: str) -> int:
    from repro.core import validate_matching
    card = validate_matching(g, cm, rm)
    if card != opt:
        raise AssertionError(f"{what}: |M|={card} != oracle {opt}")
    return card


def check_levels(state, what: str) -> int:
    """The solver's BFS level count: at least one level per phase."""
    levels, phases = int(state.levels), int(state.phases)
    if levels < phases:
        raise AssertionError(f"{what}: levels={levels} < phases={phases}")
    return levels


def phase_single() -> None:
    from repro.core import maximum_cardinality
    from repro.graphs import kron_graph, random_bipartite
    from repro.matching import DeviceCSR, Matcher, MatcherConfig

    n = 1 << LOG_N
    graphs = {"random": random_bipartite(n, n, AVG_DEG, seed=SEED),
              "kron": kron_graph(LOG_N, int(AVG_DEG), seed=SEED)}
    apsb = MatcherConfig(algo="apsb", kernel="gpubfs_wr", wr_exact=True)
    runs = (("random", MatcherConfig()), ("kron", MatcherConfig()),
            ("random", apsb))
    oracle = {name: maximum_cardinality(g) for name, g in graphs.items()}
    for name, cfg in runs:
        g = graphs[name]
        matcher = Matcher(cfg, warm_start="karp_sipser")
        graph = DeviceCSR.from_host(g)
        state, timing = timed_run(lambda: matcher.run(graph))
        card = check_matching(g, *state.to_host(), oracle[name], name)
        levels = check_levels(state, name)
        log(f"[single] {name} {g.nc}x{g.nr} nnz={g.nnz} "
            f"{matcher.config.name}+karp_sipser {timing} "
            f"phases={int(state.phases)} levels={levels} |M|={card} "
            f"oracle={oracle[name]}")


def phase_batch() -> None:
    from repro.core import maximum_cardinality
    from repro.launch.serve_matching import build_trace
    from repro.matching import DeviceCSR, Matcher, MatcherConfig
    from repro.matching.device_csr import bucket_nnz

    trace = build_trace(BATCH_SIZE, BATCH_N, SEED)
    cap = bucket_nnz(max(g.nnz for _, g in trace))
    batch = DeviceCSR.stack([
        DeviceCSR.from_host(g).pad_vertices(BATCH_N, BATCH_N).pad_to(cap)
        for _, g in trace])
    matcher = Matcher(MatcherConfig(), warm_start="cheap")
    states, timing = timed_run(lambda: matcher.run_many(batch))
    cms, rms = states.to_host()
    cards = []
    for i, (family, g) in enumerate(trace):
        cards.append(check_matching(g, cms[i][: g.nc], rms[i][: g.nr],
                                    maximum_cardinality(g),
                                    f"batch lane {i} ({family})"))
    log(f"[batch] {BATCH_SIZE} x ({BATCH_N}x{BATCH_N}, nnz_pad={cap}) "
        f"families={[f for f, _ in trace]} {matcher.config.name}+cheap "
        f"{timing} |M|={cards} (all equal to the oracle)")


def phase_service() -> None:
    from repro.core import maximum_cardinality
    from repro.launch.serve_matching import build_trace, replay
    from repro.matching import DeviceCSR, Matcher, MatcherConfig
    from repro.serving import Bucketizer, MatchingService, ladder

    service = MatchingService(
        bucketizer=Bucketizer(ladder(max_vertices=2 * SERVICE_SIZE),
                              oversize="reject", validate=True),
        config=MatcherConfig(), warm_start="cheap", max_batch=8,
        max_delay_ms=2.0)
    try:
        matcher = service.matcher()
        ev0 = dict(_EVENTS)
        report = service.warm_up()
        ev = {k: _EVENTS[k] - ev0[k] for k in _EVENTS}
        log(f"[service] {len(service.bucketizer.buckets)} buckets "
            f"{matcher.config.name}+{service.warm_start} {report}; "
            f"compile_s={ev['compile_s']:.3f} persistent_cache(requests="
            f"{ev['requests']} hits={ev['hits']} writes={ev['writes']})")
        trace = build_trace(SERVICE_REQUESTS, SERVICE_SIZE, SEED)
        futures = replay(service, trace, rate_rps=300.0, seed=SEED)
        errors = []
        for family, g, fut in futures:
            try:
                res = fut.result(timeout=600)
            except Exception as e:          # a failed or shed request
                errors.append(f"{family}: {e!r}")
                continue
            direct = Matcher(service.config, service.warm_start).run(
                DeviceCSR.from_host(g).bucketed())
            card = check_matching(g, *res.matching(), maximum_cardinality(g),
                                  f"service {family}")
            if card != int(direct.cardinality):
                errors.append(f"{family}: service |M|={card} != direct "
                              f"Matcher {int(direct.cardinality)}")
        service.drain()
        snap = service.metrics.snapshot()
    finally:
        service.close()
    bad = {k: snap[k] for k in ("failed", "rejected", "cancelled",
                                "shed_newest", "shed_oldest",
                                "deadline_misses", "quarantined")
           if snap[k]}
    log(f"[service] {snap['completed']}/{snap['submitted']} completed in "
        f"{snap['dispatches']} dispatches, latency p50 "
        f"{snap['latency_p50_ms']:.3f} ms p99 {snap['latency_p99_ms']:.3f} "
        f"ms, failed={snap['failed']} quarantined={snap['quarantined']} "
        f"shed={snap['shed_newest'] + snap['shed_oldest']}")
    if errors or bad or snap["completed"] != len(trace):
        raise AssertionError(f"service: errors={errors} counters={bad} "
                             f"completed={snap['completed']}/{len(trace)}")


def phase_sharded(n_devices: int) -> None:
    import jax
    import numpy as np
    from repro.core import maximum_cardinality
    from repro.graphs import random_bipartite
    from repro.matching import (DeviceCSR, Matcher, MatcherConfig,
                                ShardedMatcher)

    n = 1 << SHARDED_LOG_N
    g = random_bipartite(n, n, AVG_DEG, seed=SEED)
    opt = maximum_cardinality(g)
    mesh = jax.make_mesh((n_devices,), ("data",),
                         devices=jax.devices()[:n_devices])
    sharded = DeviceCSR.from_host(g).shard(mesh, "data")
    jax.block_until_ready(sharded)
    shard_bytes = {s.device.id: s.data.nbytes
                   for s in sharded.ecol.addressable_shards}
    per_shard = sharded.nnz_pad // n_devices * 4
    for d in mesh.devices.flat:
        in_use = (d.memory_stats() or {}).get("bytes_in_use")
        log(f"[sharded] device {d.id}: ecol shard {shard_bytes[d.id]} B, "
            f"bytes_in_use={in_use}")
        if shard_bytes[d.id] != per_shard:
            raise AssertionError(f"device {d.id} holds {shard_bytes[d.id]} "
                                 f"B of ecol, expected {per_shard}")
        if d.platform == "tpu" and not in_use:
            raise AssertionError(f"device {d.id} reports no bytes in use")
    # one run each (no steady repeat): the single-chip solve at this size is
    # the longest step, and its cardinality is what every warm start must hit
    ref_ws = "karp_sipser"
    single = Matcher(MatcherConfig(), warm_start=ref_ws)
    single_graph = DeviceCSR.from_host(g, device=jax.devices()[0])
    ref, timing = timed_run(lambda: single.run(single_graph), steady=False)
    ref_card = check_matching(g, *ref.to_host(), opt, "single-chip")
    log(f"[sharded] single-chip device 0 warm_start={ref_ws} {timing} "
        f"phases={int(ref.phases)} levels={check_levels(ref, 'single-chip')} "
        f"|M|={ref_card} oracle={opt}")
    for ws in (ref_ws, "cheap", "none"):
        sm = ShardedMatcher(mesh, config=MatcherConfig(), warm_start=ws)
        st, timing = timed_run(lambda: sm.run(sharded), steady=False)
        card = check_matching(g, *st.to_host(), opt, f"sharded {ws}")
        same = ""
        if ws == ref_ws:
            same = (" identical_to_single_chip=" + str(np.array_equal(
                np.asarray(ref.cmatch), np.asarray(st.cmatch))))
        log(f"[sharded] {g.nc}x{g.nr} nnz={g.nnz} over {n_devices} devices "
            f"warm_start={ws} {timing} phases={int(st.phases)} "
            f"levels={check_levels(st, f'sharded {ws}')} |M|={card} "
            f"single_chip={ref_card} oracle={opt}{same}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: the sharded path only")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.matching import enable_persistent_compile_cache
    cache_dir = enable_persistent_compile_cache()   # before any compile

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != PLATFORM or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} {PLATFORM} device(s), JAX "
              f"found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 1
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    if args.chips == 4:
        phases = {"sharded": lambda: phase_sharded(4)}
    else:
        phases = {"single": phase_single, "batch": phase_batch,
                  "service": phase_service}
    failed = []
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    try:
        for name, phase in phases.items():
            t0 = time.perf_counter()
            try:
                phase()
            except Exception:
                traceback.print_exc()
                failed.append(name)
            log(f"[{name}] {'FAILED' if name in failed else 'ok'} in "
                f"{time.perf_counter() - t0:.3f} s")
    finally:
        jax.monitoring.unregister_event_listener(_on_event)
        jax.monitoring.unregister_event_duration_listener(_on_duration)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
