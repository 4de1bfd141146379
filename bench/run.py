#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout.  It names a configuration (``bench/configs/<config>.json``: the
entry point, the matcher settings, the warm start, the service settings)
and a traffic mix (``bench/traffic/<traffic>.json``, read by
``bench/traffic/generate.py``).  The configuration's ``entry`` names the
driver of the system under test, ``bench/entries/<entry>.py``; each metric
is read by ``bench/metrics/<metric>.py``.  A later cell, configuration,
mix or metric is a new file, found by its name.

One run, in one process: set-up (the inputs from the seed, the programs
compiled or loaded from the persistent cache in ``.jax_cache/`` at the
root of the checkout), then a window of ``--seconds``, then a check of
every result of the window against the reference (``bench/reference.py``).
With ``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer ones (an entry may trace a shorter window at the same
load, ``TRACE_WINDOW_S``).  Lines before the last are informational; the
compared numbers and their limits are the last lines on standard error.
The last line on standard output is one JSON object.

Exits 1, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for; exits 2 where the checkout lacks the program (``src/repro``)
or a file the cell names.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PLATFORM = "tpu"


def process_age_s() -> float:
    """Seconds since this process started (``/proc``), so that set-up
    counts the interpreter's start and the imports too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


STARTED = time.perf_counter() - process_age_s()


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - STARTED:8.3f}] {msg}", flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(spec: dict, cell: str, traced: bool) -> list:
    """The metrics this cell reports: with ``--trace 0`` its end-to-end
    ones, with ``--trace 1`` its per-layer ones, by the contract's rules."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


class CompileCounter:
    """Compiles and persistent-cache events, from ``jax.monitoring``."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_writes"}

    def __init__(self):
        self.counts = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
                       "cache_writes": 0}

    def on_event(self, event: str, **_) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1
            self.counts["compile_s"] += secs

    def snapshot(self) -> dict:
        return dict(self.counts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell = next(w for w in spec["workloads"]
                    if w["name"] == args.workload)
        config = load_json(os.path.join(BENCH, "configs",
                                        f"{cell['config']}.json"))
    except (OSError, StopIteration, KeyError, ValueError) as e:
        print(f"bench: no cell {args.workload!r}: {e!r}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program under {ROOT}/src; run from a checkout of "
              "the repository", file=sys.stderr)
        return 2
    # the compile cache lives inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

    import jax
    devices = jax.devices()
    if devices[0].platform != PLATFORM or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} {PLATFORM} "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s)", file=sys.stderr)
        return 1
    return run_cell(spec, cell, config, args, devices[:cell["chips"]])


def run_cell(spec: dict, cell: dict, config: dict, args, devices) -> int:
    """Everything after the look for a chip: set-up, window, check, line."""
    import jax
    import reference
    from traffic import generate
    import trace_reduce

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    jax.monitoring.register_event_listener(compiles.on_event)
    jax.monitoring.register_event_duration_secs_listener(compiles.on_duration)
    dev = devices[0]
    log(f"cell {cell['name']}: config {cell['config']} "
        f"(entry {config['entry']}), traffic {cell['traffic']}, seed "
        f"{args.seed}, {args.seconds:g} s, trace {args.trace}; device "
        f"{dev.platform} {dev.device_kind} x{len(devices)}, jax "
        f"{jax.__version__}")
    mix = generate.load(cell["traffic"])
    entry = load_module(os.path.join(BENCH, "entries",
                                     f"{config['entry']}.py"),
                        f"bench_entry_{config['entry']}")
    traced = bool(args.trace)
    # an entry whose device trace grows with its load traces a shorter
    # window, at the same load (a few seconds of events is what the
    # profiler and the reduction hold)
    seconds = args.seconds
    if traced and getattr(entry, "TRACE_WINDOW_S", None):
        seconds = min(seconds, entry.TRACE_WINDOW_S)
    runner = entry.Cell(config, mix, args.seed, seconds, log)

    trace_dir = os.path.join(BENCH, ".out", f"trace-{cell['name']}")
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    before = compiles.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - STARTED
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        runner.window(seconds)
    t1 = time.perf_counter()
    in_window = {k: compiles.snapshot()[k] - before[k] for k in before}
    runner.settle()
    if traced:
        jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"window {t1 - t0:.3f} s, {runner.attempted} attempted; compiles in "
        f"the window {in_window['compiles']} (cache hits "
        f"{in_window['cache_hits']}); set-up {setup_s:.3f} s with "
        f"{before['compiles']} compiles ({before['compile_s']:.3f} s), "
        f"{before['cache_hits']} persistent-cache hits, "
        f"{before['cache_writes']} writes")

    ctx = {"setup_s": setup_s, "window_s": t1 - t0, "seconds": seconds}
    ctx.update(runner.counters(traced))
    cmp = reference.Comparison()
    runner.compare(cmp)
    ctx.update(runner.outcomes())
    if traced:
        t = time.perf_counter()
        ctx["trace"] = trace_reduce.reduce(trace_reduce.load(trace_dir),
                                           ctx.get("op_kinds"))
        log(f"trace read in {time.perf_counter() - t:.3f} s: busy "
            f"{ctx['trace']['busy_s']:.6f} s of a {ctx['trace']['window_s']:.6f}"
            f" s window; {ctx['trace']['summary']}")

    metrics = {}
    for m in cell_metrics(spec, cell["name"], traced):
        reader = load_module(os.path.join(BENCH, "metrics",
                                          f"{m['name']}.py"),
                             f"bench_metric_{m['name'].replace('.', '_')}")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": cmp.correct, "attempted": runner.attempted,
              "failed": runner.failed,
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = ctx["trace"]["breakdown"]
    result["checks"] = cmp.report()
    for name, c in cmp.report().items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
