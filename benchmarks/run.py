"""Benchmark harness: one module per paper table/figure + framework benches.

``PYTHONPATH=src python -m benchmarks.run [--scale tiny|small|large]
[--only table1,...]``  prints ``name,...`` CSV rows per bench.

``--json PATH`` additionally records every bench's rows (plus backend/scale
metadata) as a JSON artifact — the schema behind the committed perf baseline
``BENCH_PR7.json`` (``BENCH_PR5.json`` is the prior envelope, kept for
history).  With ``--baseline BASE`` (and BASE present on disk) the
run becomes a perf gate: for the benches in :data:`REGRESSION_BENCHES` each
row's machine-portable ``rel`` column is compared against the baseline row
with the same identity, and the harness exits non-zero on a
>``--tolerance`` (default 20%) regression.

``--update-baseline PATH`` *regenerates* a committed baseline instead of
gating against one: the gated benches re-run ``--runs`` times and each gated
row's ``rel`` is written as the **max envelope** over the runs (the same
discipline the earlier hand-assembled artifacts followed, now mechanical —
never hand-edit a baseline again).  ``--list`` prints the registered
benches.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.matching import enable_persistent_compile_cache

from . import (batch_matching, corpus, fig2_bfs_iters, fig35_speedups,
               perf_matcher, perf_smoke, roofline, serving, sharded_matching,
               table1_variants, table2_hardest, table_init, table_router)

BENCHES = {
    "table1": table1_variants.run,     # paper Table 1
    "table2": table2_hardest.run,      # paper Table 2
    "fig2": fig2_bfs_iters.run,        # paper Figure 2
    "fig35": fig35_speedups.run,       # paper Figures 3-5
    "router": table_router.run,        # framework integration (DESIGN §4)
    "init": table_init.run,            # KS vs cheap init (beyond-paper)
    "perf_matcher": perf_matcher.run,  # matcher hillclimb (docs/architecture.md)
    "perf_smoke": perf_smoke.run,      # level-sweep microbench (perf gate)
    "roofline": roofline.run,          # roofline table (from dry-run artifacts)
    "batch": batch_matching.run,       # match_many serving throughput
    "sharded": sharded_matching.run,   # ShardedMatcher vs single-device sweep
    "serving": serving.run,            # MatchingService open-loop load sweep
    "corpus": corpus.run,              # per-family dirop win/loss + heuristic gate
}

# row sets that feed the --baseline regression gate.  Gated rows must carry
# a `rel` column: time relative to the same-host jnp path, portable across
# machine speeds (absolute ms would flake on slower runners) — and only the
# aggregated sets are gated; per-graph sub-ms detail rows are too noisy.
# corpus.heuristic rows are deterministic modelled rels (no timing at all),
# so an alpha/beta heuristic regression fails the gate exactly like a perf
# regression — run that bench with a much tighter --tolerance than the
# timing-based perf_smoke sets (CI uses separate --only invocations).
# serving.overload_summary gates the overload posture (loss rate past
# saturation as rel); it stays dormant against baselines that predate it
# (no matching rows -> skipped) until the baseline artifact is refreshed.
REGRESSION_BENCHES = ("perf_smoke", "corpus", "serving")
GATED_SETS = ("perf_smoke.solve", "corpus.heuristic",
              "serving.overload_summary")

SCHEMA = "repro-bench/1"


def _records(rows):
    """Bench rows -> (set_name, record) pairs.

    A bench may emit several CSV sections, each opened by its own header
    line (``set_name,col,...``); a header is any row whose trailing field is
    not numeric.  Comment rows (``# ...``) are skipped.
    """
    out = []
    header = None
    for row in rows:
        if row.startswith("#"):
            continue
        parts = row.split(",")
        try:
            float(parts[-1])
        except ValueError:
            header = parts
            continue
        if header is None or len(parts) != len(header):
            continue
        out.append((header[0], dict(zip(header[1:], parts[1:]))))
    return out


def _rel_index(payload, bench):
    """{row identity -> rel} over the gated sets of one bench's rows."""
    out = {}
    for set_name, rec in _records(payload.get("benches", {}).get(bench, [])):
        if set_name not in GATED_SETS or "rel" not in rec:
            continue
        try:
            out[_row_key(set_name, rec)] = float(rec["rel"])
        except ValueError:
            continue
    return out


def _row_key(set_name: str, rec: dict):
    """The gate's row identity: everything but the measured columns."""
    return (set_name,) + tuple(sorted(
        (k, v) for k, v in rec.items()
        if k not in ("ms", "geomean_ms", "rel")))


def envelope_rows(rows_runs):
    """Merge repeated runs of one bench into a max-rel envelope.

    The first run's rows are the template (headers, detail rows, ms values);
    every gated row's ``rel`` — always the trailing field — is replaced by
    the maximum over all runs for that row identity.  Baselines committed
    this way absorb run-to-run noise without a human editing JSON.
    """
    maxima = {}
    for rows in rows_runs:
        for set_name, rec in _records(rows):
            if set_name in GATED_SETS and "rel" in rec:
                try:
                    rel = float(rec["rel"])
                except ValueError:
                    continue
                key = _row_key(set_name, rec)
                maxima[key] = max(maxima.get(key, rel), rel)
    out, header = [], None
    for row in rows_runs[0]:
        parts = row.split(",")
        if row.startswith("#"):
            out.append(row)
            continue
        try:
            float(parts[-1])
        except ValueError:
            header = parts
            out.append(row)
            continue
        if (header and header[0] in GATED_SETS
                and header[-1] == "rel" and len(parts) == len(header)):
            key = _row_key(header[0], dict(zip(header[1:], parts[1:])))
            if key in maxima:
                parts[-1] = f"{maxima[key]:.3f}"
                row = ",".join(parts)
        out.append(row)
    return out


def check_regressions(baseline: dict, payload: dict, tolerance: float):
    """Gated rows regressed by more than ``tolerance`` vs the baseline.

    A baseline with gated rows that matches NOTHING in the new run is itself
    a failure — renamed paths/configs (or a backend change) would otherwise
    turn the gate vacuous and CI silently green.
    """
    failures = []
    for bench in REGRESSION_BENCHES:
        if bench not in payload.get("benches", {}):
            continue          # deselected via --only, not a vacuous gate
        old = _rel_index(baseline, bench)
        new = _rel_index(payload, bench)
        matched = old.keys() & new.keys()
        if old and not matched:
            failures.append(
                f"{bench}: 0 of {len(old)} baseline row identities match "
                f"this run (renamed sets/paths, dropped rel column, or "
                f"backend drift?) — refresh the baseline artifact instead "
                f"of letting the gate go vacuous")
            continue
        for key in sorted(old.keys() - new.keys()):
            # a vanished row could hide an unbounded regression on that path
            failures.append(
                f"{bench}: baseline row {key[0]} {dict(key[1:])} missing "
                f"from this run — renamed/removed paths need a baseline "
                f"refresh, not a silently narrower gate")
        for key in matched:
            if new[key] > old[key] * (1.0 + tolerance):
                failures.append(
                    f"{bench}: {key[0]} {dict(key[1:])} rel "
                    f"{old[key]:.3f} -> {new[key]:.3f} "
                    f"(> {tolerance:.0%} regression)")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="tiny",
                    choices=["tiny", "small", "large"])
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="",
                    help="write the run's rows as a JSON artifact")
    ap.add_argument("--baseline", default="",
                    help="prior --json artifact to gate regressions against "
                         "(skipped when the file does not exist)")
    ap.add_argument("--tolerance", type=float, default=0.20,
                    help="allowed rel-slowdown before the gate fails")
    ap.add_argument("--list", action="store_true",
                    help="print the registered benches and exit")
    ap.add_argument("--update-baseline", default="",
                    help="re-run the gated benches --runs times and write "
                         "this baseline artifact with the max-rel envelope")
    ap.add_argument("--runs", type=int, default=3,
                    help="runs folded into the --update-baseline envelope")
    args = ap.parse_args()
    enable_persistent_compile_cache()
    if args.list:
        for name, fn in BENCHES.items():
            doc = (fn.__module__.replace("benchmarks.", "")
                   + (" [gated]" if name in REGRESSION_BENCHES else ""))
            print(f"{name:14s} {doc}")
        return
    only = set(args.only.split(",")) if args.only else set(BENCHES)
    failures = 0
    results = {}
    for name, fn in BENCHES.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn(args.scale)
            results[name] = rows
            print("\n".join(rows), flush=True)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # keep the harness going; report at exit
            import traceback
            traceback.print_exc()
            print(f"# {name} FAILED: {e}", flush=True)
            failures += 1

    if args.update_baseline and not failures:
        import jax
        envelopes = dict(results)
        for bench in REGRESSION_BENCHES:
            if bench not in results:
                continue
            runs = [results[bench]]
            for i in range(max(0, args.runs - 1)):
                print(f"# {bench} envelope run {i + 2}/{args.runs}",
                      flush=True)
                runs.append(BENCHES[bench](args.scale))
            envelopes[bench] = envelope_rows(runs)
        payload = {"schema": SCHEMA, "backend": jax.default_backend(),
                   "scale": args.scale,
                   "note": (f"max-rel envelope over {args.runs} runs "
                            f"(benchmarks/run.py --update-baseline); gated "
                            f"sets: {', '.join(GATED_SETS)}"),
                   "benches": envelopes}
        with open(args.update_baseline, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote baseline {args.update_baseline}", flush=True)

    if args.json or args.baseline:      # the gate must not no-op without --json
        import jax
        payload = {"schema": SCHEMA, "backend": jax.default_backend(),
                   "scale": args.scale, "benches": results}
        regressions = []
        if args.baseline and os.path.exists(args.baseline):
            with open(args.baseline) as f:
                baseline = json.load(f)
            regressions = check_regressions(baseline, payload,
                                            args.tolerance)
        elif args.baseline:
            # absence is allowed (bootstrap) but must never be silent: a
            # deleted/renamed baseline would otherwise green-light CI with
            # the gate quietly doing nothing
            print(f"# BASELINE MISSING: {args.baseline} not found — "
                  f"regression gate SKIPPED, commit a baseline artifact "
                  f"to arm it", flush=True)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            print(f"# wrote {args.json}", flush=True)
        for r in regressions:
            print(f"# REGRESSION {r}", flush=True)
        failures += len(regressions)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
