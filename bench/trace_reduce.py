"""Reduce a profiler trace to device busy and idle time and op classes.

Input: the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes under
``<dir>/plugins/profile/<time>/``, read with ``jax.profiler.ProfileData``.

* Device planes are the ones named ``/device:TPU:<n>``; the operations are
  the events of their ``XLA Ops`` line.  On the v5e an event's name is the
  whole HLO instruction (``%fusion.195 = s32[1048577]{...} fusion(...),
  kind=kCustom, calls=%fused_computation.97``); a ``while`` or
  ``conditional`` event spans the operations of its body.
* The host plane ``/host:CPU`` carries the benchmark's own spans
  (``jax.profiler.TraceAnnotation``, names starting ``bench.``); the one
  named :data:`WINDOW_SPAN` bounds the measured window.  Device and host
  events share one clock in the trace.

``busy_s`` is the union of the intervals in which an operation ran, inside
the window, averaged over the devices; ``window_s`` the window's length.
An operation is named by its instruction (``fusion.195``) and classed by
:func:`op_class`: the chip runs XLA's gathers and scatters as ``kCustom``
fusions whose names do not say which, so the class comes from the compiled
program's HLO text where the entry supplies it (:func:`op_kinds`), else
from the instruction's name.  Loop and branch events, which span their
bodies, count towards busy time but not towards any class.  Each idle gap
of the device is put down to the benchmark span, other than the window,
that covers at least half of it (``bench.solve``, ``bench.submit``,
``bench.drain``), else to ``host``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir: str) -> dict:
    """Device operations and host spans of the newest trace in a directory,
    as plain lists: ``{"devices": {plane: [(name, start_ns, end_ns)]},
    "spans": [(name, start_ns, end_ns)]}``."""
    import jax
    data = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    devices: Dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


CONTAINERS = ("while", "conditional", "call")
_CONTAINER = re.compile(r"[)}\]] (while|conditional|call)\(")
_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_OP_NAME = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*"
                      r"metadata=\{op_name=\"([^\"]*)\"")


def instruction(name: str) -> str:
    """``fusion.195`` of ``%fusion.195 = s32[...] fusion(...), ...``."""
    m = _INSTR.match(name)
    return m.group(1) if m else name


def op_kinds(hlo_text: str) -> Dict[str, str]:
    """``{instruction: "scatter" | "gather"}`` for a compiled HLO module
    (``Compiled.as_text()``): every instruction whose JAX op name (the last
    part of its ``op_name`` metadata, e.g. ``.../while/body/scatter-min``)
    is a scatter or a gather, fused or not."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            op = m.group(2).rsplit("/", 1)[-1]
            for kind in ("scatter", "gather"):
                if op.startswith(kind):
                    out[m.group(1)] = kind
    return out


def op_class(name: str, kinds: Optional[Dict[str, str]] = None) -> str:
    """An operation's class: its kind in ``kinds`` where known, else its
    instruction name without the numeric suffix (``scatter.12`` reads
    ``scatter``, ``copy-start.4`` reads ``copy-start``)."""
    ins = instruction(name)
    if kinds and ins in kinds:
        return kinds[ins]
    m = _CONTAINER.search(name)
    if m:
        return m.group(1)
    base = re.sub(r"(\.\d+)+$", "", ins).lower()
    for cls in ("scatter", "gather") + CONTAINERS:
        if base.startswith(cls):
            return cls
    return base or "unnamed"


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _window(trace: dict) -> Optional[Interval]:
    spans = [(s, e) for n, s, e in trace["spans"] if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ops = [(s, e) for v in trace["devices"].values() for _, s, e in v]
    if not ops:
        return None
    return min(s for s, _ in ops), max(e for _, e in ops)


def reduce(trace: dict, kinds: Optional[Dict[str, str]] = None,
           top: int = 10) -> dict:
    """``busy_s``, ``window_s``, ``class_s`` (device seconds per op class,
    summed over devices and divided by their number), and the
    ``breakdown`` of the result line: the ``top`` operations by device time
    (``instruction:class``) and the ``top`` longest idle causes."""
    win = _window(trace)
    if win is None or not trace["devices"]:
        return {"busy_s": 0.0, "window_s": 0.0, "class_s": {},
                "breakdown": {"device_ops": [], "idle_gaps": []},
                "summary": "no device operations in the trace"}
    lo, hi = win
    ndev = len(trace["devices"])
    busy_ns = 0
    class_ns: Dict[str, int] = defaultdict(int)
    op_ns: Dict[str, int] = defaultdict(int)
    gaps: Dict[str, int] = defaultdict(int)
    spans = [(n, s, e) for n, s, e in trace["spans"] if n != WINDOW_SPAN]
    n_ops = 0
    for ops in trace["devices"].values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        n_ops += len(inside)
        for n, s, e in inside:
            cls = op_class(n, kinds)
            if cls in CONTAINERS:
                continue
            class_ns[cls] += e - s
            op_ns[f"{instruction(n)}:{cls}"] += e - s
        busy = union([(s, e) for _, s, e in inside])
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps[_cause(spans, gs, ge)] += ge - gs
    window_s = (hi - lo) / 1e9
    busy_s = busy_ns / ndev / 1e9
    ranked = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_s, "window_s": window_s,
        "class_s": {k: v / ndev / 1e9 for k, v in class_ns.items()},
        "breakdown": {
            "device_ops": [[k, v / ndev / 1e9] for k, v in ranked],
            "idle_gaps": [[k, v / ndev / 1e9] for k, v in idle]},
        "summary": f"{n_ops} device operations on {ndev} device(s)",
    }


def _cause(spans, gs: int, ge: int) -> str:
    """The benchmark span that covers most of the gap ``[gs, ge)``, where
    one covers at least half of it; else ``host`` (the host in none of the
    benchmark's calls: waiting for an arrival, or its own work)."""
    best, best_ns = "host", (ge - gs) / 2
    for n, s, e in spans:
        cover = min(e, ge) - max(s, gs)
        if cover >= best_ns:
            best, best_ns = n, cover
    return best


def idle_share(ctx: dict) -> Optional[float]:
    """Percent of the traced window in which no operation ran."""
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def class_share(ctx: dict, cls: str) -> Optional[float]:
    """Percent of device busy time spent in operations of class ``cls``;
    nothing where the trace holds none."""
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0 or not t["class_s"].get(cls):
        return None
    return 100.0 * t["class_s"][cls] / t["busy_s"]
