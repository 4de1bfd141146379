"""The program's own spans in a traced run, and the device idle time under
them.

The program names its stages with ``jax.profiler.TraceAnnotation`` spans
whose names start ``repro.`` (the service's ``repro.serve.admit``,
``.wait``, ``.flush``, ``.stack``, ``.solve``, ``.resolve``); they lie on
the profiler's host plane, on the clock of the device operations.  A
program without them yields no spans, and the readers built on this module
then report nothing.

:func:`load` reads the newest ``.xplane.pb`` under ``bench/.out/``
(``bench/run.py`` clears the cell's trace directory before each traced run,
so the newest file is this run's): the window and the device operations
through :func:`trace_reduce.load`, and the program's spans.
:func:`idle_under` is the reduction, on plain lists, so that a test can
feed it hand-made spans and operations.
"""
from __future__ import annotations

import glob
import os
from typing import Iterable, List, Optional, Tuple

import trace_reduce

PREFIX = "repro."
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".out")

Span = Tuple[str, float, float]


def newest_xplane(root: str) -> Optional[str]:
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def host_spans(path: str) -> List[Span]:
    """``[(name, start_ns, end_ns)]`` of the ``repro.`` spans on the host
    planes of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def load(root: Optional[str] = None) -> Optional[Tuple[dict, List[Span]]]:
    """``(trace, spans)`` of the newest trace under ``root`` (default
    :data:`OUT`): ``trace_reduce.load``'s device operations and benchmark
    spans, and the program's spans; ``None`` where there is no trace."""
    path = newest_xplane(root or OUT)
    if path is None:
        return None
    return trace_reduce.load(os.path.dirname(path)), host_spans(path)


def _overlap(a: List[trace_reduce.Interval],
             b: List[trace_reduce.Interval]) -> float:
    """Length of the intersection of two sorted unions of intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under(trace: dict, spans: List[Span],
               names: Iterable[str]) -> Optional[float]:
    """Percent of the traced window in which the device is idle and the host
    is inside a span named in ``names``, averaged over the devices as
    ``trace_reduce.reduce`` averages busy time, so it never exceeds the
    window's idle share.  ``None`` where the trace has no window or no
    device, or no such span falls in the window."""
    win = trace_reduce._window(trace)
    if win is None or not trace["devices"] or win[1] <= win[0]:
        return None
    lo, hi = win
    names = set(names)
    under = trace_reduce.union(trace_reduce.clip(
        [(s, e) for n, s, e in spans if n in names], lo, hi))
    if not under:
        return None
    held = sum(e - s for s, e in under)
    idle = 0.0
    for ops in trace["devices"].values():
        busy = trace_reduce.union(trace_reduce.clip(
            [(s, e) for _, s, e in ops], lo, hi))
        idle += held - _overlap(under, busy)
    return 100.0 * idle / len(trace["devices"]) / (hi - lo)
