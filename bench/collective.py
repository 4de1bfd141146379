"""The merge of the edge shards: its bytes, its instructions, its time.

``ShardedMatcher`` merges the per-shard winner vectors of each BFS level
with one ``lax.pmin`` under the named scope ``merge_shards``: an all-reduce
of an ``(nr + 1)`` int32 vector over the mesh axis.  This module keeps, with
the benchmark, the least bytes that merge must move
(:func:`allreduce_bytes`), which instructions of a compiled program are the
merge (:func:`merge_instructions`), and their device time in a trace
(:func:`merge_seconds`).  A program without the scope has no merge
instructions, and then nothing here finds anything to read.
"""
from __future__ import annotations

import re
from typing import Iterable, List, Optional

import trace_reduce

SCOPE = "merge_shards"
_OP_NAME = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*"
                      r"metadata=\{op_name=\"([^\"]*)\"")
# the opcode of an asynchronous collective's two halves, as the device
# trace names the whole instruction: ``... all-reduce-start(...)``
_ASYNC = re.compile(r" [\w\-]+-(start|done)\(")


def allreduce_bytes(elements: int, devices: int, itemsize: int = 4) -> float:
    """Bytes each device must send in a ring all-reduce of ``elements``
    values of ``itemsize`` bytes over ``devices`` devices: a reduce-scatter
    and an all-gather, each ``(D - 1) / D`` of the vector."""
    return 2 * (devices - 1) / devices * itemsize * elements


def merge_instructions(hlo_text: str, scope: str = SCOPE) -> List[str]:
    """The instructions of a compiled HLO module (``Compiled.as_text()``)
    whose ``op_name`` lies under ``scope``."""
    out = []
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m and f"/{scope}/" in m.group(2):
            out.append(m.group(1))
    return out


def merge_seconds(trace: dict, names: Iterable[str]) -> Optional[float]:
    """Device seconds of the merge inside the traced window, averaged over
    the devices: the union of the intervals of the instructions ``names``
    (a trace as :func:`trace_reduce.load` returns it).  An asynchronous
    merge counts from its ``-start`` to its ``-done``, so overlapping work
    cannot shorten it.  Nothing where no such instruction ran."""
    names = set(names)
    lo_hi = trace_reduce._window(trace)
    if not names or lo_hi is None or not trace["devices"]:
        return None
    total = 0
    for ops in trace["devices"].values():
        spans, opened = [], None
        for name, s, e in sorted(ops, key=lambda o: o[1]):
            if trace_reduce.instruction(name) not in names:
                continue
            half = _ASYNC.search(name)
            if half is None:
                spans.append((s, e))
            elif half.group(1) == "start":
                opened = s if opened is None else opened
            else:
                spans.append((s if opened is None else opened, e))
                opened = None
        total += sum(e - s for s, e in trace_reduce.clip(
            trace_reduce.union(spans), *lo_hi))
    return total / len(trace["devices"]) / 1e9 if total else None

