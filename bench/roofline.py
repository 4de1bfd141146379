"""Peaks and the bytes the BFS sweep must move: the roofline's yardstick.

No metric reads a roofline share yet: it needs the time of one BFS level,
and the program does not yet name a level in the trace or count levels.
The functions are kept here, with the peaks table, so that the PR that adds
those spans reports ``sweep_roofline`` from this arithmetic, which later
PRs cannot change.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``
    (``bench/peaks.json``); a device that is not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json") from None


def sweep_bytes_per_level(nnz_pad: int, nc: int, nr: int) -> int:
    """Bytes one level of the push sweep (proposals, scatter-min into the
    per-row winners, then the winners folded into the BFS state) must move
    at least: every edge slot's two int32 endpoints read once, the vectors
    the proposals read once (``bfs``, ``root`` per column, ``rmatch`` per
    row), the winner vector written once, and the state the fold updates
    (``bfs``, ``root`` per column; ``pred``, ``rmatch`` per row) read and
    written once.  Gathers that re-read a vector count once: this is the
    floor, not what XLA's scatter moves."""
    edges = 2 * 4 * nnz_pad
    reads = 4 * (2 * (nc + 1) + (nr + 1))
    winner = 4 * (nr + 1)
    fold = 2 * 4 * (2 * (nc + 1) + 2 * (nr + 1))
    return edges + reads + winner + fold


def roofline_share(bytes_moved: float, seconds: float,
                   device_kind: str) -> float:
    """Percent of the HBM roofline: the least time the bytes need at peak
    bandwidth over the time measured.  The sweep does no arithmetic worth
    counting, so bandwidth bounds it."""
    return 100.0 * bytes_moved / (peaks(device_kind)["hbm_bytes_per_s"]
                                  * seconds)
