"""The merge's share of its roofline: the least time the window's merges
need at the chip's interconnect peak (the bytes a ring all-reduce of the
winner vector sends per device, ``collective.allreduce_bytes``, times the
BFS levels of the window's solves) over their device time in the trace,
from each ``-start`` to its ``-done`` where the merge is asynchronous."""

import roofline


def read(ctx: dict):
    seconds = ctx.get("merge_s")
    if not seconds or "merge_bytes" not in ctx:
        return None
    ici_bytes_per_s = roofline.peaks(ctx["device_kind"])["ici_bits_per_s"] / 8
    return 100.0 * ctx["merge_bytes"] / ici_bytes_per_s / seconds
